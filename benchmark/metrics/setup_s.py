"""setup_s: process start to the first timed request (JAX and chip
start-up, bank build, compile or cache replay, request pool, warm-up)."""


def read(run):
    return run.setup_s
