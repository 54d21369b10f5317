"""lock_wait_s_per_mline.bulk: engine seconds waiting for state_lock
before finalize, per million lines."""


def read(run):
    return run.per_mline(run.phase_s("lock_wait"))
