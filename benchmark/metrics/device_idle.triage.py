"""device_idle.triage: 1 − device busy ÷ traced window (trace reduction)."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
