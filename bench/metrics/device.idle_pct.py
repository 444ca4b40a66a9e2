"""device.idle_pct: the share of the traced window in which no operation
ran on the device, from the profiler trace (``bench/devtrace.py``)."""


def install(run):
    pass


def read(run):
    t = run.trace_data
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
