"""mine.window_share_pct: the share of the traced window's wall time
spent inside mining rounds (the program's ``palp.mine`` spans), the
window as ``bench/devtrace.py`` takes it."""

import mineprofile


def install(run):
    mineprofile.install(run)


def read(run):
    prof, n = mineprofile.rounds(run)
    t = run.trace_data
    if not n or not t or t["window_s"] <= 0:
        return None
    return 100.0 * prof.seconds.get(mineprofile.MINE, 0.0) / t["window_s"]
