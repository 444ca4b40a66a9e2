"""walk.overlap_pct: the share of decision walks begun ahead of the call
that consumes them, so that their round trip runs under another engine's
walk (``kernels/decision_walk/ops.py`` ``start_walk``): the program's
``palp.walk.overlapped`` counter over its ``palp.walk`` calls, in
percent.  A program that does not register the counter reads nothing."""

import hostprofile

#: the program's counter, spelled here so that a program without it
#: still loads this reader
OVERLAPPED = "palp.walk.overlapped"


def install(run):
    hostprofile.install(run)


def read(run):
    from repro.core import obs

    prof, n = hostprofile.walks(run)
    if not n or OVERLAPPED not in getattr(obs, "REGISTERED_NAMES", ()):
        return None
    return 100.0 * prof.counters.get(OVERLAPPED, 0) / n
