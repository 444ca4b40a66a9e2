"""device.idle_in_walk_pct: the share of the traced window in which the
host is inside a decision-walk call (the program's ``palp.walk`` span)
and no operation runs on the device, from the profiler trace.  The
window is the one ``bench/devtrace.py`` takes, from the first ``serve``
span's start to the last one's end; with several device planes the
share is their mean."""

from __future__ import annotations

from pathlib import Path

import devtrace
import hostprofile

WALK = "palp.walk"


def install(run):
    # the profile makes the program write its palp.walk spans
    hostprofile.install(run)


def load(tdir: Path) -> tuple[dict, list, list]:
    """(device events by plane, ``serve`` spans, ``palp.walk`` spans) of
    the newest trace under ``tdir``; an event is (name, start_ns,
    duration_ns), a span (start_ns, end_ns)."""
    from jax.profiler import ProfileData

    files = sorted(Path(tdir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {}, [], []
    prof = ProfileData.from_file(str(files[-1]))
    device: dict = {}
    serve: list = []
    walk: list = []
    for plane in prof.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "serve":
                        serve.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name == WALK:
                        walk.append((e.start_ns, e.start_ns + e.duration_ns))
    return device, serve, walk


def _clip(intervals, w0, w1) -> list:
    return devtrace.merge([(max(s, w0), min(e, w1)) for s, e in intervals
                           if min(e, w1) > max(s, w0)])


def _overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_in_walk_pct(device: dict, serve: list, walk: list):
    """The reduction, on events as :func:`load` gives them."""
    if not serve or not walk:
        return None
    w0 = min(s for s, _ in serve)
    w1 = max(e for _, e in serve)
    if w1 <= w0:
        return None
    walking = _clip(walk, w0, w1)
    in_walk = sum(e - s for s, e in walking)
    planes = list(device.values()) or [[]]
    idle = 0.0
    for evs in planes:
        busy = _clip([(s, s + d) for _, s, d in evs], w0, w1)
        idle += in_walk - _overlap_ns(walking, busy)
    return 100.0 * idle / len(planes) / (w1 - w0)


def read(run):
    import harness

    if run.state.get(hostprofile.KEY) is None:
        return None
    return idle_in_walk_pct(*load(harness.ROOT / ".bench_trace"))
