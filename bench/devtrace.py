"""Reduction of a JAX profiler trace to the device's busy time, each
operation's device time, and the idle gaps by what the host was doing.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  The host's activity comes from the
``jax.profiler.TraceAnnotation`` spans the harness writes (``serve``
around each client call, ``decide`` around each decision engine's
call); the traced window runs from the first ``serve`` span's start to
the last one's end.
"""

from __future__ import annotations

import bisect
import re
import shutil
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: host spans, innermost first: a gap is put down to the innermost span
#: of these that covers its midpoint
HOST_SPANS = ("decide", "serve")
TOP = 10


def clear(tdir: Path) -> None:
    shutil.rmtree(tdir, ignore_errors=True)


def load(tdir: Path) -> tuple[dict, list]:
    """(device events by plane, host span events) of the newest trace
    under ``tdir``; an event is (name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData

    files = sorted(Path(tdir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {tdir}")
    prof = ProfileData.from_file(str(files[-1]))
    device: dict = {}
    host: list = []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return device, host


def merge(intervals: list) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_events(device: dict, host: list) -> dict:
    """busy_s (averaged over the device planes), window_s, per-operation
    device seconds, and the breakdown the result line carries."""
    serve = [(s, s + d) for n, s, d in host if n == "serve"]
    if not serve:
        raise ValueError("the trace holds no serve span")
    w0 = min(s for s, _ in serve)
    w1 = max(e for _, e in serve)
    window_s = (w1 - w0) * 1e-9
    ops: dict = {}
    busy_ns = 0.0
    gaps: list = []
    for evs in device.values():
        clipped = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        busy = merge(clipped)
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(1, len(device))
    spans = {name: sorted((s, s + d) for n, s, d in host if n == name)
             for name in HOST_SPANS}
    starts = {name: [s for s, _ in iv] for name, iv in spans.items()}
    idle: dict = {}
    for a, b in gaps:
        mid = (a + b) / 2
        who = "harness"
        for name in HOST_SPANS:
            i = bisect.bisect_right(starts[name], mid) - 1
            if i >= 0 and spans[name][i][1] > mid:
                who = name
                break
        idle[who] = idle.get(who, 0.0) + (b - a) * 1e-9 / n_dev
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    # an HLO op's event is named by its whole instruction; keep its name
    top = [(k.split(" = ", 1)[0].lstrip("%"), v) for k, v in top]
    return {
        "busy_s": busy_ns * 1e-9 / n_dev,
        "window_s": window_s,
        "ops": ops,
        "breakdown": {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def reduce(tdir: Path) -> dict:
    return reduce_events(*load(tdir))
