"""Reduction of a JAX profiler trace to the device's busy time, each
operation's device time, and the idle gaps by what the host was doing.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  The host's activity comes from the
``jax.profiler.TraceAnnotation`` spans the harness writes (``serve``
around each client call, ``decide`` around each decision engine's
call) and those of the program's host profile (``palp.decide``,
``palp.walk`` and its phases, ``palp.mine`` and whatever else it names
``palp.*``); the traced window runs from the first ``serve`` span's
start to the last one's end.
"""

from __future__ import annotations

import heapq
import re
import shutil
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: the harness's host spans; with the program's (``PROGRAM_SPANS``) they
#: are what an idle gap is put down to: the innermost of them that
#: covers the gap's midpoint, or ``harness`` where none does
HOST_SPANS = ("decide", "serve")
PROGRAM_SPANS = "palp."
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
                            for e in line.events if host_span(e.name))
    return device, host


def host_span(name: str) -> bool:
    return name in HOST_SPANS or name.startswith(PROGRAM_SPANS)


def innermost(spans: list, points: list) -> list:
    """For each point, in ascending order, the name of the innermost span
    that covers it (the one that started last; of two that started
    together, the shorter), or None.  A span is (name, start, end)."""
    spans = sorted(spans, key=lambda sp: sp[1])
    heap: list = []
    out = []
    i = 0
    for p in points:
        while i < len(spans) and spans[i][1] <= p:
            name, s, e = spans[i]
            heapq.heappush(heap, (-s, e - s, e, name))
            i += 1
        while heap and heap[0][2] <= p:
            heapq.heappop(heap)
        out.append(heap[0][3] if heap else None)
    return out


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
    gaps.sort(key=lambda g: g[0] + g[1])
    who = innermost([(n, s, s + d) for n, s, d in host if host_span(n)],
                    [(a + b) / 2 for a, b in gaps])
    idle: dict = {}
    for (a, b), name in zip(gaps, who):
        name = name or "harness"
        idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9 / n_dev
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
