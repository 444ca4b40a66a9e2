"""mine.join_roofline_pct: the frontier support join's share of its HBM
roofline in the window's mining rounds, from the profiler trace.

The least bytes a join of logical shapes (P prefixes, K candidates, S
sessions, W words) must move are its two packed operands and its
support matrix, ``((P + K) * S * W + P * K) * 4``; the work is bitwise
and light, so HBM bandwidth bounds it.  The reader records each join's
logical shapes where ``kernels/bitmap_support/ops.py``
``frontier_join_support`` is called (whatever it pads to), sums their
least bytes, and divides by the device's HBM bandwidth
(``bench/peaks.json``) times the device time of the operations inside
the program's ``palp.mine.join`` spans; with several device planes the
time is their mean.  A program without those spans reads nothing.
"""

from __future__ import annotations

import json

import devtrace
import mineprofile

KEY = "mine.join_roofline_pct"


def least_bytes(p: int, k: int, s: int, w: int) -> int:
    return ((p + k) * s * w + p * k) * 4


def install(run):
    from repro.kernels.bitmap_support import ops

    mineprofile.install(run)
    shapes = run.state.setdefault(KEY, [])

    def make(join):
        def recorded(slots, cand, **kw):
            if run.in_window:
                shapes.append((*slots.shape[:1], *cand.shape))
            return join(slots, cand, **kw)
        return recorded
    run.patch(ops, "frontier_join_support", make)


def _overlap_ns(intervals: list, spans: list) -> float:
    """Length of the intersection of the union of ``intervals`` with the
    union of ``spans``; each a list of (start, end)."""
    a, b = devtrace.merge(intervals), devtrace.merge(spans)
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


def roofline_pct(device: dict, host: list, shapes: list,
                 bytes_per_s: float):
    """The reduction, on events as ``devtrace.load`` gives them and the
    joins' logical (P, K, S, W)."""
    joins = [(s, s + d) for name, s, d in host if name == mineprofile.JOIN]
    if not joins or not shapes or not device:
        return None
    device_ns = sum(_overlap_ns([(s, s + d) for _, s, d in evs], joins)
                    for evs in device.values()) / len(device)
    if device_ns <= 0:
        return None
    moved = sum(least_bytes(*shape) for shape in shapes)
    return 100.0 * moved / (bytes_per_s * device_ns * 1e-9)


def read(run):
    import harness
    import jax

    if run.state.get(mineprofile.hostprofile.KEY) is None:
        return None
    try:
        device, host = devtrace.load(harness.ROOT / ".bench_trace")
    except FileNotFoundError:
        return None
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())
    peak = peaks.get(jax.devices()[0].device_kind, {}).get("hbm_bytes_per_s")
    if not peak:
        return None
    return roofline_pct(device, host, run.state.get(KEY, []), peak)
