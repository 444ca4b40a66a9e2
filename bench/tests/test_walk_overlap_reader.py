"""The ``walk.overlap_pct`` reader, rehearsed on the CPU: its arithmetic
on a hand-filled host profile, a program that does not count begun
walks, and a tiny traced ``tpcc-serve`` run on the device walk.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import hostprofile  # noqa: E402

SEED = 2**33 + 29


def metric(name: str):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_the_overlap_reader_reads_the_share_of_begun_walks(monkeypatch):
    from repro.core import obs

    overlap = metric("walk.overlap_pct")
    run = harness.Run(trace=True)
    overlap.install(run)
    prof = run.state[hostprofile.KEY]
    run.restore()
    assert overlap.read(run) is None               # no walk yet
    prof.calls["palp.walk"] = 40
    assert overlap.read(run) == 0.0                # none begun ahead
    prof.counters["palp.walk.overlapped"] = 3
    assert overlap.read(run) == pytest.approx(7.5)
    # a program that does not count begun walks reads nothing
    monkeypatch.setattr(obs, "REGISTERED_NAMES",
                        obs.REGISTERED_NAMES - {"palp.walk.overlapped"})
    assert overlap.read(run) is None


def test_a_traced_tpcc_run_overlaps_the_column_walk(monkeypatch, tmp_path):
    """A tiny ``tpcc-serve`` run on the device walk (CPU here; mining on
    the host, which is quicker to run): the column engine's walks are
    begun ahead of the main engine's, each walk still makes two copies,
    no program starts in the window, and the run is correct."""
    import time

    import repro.compile_cache
    from test_bench_harness import tiny

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off")
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "DEVICE_SELECTORS",
                        {"decision_backend": "jax"})
    spec = tiny("tpcc-serve")
    r = harness.run_cell(spec, SEED, 0.5, True, time.perf_counter(),
                         say=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["checks"]["window_programs"] == {"value": 0, "limit": 0}
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0 < m["walk.overlap_pct"] < 100
    assert m["walk.copies_per_call"] == 2.0
