"""The ``mine.passes_per_round`` reader, rehearsed on the CPU: its
arithmetic on a hand-filled host profile, a program that does not count
the descent's passes, and a tiny traced ``seqb-mine`` run whose window
rounds each make one pass.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(ROOT / "src")]

import harness  # noqa: E402
import mineprofile  # noqa: E402
from test_bench_harness import no_compile_cache  # noqa: E402,F401
from test_seqb_mine import tiny_mine  # noqa: E402

SEED = 2**33 + 67


def passes():
    return harness.load_module(BENCH / "metrics" / "mine.passes_per_round.py")


def test_the_passes_reader_divides_by_the_window_rounds(monkeypatch):
    from repro.core import obs

    mod = passes()
    run = harness.Run(trace=True)
    mod.install(run)
    prof = run.state["host_profile"]
    run.restore()
    assert mod.read(run) is None                   # no round yet
    prof.calls[mineprofile.MINE] = 4
    assert mod.read(run) == 0.0                    # rounds, no pass counted
    prof.counters["palp.mine.passes"] = 6
    assert mod.read(run) == pytest.approx(1.5)
    # a program that does not count the passes reads nothing
    monkeypatch.setattr(obs, "REGISTERED_NAMES",
                        obs.REGISTERED_NAMES - {"palp.mine.passes"})
    assert mod.read(run) is None


def test_a_traced_seqb_mine_run_makes_one_pass_a_window_round(
        no_compile_cache):
    """Set-up's first round descends rung by rung; every later round
    makes one pass at the rung the last settled on, so the window's
    rounds read one pass each, and the run is correct."""
    spec = tiny_mine()
    r = harness.run_cell(spec, SEED, 8.0, True, time.perf_counter(),
                         say=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["metrics"]["mine.passes_per_round"]["value"] == 1.0
