"""The ``seqb-mine`` cell (``seqb-online`` under ``seqb-drift``),
rehearsed on the CPU at tiny sizes: its rounds inside the window make
no program on the device paths and every answer matches the reference;
its control fails; the drifting mix is a function of its structure
seed and the session index; its ``mine.*`` readers read what the
program books, and nothing where it books nothing.
"""

from __future__ import annotations

import math
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(ROOT / "src")]

import control  # noqa: E402
import harness  # noqa: E402
import mineprofile  # noqa: E402
from test_bench_harness import no_compile_cache, tiny  # noqa: E402,F401
from test_online_mining import Rounds, online  # noqa: E402

SEED = 2**33 + 59
CELL = "seqb-mine"
MINE_METRICS = ("mine.window_round_s", "mine.window_share_pct",
                "mine.bitmaps_s", "mine.join_s")


def tiny_mine() -> dict:
    """The real ``seqb-mine`` entry at a size a test run holds: a round
    every 100 reads over the last 40 sessions, the hot set moving 7
    ranks every 10 sessions."""
    spec = harness.load_cell(CELL)
    data, mix = spec["config"]["data"], spec["mix"]
    data.update(n_blocks=5_000, n_frequent=40)
    mix["backlog"]["sessions"], mix["window"]["sessions"] = 60, 120
    mix["warm_sessions"] = 4
    mix["window"].update(drift_every=10, drift_ranks=7)
    spec["config"]["client"].update(
        online_mine_every=100, online_tail_sessions=40,
        # fewer dynamic-minsup passes and a smaller forest bound: fewer
        # programs to interpret and warm
        dynamic_minsup_start=0.05, metastore_capacity=200)
    return spec


def _run(spec, seconds, hook=None, trace=False):
    return harness.run_cell(spec, SEED, seconds, trace, time.perf_counter(),
                            say=lambda m: None, fault=hook)


def test_online_rounds_on_the_device_paths_start_no_program(no_compile_cache):
    """The device paths through online rounds, set-up's and the window's:
    every answer matches the reference, and the window makes no program
    (the warm-up in the first round made them all)."""
    spec = online(tiny("seqb-serve"), 100, 40, 60)
    spec["config"]["client"].update(dynamic_minsup_start=0.05,
                                    metastore_capacity=200)
    rounds = Rounds()
    r = _run(spec, 8.0, rounds)
    assert rounds.setup >= 3 and rounds.window >= 1
    assert {k: c["value"] for k, c in r["checks"].items()} == dict.fromkeys(
        r["checks"], 0)
    assert rounds.client.cold_programs == 0


def test_a_tiny_seqb_mine_run_is_correct(no_compile_cache):
    spec = tiny_mine()
    rounds = Rounds()
    r = _run(spec, 8.0, rounds, trace=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["window_programs"] == {"value": 0, "limit": 0}
    assert rounds.window >= 1 and rounds.client.cold_programs == 0
    assert r["attempted"] > 0 and r["failed"] == 0
    for name in MINE_METRICS:
        assert r["metrics"][name]["value"] > 0, name
    # the CPU holds no device plane to time the join on
    assert "mine.join_roofline_pct" not in r["metrics"]
    assert 0 < r["metrics"]["mine.window_share_pct"]["value"] < 100


def test_the_seqb_mine_control_is_not_correct():
    spec = tiny_mine()
    sound = control.control_run(spec, SEED, 600, None)
    assert all(c["value"] == 0 for c in sound.values())
    broken = control.control_run(spec, SEED, 600, spec["config"]["control"])
    assert any(c["value"] > c["limit"] for c in broken.values())


def _keys_map(a: list, b: list) -> dict:
    """The key-for-key map from one build's sessions to another's;
    fails unless it is one-to-one."""
    out: dict = {}
    for sa, sb in zip(a, b, strict=True):
        assert len(sa) == len(sb)
        for (ka, _), (kb, _) in zip(sa, sb):
            assert out.setdefault(ka, kb) == kb
    assert len(set(out.values())) == len(out)
    return out


def test_seqb_drift_is_deterministic_and_the_seed_renames_blocks_only():
    spec = tiny_mine()
    gen = harness.generator(spec)
    a, b = (gen.build(spec["config"], spec["mix"], SEED) for _ in range(2))
    c = gen.build(spec["config"], spec["mix"], SEED + 1)
    for part in ("backlog", "window", "data"):
        assert a[part] == b[part]
    assert a["window"] != c["window"] and a["data"] != c["data"]
    _keys_map(a["backlog"] + a["window"], c["backlog"] + c["window"])


def test_seqb_drift_moves_the_ranks_by_the_session_index():
    """Drawn with the same structure seed, session i of a drifting window
    reads sequence (r + ranks * (i // every)) % n where the still window
    reads sequence r; the backlog and the background reads are equal."""
    spec = tiny_mine()
    gen = harness.generator(spec)
    n = spec["config"]["data"]["n_frequent"]
    every, ranks = 10, 7
    still = {**spec["mix"], "window": {**spec["mix"]["window"],
                                       "drift_ranks": 0}}
    a = gen.build(spec["config"], spec["mix"], SEED)
    b = gen.build(spec["config"], still, SEED)
    assert a["backlog"] == b["backlog"]
    structure = gen.seqb.SEQB(spec["config"]["data"], np.random.default_rng(
        spec["mix"]["structure_seed"]))
    rename = np.random.default_rng(SEED).permutation(
        spec["config"]["data"]["n_blocks"])
    index = {tuple(gen.seqb.key(int(rename[x])) for x in s): r
             for r, s in enumerate(structure.sequences)}
    assert len(index) == n
    moved = 0
    for i, (sa, sb) in enumerate(zip(a["window"], b["window"], strict=True)):
        ka, kb = (tuple(k for k, _ in s) for s in (sa, sb))
        if kb in index:
            assert index[ka] == (index[kb] + ranks * (i // every)) % n, i
            moved += i >= every
        else:
            assert ka == kb
    assert moved > 50


def test_the_ladder_covers_what_the_configuration_allows():
    """With maxgap 1 a level holds at most S * L / floor_count prefixes or
    candidates; every join up to that bound calls a warmed program, and
    every forest up to the metastore's bound lands on a warmed rung."""
    from repro.kernels.bitmap_support import ops as bm_ops
    from repro.kernels.decision_walk import ops as dw_ops

    config = harness.load_cell(CELL)["config"]
    client, data = config["client"], config["data"]
    s = client["online_tail_sessions"]
    floor = math.ceil(client["dynamic_minsup_floor"] * s)
    bound = s * data["max_seq"] // floor
    assert bound == 5_000
    words = -(-data["max_seq"] // 32)
    warm = {p[:4] for p in bm_ops.frontier_programs(s, words, s)}
    edges = sorted({1, bound} | {e + d for e in bm_ops.ROW_LADDER
                                 for d in (-1, 0, 1)}
                   | {k * bm_ops.ROW_TILE + d for k in range(1, 10)
                      for d in (-1, 0, 1)})
    for p in edges:
        for k in edges:
            for sessions in (1, s // 2, s):
                assert set(bm_ops.frontier_calls(p, k, sessions, words,
                                                 s)) <= warm
    nodes = client["metastore_capacity"] * client["mining"]["max_len"]
    ladder = dw_ops.node_ladder(nodes)
    for n in (1, 2, 33, 277, 4_000, nodes):
        assert dw_ops.node_bucket(n) in ladder


def test_the_join_roofline_on_a_synthetic_trace():
    mod = harness.reader(harness.load_cell(CELL), "mine.join_roofline_pct")
    shapes = [(403, 403, 2000, 1), (12, 6, 2000, 1)]
    moved = sum(mod.least_bytes(*s) for s in shapes)
    assert moved == ((806 * 2000 + 403 * 403) + (18 * 2000 + 72)) * 4
    join = mineprofile.JOIN
    # two joins of 20 us and 30 us; the device works 10 us in the first
    # (two overlapping ops) and 15 us in the second, and 50 us outside
    host = [(join, 0, 20_000), (join, 100_000, 30_000), ("serve", 0, 200_000)]
    device = {"/device:TPU:0": [("join", 5_000, 8_000), ("join", 7_000, 8_000),
                                ("join", 110_000, 15_000),
                                ("walk", 150_000, 50_000)]}
    peak = 819e9
    pct = mod.roofline_pct(device, host, shapes, peak)
    assert pct == pytest.approx(100.0 * moved / (peak * 25e-6))
    assert 0 < pct <= 100
    # no join span, no shapes, or no device plane: nothing to read
    assert mod.roofline_pct(device, host[2:], shapes, peak) is None
    assert mod.roofline_pct(device, host, [], peak) is None
    assert mod.roofline_pct({}, host, shapes, peak) is None


def test_the_mine_readers_read_nothing_without_the_spans():
    """A program whose rounds book no ``palp.mine`` span (or a run with no
    round in its window) gives no ``mine.*`` number."""
    run = harness.Run(trace=True)
    run.state["host_profile"] = type(
        "Profile", (), {"calls": {}, "seconds": {}})()
    run.trace_data = {"window_s": 30.0}
    spec = harness.load_cell(CELL)
    for name in MINE_METRICS:
        assert harness.reader(spec, name).read(run) is None, name
