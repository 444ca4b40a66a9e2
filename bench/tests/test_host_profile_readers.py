"""The per-layer readers of the program's host profile (``palp.*``
spans and counters, ``repro.core.obs``) and of the mining-round timers,
rehearsed on the CPU: the idle-in-walk reduction on a small trace, the
readers' arithmetic on a hand-filled profile, a program without the
profile, and a tiny traced run that reports every one of them.
"""

from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import devtrace  # noqa: E402
import harness  # noqa: E402
import hostprofile  # noqa: E402

SEED = 2**33 + 29
PROFILE_READERS = ("decide.self_us_per_op", "walk.upload_us",
                   "walk.dispatch_us", "walk.wait_us", "walk.readback_us",
                   "walk.unpack_us", "walk.copies_per_call",
                   "walk.readback_bytes", "walk.upload_bytes",
                   "device.idle_in_walk_pct")
MINE_READERS = ("mine.round_s", "mine.rebuild_s")
WALK_US = ("walk.upload_us", "walk.dispatch_us", "walk.wait_us",
           "walk.readback_us", "walk.unpack_us")


def metric(name: str):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_idle_in_walk_reduction_on_a_small_trace():
    idle_in_walk = metric("device.idle_in_walk_pct").idle_in_walk_pct
    device = {"/device:TPU:0": [("fusion.1", 0, 10), ("fusion.2", 30, 10)]}
    serve = [(0, 20), (22, 50)]
    # [5, 35) less the device's [5, 10) and [30, 35): 20 of the 50 ns
    # window; the walk's part past the window's end is clipped
    walk = [(5, 35), (45, 60)]
    assert idle_in_walk(device, serve, walk) == pytest.approx(
        100.0 * (20 + 5) / 50)
    assert idle_in_walk(device, serve, walk[:1]) == pytest.approx(40.0)
    # two devices: the mean of their shares (the second idles in all 30)
    two = dict(device, **{"/device:TPU:1": []})
    assert idle_in_walk(two, serve, walk[:1]) == pytest.approx(50.0)
    # no device plane (a CPU trace): all of the walk is idle
    assert idle_in_walk({}, serve, walk[:1]) == pytest.approx(60.0)
    assert idle_in_walk(device, serve, []) is None
    assert idle_in_walk(device, [], walk) is None
    # never above the device's whole idle share over the same window
    host = [("serve", s, e - s) for s, e in serve]
    r = devtrace.reduce_events(device, host)
    idle_pct = 100.0 * (1 - r["busy_s"] / r["window_s"])
    assert idle_in_walk(device, serve, walk) <= idle_pct


def test_readers_share_one_profile_and_restore_turns_it_off():
    from repro.core import obs

    run = harness.Run(trace=True)
    mods = {m: metric(m) for m in PROFILE_READERS}
    for mod in mods.values():
        mod.install(run)
    prof = run.state[hostprofile.KEY]
    assert obs.host_profile is prof and prof.active
    run.restore()
    assert obs.host_profile is obs.NULL_HOST_PROFILE
    # a window of 4 client calls that made 2 walk calls
    run.window_calls = 4
    prof.calls.update({"palp.decide": 3, "palp.walk": 2})
    prof.seconds.update({"palp.decide": 0.010, "palp.walk": 0.008,
                         "palp.walk.upload": 0.002,
                         "palp.walk.dispatch": 0.001,
                         "palp.walk.wait": 0.0005,
                         "palp.walk.readback": 0.004,
                         "palp.walk.unpack": 0.0003})
    prof.child_seconds["palp.decide"] = 0.008
    prof.counters.update({"palp.walk.h2d_copies": 8,
                          "palp.walk.d2h_copies": 12,
                          "palp.walk.h2d_bytes": 2 * 3_328,
                          "palp.walk.d2h_bytes": 2 * 73_728})
    read = {m: mod.read(run) for m, mod in mods.items() if m in
            PROFILE_READERS[:-1]}
    assert read == pytest.approx({
        "decide.self_us_per_op": 500.0, "walk.upload_us": 1000.0,
        "walk.dispatch_us": 500.0, "walk.wait_us": 250.0,
        "walk.readback_us": 2000.0, "walk.unpack_us": 150.0,
        "walk.copies_per_call": 10.0, "walk.readback_bytes": 73_728.0,
        "walk.upload_bytes": 3_328.0})


def test_readers_read_nothing_from_a_program_without_the_profile(
        monkeypatch):
    from repro.core import obs

    monkeypatch.delattr(obs, "HostProfile")
    run = harness.Run(trace=True)
    run.window_calls = 10
    # the older client: a mining timer, no rebuild timer
    run.client = types.SimpleNamespace(mining_runs=1, mining_wall_time=4.5)
    for name in PROFILE_READERS + MINE_READERS:
        metric(name).install(run)
    assert obs.host_profile is obs.NULL_HOST_PROFILE
    assert {name: metric(name).read(run)
            for name in PROFILE_READERS + MINE_READERS} == {
        **{name: None for name in PROFILE_READERS},
        "mine.round_s": 4.5, "mine.rebuild_s": None}


def test_mining_readers_divide_by_the_rounds():
    run = harness.Run(trace=True)
    run.client = types.SimpleNamespace(mining_runs=2, mining_wall_time=9.0,
                                       rebuild_wall_time=1.0)
    assert metric("mine.round_s").read(run) == 4.5
    assert metric("mine.rebuild_s").read(run) == 0.5
    run.client.mining_runs = 0
    assert metric("mine.round_s").read(run) is None
    assert metric("mine.rebuild_s").read(run) is None


def test_a_traced_run_reports_every_new_metric(monkeypatch, tmp_path):
    """A tiny ``seqb-serve`` run on the device paths (CPU here): every
    reader reads, the five walk phases fit inside the outside timer of
    the walk call, and the readers leave the profile off."""
    import time

    import repro.compile_cache
    from repro.core import obs

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off")
    # a trace directory of its own, apart from other tests' traced runs
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    spec = harness.load_cell("seqb-serve")
    data, mix = spec["config"]["data"], spec["mix"]
    data.update(n_blocks=5_000, n_frequent=40)
    mix["backlog"]["sessions"], mix["window"]["sessions"] = 300, 120
    mix["warm_sessions"] = 4
    spec["config"]["client"]["dynamic_minsup_start"] = 0.05
    said = []
    r = harness.run_cell(spec, SEED, 0.5, True, time.perf_counter(),
                         say=said.append)
    assert r["correct"], r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(PROFILE_READERS + MINE_READERS) <= set(m)
    assert obs.host_profile is obs.NULL_HOST_PROFILE
    assert m["walk.copies_per_call"] == 2.0
    # one packed int32 upload: three context rows per padded context, the
    # live count and the item
    c = spec["config"]["semantics"]["max_contexts"]
    assert m["walk.upload_bytes"] == (3 * c + 2) * 4
    assert 0 < sum(m[k] for k in WALK_US) <= m["decide.walk_call_us"]
    assert 0 <= m["decide.self_us_per_op"] <= m["decide.us_per_op"]
    assert 0 < m["device.idle_in_walk_pct"] <= m["device.idle_pct"]
    assert 0 < m["mine.rebuild_s"] <= m["mine.round_s"]
    (setup,) = [s for s in said if s.startswith("set-up s:")]
    first_round = float(setup.split("first_round ")[1].split(",")[0])
    assert m["mine.round_s"] <= first_round
