"""A client that mines inside the measured window, and the check that a
window starts no new program, rehearsed on the CPU at tiny sizes.

The reference replays ``online_mine_every`` / ``online_tail_sessions``
as the program runs them: a sound run with rounds in its window is
correct, and a round taken one read late, over the whole backlog, or
that leaves the trees as they were, is not; nor is the control.  A
program that JAX first makes inside the window (traced, compiled, or
loaded from the persistent cache) fails ``window_programs``.
"""

from __future__ import annotations

import json
import shutil
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
from test_bench_harness import (  # noqa: E402,F401 (fixtures)
    host_paths, no_compile_cache, shrink, tiny)

SEED = 2**33 + 41
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def online(spec: dict, every: int, tail: int, backlog: int) -> dict:
    """The spec with a client that mines every ``every`` reads over the
    last ``tail`` sessions, after a backlog of ``backlog`` sessions."""
    spec["config"]["client"].update(online_mine_every=every,
                                    online_tail_sessions=tail)
    spec["mix"]["backlog"]["sessions"] = backlog
    return spec


#: per cell: (every, tail, backlog, window seconds) that put two rounds
#: or more in a host-path window
ONLINE = {"seqb-serve": (50, 40, 60, 0.3), "tpcc-serve": (100, 20, 20, 5.0)}


def online_tiny(cell: str) -> tuple:
    every, tail, backlog, seconds = ONLINE[cell]
    return online(tiny(cell), every, tail, backlog), seconds


class Rounds:
    """A hook that counts the program's mining rounds, set-up's and the
    window's, on top of the harness's recorder, and keeps the client."""

    def __init__(self, fault=None):
        self.fault = fault
        self.setup = self.window = 0
        self.client = self.run = None

    def __call__(self, client, run):
        self.client, self.run = client, run

        def make(mine_now):
            def counted(*a, **kw):
                if run.in_window:
                    self.window += 1
                else:
                    self.setup += 1
                return mine_now(*a, **kw)
            return counted
        run.patch(client, "mine_now", make)
        if self.fault is not None:
            self.fault(client, run)


def _run(spec, seconds, hook=None, trace=False):
    return harness.run_cell(spec, SEED, seconds, trace, time.perf_counter(),
                            say=lambda m: None, fault=hook)


@pytest.mark.parametrize("cell", sorted(ONLINE))
def test_a_run_that_mines_in_its_window_is_correct(cell, monkeypatch,
                                                   no_compile_cache,
                                                   host_paths):
    spec, seconds = online_tiny(cell)
    seen = {}
    compare = harness.compare

    def keep(program, *a):
        seen["rounds"] = len(program["rounds"])
        return compare(program, *a)
    monkeypatch.setattr(harness, "compare", keep)
    rounds = Rounds()
    r = _run(spec, seconds, rounds)
    assert r["correct"], r["checks"]
    assert rounds.window >= 2
    # the recorder's mine_now is the client's own attribute, so the
    # program's online trigger goes through it: every round is compared
    assert seen["rounds"] == rounds.client.mining_runs
    assert rounds.client.mining_runs == rounds.setup + rounds.window


def test_the_online_reference_holds_on_the_device_paths(no_compile_cache):
    """Every answer of the device paths matches the reference's through
    online rounds, set-up's and the window's.  Each round mines a new
    tail, so its join and walk shapes are new programs: until the
    program buckets them, ``window_programs`` is what fails here."""
    spec = online(tiny("seqb-serve"), 100, 40, 60)
    # fewer dynamic-minsup passes: fewer kernel shapes to interpret
    spec["config"]["client"]["dynamic_minsup_start"] = 0.05
    rounds = Rounds()
    r = _run(spec, 8.0, rounds)
    assert rounds.setup >= 3 and rounds.window >= 1
    assert {k: c["value"] for k, c in r["checks"].items()
            if k != "window_programs"} == dict.fromkeys(
        set(r["checks"]) - {"window_programs"}, 0)


def _mines_one_read_late(client, run):
    every = client.cfg.online_mine_every

    def make(_):
        def late():
            client._ops_since_mine += 1
            if client._ops_since_mine > every:
                client._ops_since_mine = 0
                client.mine_now()
        return late
    run.patch(client, "_maybe_online_mine", make)


def _mines_the_whole_backlog(client, run):
    run.patch(client.cfg, "online_tail_sessions", lambda _: 10**9)


def _online_round_keeps_the_trees(client, run):
    online_round = [False]

    def trigger(fn):
        def wrapped():
            online_round[0] = True
            try:
                fn()
            finally:
                online_round[0] = False
        return wrapped

    def replace(fn):
        def wrapped(index):
            if not online_round[0]:
                fn(index)
        return wrapped
    run.patch(client, "_maybe_online_mine", trigger)
    for engine in run.engines():
        run.patch(engine, "replace_index", replace)


@pytest.mark.parametrize("cell", sorted(ONLINE))
@pytest.mark.parametrize("fault", [_mines_one_read_late,
                                   _mines_the_whole_backlog,
                                   _online_round_keeps_the_trees])
def test_a_fault_in_online_mining_is_not_correct(cell, fault,
                                                 no_compile_cache,
                                                 host_paths):
    spec, seconds = online_tiny(cell)
    r = _run(spec, seconds, Rounds(fault))
    assert not r["correct"]
    assert r["checks"]["window_programs"]["value"] == 0


@pytest.mark.parametrize("cell", sorted(ONLINE))
def test_the_control_fails_with_online_mining(cell):
    spec, _ = online_tiny(cell)
    sound = control.control_run(spec, SEED, 600, None)
    assert all(c["value"] == 0 for c in sound.values())
    broken = control.control_run(spec, SEED, 600, spec["config"]["control"])
    assert any(c["value"] > c["limit"] for c in broken.values())


def test_an_online_cell_is_added_as_new_files_only(tmp_path,
                                                   no_compile_cache,
                                                   host_paths):
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    config = json.loads((BENCH / "configs" / "seqb-paper.json").read_text())
    config["name"] = "seqb-online"
    config["client"].update(online_mine_every=50, online_tail_sessions=40)
    (tmp_path / "configs" / "seqb-online.json").write_text(json.dumps(config))
    shutil.copy(BENCH / "traffic" / "seqb.py", tmp_path / "traffic" / "seqb.py")
    shutil.copy(BENCH / "traffic" / "seqb-pattern.json",
                tmp_path / "traffic" / "seqb-drift.json")
    bench = {"workloads": [{"name": "seqb-mine", "config": "seqb-online",
                            "traffic": "seqb-drift", "chips": 1,
                            "why": "t"}],
             "end_to_end": [{"name": "ops_per_s", "unit": "ops/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_cell("seqb-mine", tmp_path / "BENCHMARK.json",
                             tmp_path)
    spec = shrink(spec)
    spec["mix"]["backlog"]["sessions"] = 60
    rounds = Rounds()
    r = _run(spec, 0.3, rounds)
    assert r["correct"], r["checks"]
    assert rounds.window >= 2
    assert set(r["metrics"]) == {"ops_per_s", "setup_s"}


# -- window_programs ----------------------------------------------------------

SHAPE = (3, 37)


def _fresh(x):
    return x * 2 + 1


def _fresh_program_in_a_read(client, run):
    """Calls a jitted function on a shape nothing made before, once,
    inside a read of the window."""
    import jax

    step = jax.jit(_fresh)
    done = [False]

    def make(read):
        def read_and_compute(key):
            if run.in_window and not done[0]:
                done[0] = True
                step(np.ones(SHAPE, np.float32)).block_until_ready()
            return read(key)
        return read_and_compute
    run.patch(client, "read", make)


@pytest.fixture
def compile_cache_in(tmp_path, monkeypatch):
    """The persistent compilation cache in a directory of the test's own,
    for the harness's ``enable_compile_cache``, keeping programs however
    small; set back afterwards."""
    import jax
    from jax._src import compilation_cache

    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield tmp_path / "cache"
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_program_first_made_in_the_window_is_not_correct(no_compile_cache,
                                                           host_paths):
    rounds = Rounds(_fresh_program_in_a_read)
    r = _run(tiny("seqb-serve"), 0.3, rounds)
    assert not r["correct"]
    assert r["checks"]["window_programs"]["value"] >= 1
    assert {k: c["value"] for k, c in r["checks"].items()
            if k != "window_programs"} == dict.fromkeys(
        set(r["checks"]) - {"window_programs"}, 0)


def test_a_program_loaded_from_the_cache_in_the_window_is_not_correct(
        compile_cache_in, host_paths):
    import jax

    from repro.compile_cache import enable_compile_cache

    assert enable_compile_cache() == str(compile_cache_in)
    jax.clear_caches()            # so that this process compiles it anew
    jax.jit(_fresh)(np.ones(SHAPE, np.float32)).block_until_ready()
    assert any(compile_cache_in.iterdir())
    jax.clear_caches()            # only the persistent cache holds it now
    rounds = Rounds(_fresh_program_in_a_read)
    r = _run(tiny("seqb-serve"), 0.3, rounds)
    assert not r["correct"]
    assert r["checks"]["window_programs"]["value"] >= 1
    assert rounds.run.window_programs[RETRIEVAL] == 1


def test_a_sound_run_makes_no_program_in_its_window(no_compile_cache,
                                                    host_paths):
    r = _run(tiny("seqb-serve"), 0.3)
    assert r["correct"], r["checks"]
    assert r["checks"]["window_programs"] == {"value": 0, "limit": 0}
    assert list(r["checks"])[-1] == "window_programs"
