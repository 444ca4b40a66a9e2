"""The chip benchmark's harness, rehearsed on the CPU at tiny sizes.

Covers the generators, the TPC-C shapes, the trace reduction, finding a
cell's files by name, the refusal of a CPU, and ``correct``: a sound run
passes, a run with a fault planted in the timed path fails, and so does
the control (the reference with a guarantee broken).
"""

from __future__ import annotations

import collections
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import control  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402

SEED = 2**33 + 17          # seeds are larger than 32 bits


def tiny(cell: str) -> dict:
    """The cell at a size a test run holds."""
    return shrink(harness.load_cell(cell))


def shrink(spec: dict) -> dict:
    """A cell's spec cut to a size a test run holds, by its generator."""
    data, mix = spec["config"]["data"], spec["mix"]
    if spec["mix"]["generator"] == "seqb":
        data.update(n_blocks=5_000, n_frequent=40)
        mix["backlog"]["sessions"], mix["window"]["sessions"] = 300, 120
        mix["warm_sessions"] = 4
    else:
        data.update(customers_per_district=1_000, items=1_000,
                    orders_per_district=100, new_orders_per_district=30)
        mix["backlog"]["sessions"], mix["window"]["sessions"] = 200, 40
        mix["warm_sessions"] = 2
    return spec


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep the test process off the persistent compilation cache."""
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off")


@pytest.fixture
def host_paths(monkeypatch):
    """The numpy paths: the comparison does not depend on the path."""
    monkeypatch.setattr(harness, "DEVICE_SELECTORS", {})


CELLS = ("seqb-serve", "tpcc-serve")


@pytest.mark.parametrize("cell", CELLS)
def test_generator_is_deterministic_by_seed(cell):
    spec = tiny(cell)
    gen = harness.generator(spec)
    a, b = (gen.build(spec["config"], spec["mix"], SEED) for _ in range(2))
    c = gen.build(spec["config"], spec["mix"], SEED + 1)
    for part in ("backlog", "window", "data"):
        assert a[part] == b[part]
    assert a["window"] != c["window"]
    assert a["data"] != c["data"]
    # the seed renames keys and draws values; the work stays the same
    for part in ("backlog", "window"):
        assert ([[v is None for _, v in s] for s in a[part]]
                == [[v is None for _, v in s] for s in c[part]])


def test_tpcc_keeps_the_spec_cardinalities_widths_and_mix():
    spec = harness.load_cell("tpcc-serve")
    d = spec["config"]["data"]
    gen = harness.generator(spec)
    rng = np.random.default_rng(SEED)
    t = gen.TPCC(d, rng, gen.Values(d["widths"], SEED))
    data = t.dataset()
    rows = collections.Counter(k[0] for k in data)
    assert rows["warehouse"] == 1 and rows["district"] == 10
    assert rows["customer"] == rows["history"] == 30_000
    assert rows["item"] == rows["stock"] == 100_000
    assert rows["orders"] == 30_000 and rows["new_order"] == 9_000
    assert 5 * 30_000 <= rows["order_line"] <= 15 * 30_000
    assert abs(rows["order_line"] / 30_000 - 10) < 0.1
    widths = {"warehouse": 89, "district": 95, "customer": 655,
              "history": 46, "new_order": 8, "orders": 24,
              "order_line": 54, "item": 82, "stock": 306}
    for k, v in data.items():
        assert len(v) == widths[k[0]]
    assert abs(sum(len(v) for v in data.values()) / 1e6 - 76.8) < 1.0
    # the standard mix (5.2.3), counted by the transaction each draw picks
    kinds = collections.Counter()
    for name, _ in gen.MIX:
        setattr(t, name, lambda name=name: kinds.update([name]))
    for _ in range(20_000):
        t.transaction()
    for name, share in gen.MIX:
        assert abs(kinds[name] / 20_000 - share) < 0.012, name
    # NURand stays in its range and is skewed
    draws = [t.nurand(8191, 1, 100_000, t.c_item) for _ in range(5_000)]
    assert 1 <= min(draws) and max(draws) <= 100_000


def test_tpcc_new_order_writes_5_to_15_lines_that_read_back():
    spec = harness.load_cell("tpcc-serve")
    d = spec["config"]["data"]
    gen = harness.generator(spec)
    t = gen.TPCC(d, np.random.default_rng(SEED), gen.Values(d["widths"], SEED))
    for _ in range(50):
        ops = t.new_order()
        lines = [k for k, v in ops if k[0] == "order_line"]
        assert 5 <= len(lines) <= 15
        values = [v for _, v in ops if v is not None]
        assert len(set(values)) == len(values)      # each write is its own


def test_trace_reduction_on_a_small_trace():
    device = {"/device:TPU:0": [("fusion.1", 0, 10), ("frontier", 5, 10),
                                ("fusion.1", 30, 10), ("late", 60, 10)]}
    host = [("serve", 0, 20), ("serve", 21, 29), ("decide", 22, 8)]
    r = devtrace.reduce_events(device, host)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(25e-9)      # [0,15) + [30,40)
    assert r["ops"] == pytest.approx({"fusion.1": 20e-9, "frontier": 10e-9})
    idle = dict(r["breakdown"]["idle_gaps"])
    # [15,30) has its midpoint in decide, inside serve; [40,50) in serve
    assert idle == pytest.approx({"decide": 15e-9, "serve": 10e-9})
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]

    # the program's palp.* spans nest inside the harness's: each gap goes
    # to the innermost span over its midpoint, and the busy time, the
    # window and the operations stay as they were without them
    device = {"/device:TPU:0": [("walk", 0, 10), ("walk", 40, 10),
                                ("walk", 56, 2)]}
    harness_spans = [("serve", 0, 100), ("decide", 6, 58)]
    program_spans = [("palp.decide", 5, 60), ("palp.walk", 8, 50),
                     ("palp.walk.upload", 8, 4), ("palp.walk.wait", 12, 20),
                     ("palp.mine", 70, 25)]
    other = [("jit_step", 0, 100)]
    r = devtrace.reduce_events(device, harness_spans + program_spans + other)
    bare = devtrace.reduce_events(device, harness_spans)
    for key in ("busy_s", "window_s", "ops"):
        assert r[key] == bare[key]
    assert r["busy_s"] == pytest.approx(22e-9)
    # [10,40) has its midpoint in the wait, [50,56) in the walk outside
    # its phases, [58,100) in the mining round
    assert dict(r["breakdown"]["idle_gaps"]) == pytest.approx(
        {"palp.walk.wait": 30e-9, "palp.walk": 6e-9, "palp.mine": 42e-9})
    assert dict(bare["breakdown"]["idle_gaps"]) == pytest.approx(
        {"decide": 36e-9, "serve": 42e-9})
    # spans that start together: the shorter is the inner
    assert devtrace.innermost([("a", 0, 10), ("b", 0, 5)],
                              [1, 7, 12]) == ["b", "a", None]


def test_trace_reduction_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("serve"):
            with jax.profiler.TraceAnnotation("decide"):
                with jax.profiler.TraceAnnotation("palp.walk"):
                    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    device, host = devtrace.load(tmp_path)
    names = collections.Counter(n for n, _, _ in host)
    assert names["serve"] == 3 and names["decide"] == 3
    assert names["palp.walk"] == 3
    r = devtrace.reduce_events(device, host)
    assert r["window_s"] > 0 and r["busy_s"] == 0.0     # no TPU plane here


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    shutil.copy(BENCH / "configs" / "seqb-paper.json",
                tmp_path / "configs" / "seqb-new.json")
    shutil.copy(BENCH / "traffic" / "seqb.py", tmp_path / "traffic" / "gen-x.py")
    mix = json.loads((BENCH / "traffic" / "seqb-pattern.json").read_text())
    mix["generator"] = "gen-x"
    (tmp_path / "traffic" / "mix-y.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "layer.new_us.py").write_text(
        "def install(run):\n    run.state['x'] = 1\n\n"
        "def read(run):\n    return 41.0 + run.state['x']\n")
    bench = {"workloads": [{"name": "cell-z", "config": "seqb-new",
                            "traffic": "mix-y", "chips": 1, "why": "t"}],
             "end_to_end": [{"name": "ops_per_s"}],
             "per_layer": [{"name": "layer.new_us"},
                           {"name": "other", "workloads": ["elsewhere"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_cell("cell-z", tmp_path / "BENCHMARK.json", tmp_path)
    assert spec["config"]["name"] == "seqb-paper"
    assert [m["name"] for m in spec["per_layer"]] == ["layer.new_us"]
    assert harness.generator(spec).key(7) == ("blocks", "b7", "d")
    run = harness.Run(trace=True)
    mod = harness.reader(spec, "layer.new_us")
    mod.install(run)
    assert mod.read(run) == 42.0


def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "seqb-serve", "--seed", str(SEED), "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def _run(spec, fault=None, trace=False):
    import time
    return harness.run_cell(spec, SEED, 0.3, trace, time.perf_counter(),
                            say=lambda m: None, fault=fault)


def test_seqb_run_on_the_device_paths_is_correct(no_compile_cache):
    spec = tiny("seqb-serve")
    # fewer dynamic-minsup passes: fewer kernel shapes to interpret
    spec["config"]["client"]["dynamic_minsup_start"] = 0.05
    r = _run(spec, trace=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["window_programs"] == {"value": 0, "limit": 0}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) <= {m["name"] for m in spec["per_layer"]}
    assert "decide.us_per_op" in r["metrics"]
    assert list(r)[-1] == "checks"


def test_tpcc_run_is_correct(no_compile_cache, host_paths):
    r = _run(tiny("tpcc-serve"))
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"ops_per_s", "op_p50_us", "op_p99_us",
                                 "setup_s"}


def _state_unchanged(client, run):
    # a mining round that leaves the trees as they were
    for engine in run.engines():
        run.patch(engine, "replace_index", lambda f: lambda index: None)


def _half_left_out(client, run):
    # mining over half of the logged sessions
    def make(snapshot):
        def half():
            db = snapshot()
            return db.tail(len(db) // 2)
        return half
    run.patch(client.logger, "snapshot", make)


def _answer_altered(client, run):
    n = [0]

    def make(read):
        def altered(key):
            value, latency = read(key)
            n[0] += 1
            if n[0] % 50 == 0:
                value = bytes([value[0] ^ 1]) + value[1:]
            return value, latency
        return altered
    run.patch(client, "read", make)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered])
def test_a_fault_in_the_timed_path_is_not_correct(cell, fault,
                                                  no_compile_cache,
                                                  host_paths):
    r = _run(tiny(cell), fault=fault)
    assert not r["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_takes_the_steps_a_run_takes(cell, monkeypatch,
                                                 no_compile_cache,
                                                 host_paths):
    spec = tiny(cell)
    seen = {}
    compare = harness.compare

    def keep(program, ops, *a):
        seen["ops"] = list(ops)
        return compare(program, ops, *a)
    monkeypatch.setattr(harness, "compare", keep)
    r = _run(spec)
    work = harness.generator(spec).build(spec["config"], spec["mix"], SEED)
    assert seen["ops"] == control.steps(work, spec["mix"], r["attempted"])


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    spec = tiny(cell)
    calls = 600
    sound = control.control_run(spec, SEED, calls, None)
    assert all(c["value"] == 0 for c in sound.values())
    broken = control.control_run(spec, SEED, calls,
                                 spec["config"]["control"])
    assert any(c["value"] > c["limit"] for c in broken.values())
