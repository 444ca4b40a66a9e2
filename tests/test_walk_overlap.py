"""A decision walk begun ahead and finished later: the two halves of
``kernels/decision_walk/ops.py`` against the one-call walk and the
reference, the engine's rule for consuming a begun walk, and a TPC-C
client whose column engine walks while its main engine does.

Not tier1 (imports jax).
"""

import functools

import numpy as np
import pytest

from benchmarks.workloads import TPCC, TPCCConfig
from repro.core import (
    HeuristicConfig,
    LatencyModel,
    MiningParams,
    PalpatineClient,
    PalpatineConfig,
    PrefetchEngine,
    PTreeIndex,
    SimulatedDKVStore,
    VectorizedPrefetchEngine,
)
from repro.core import obs
from repro.kernels.decision_walk import ops as dw_ops
from repro.kernels.decision_walk import ref as dw_ref

from test_decision import random_index, seqb_stream
from test_decision_kernel import (WALK_PHASES, _profiled, branching_forest,
                                  live_states)
from test_program_ladders import ProgramEvents

STATE_KEYS = ("found", "stay", "nodes", "alive", "fetched", "wave_nodes")
C = 16


@functools.lru_cache(maxsize=None)
def rung_forest(rung):
    flat = branching_forest(rung)
    return flat, dw_ops.device_forest(flat)


@pytest.mark.parametrize("live", [0, 1, C])
@pytest.mark.parametrize("rung", [32 << k for k in range(10)])
def test_a_begun_walk_equals_the_one_call_walk(rung, live):
    """Rungs 32 to 16,384, no live context, one and ``max_contexts``:
    the finished walk is the one-call walk and the reference, dtypes
    included, though the caller changed its arrays after the start."""
    flat, jf = rung_forest(rung)
    assert jf.n_padded == rung
    rng = np.random.default_rng(rung + live)
    nodes, trees, fetched = live_states(flat, rng, live)
    if live:
        nodes[0], fetched[0] = 0, 0     # a root context: a full wave
    for item in (1, -1, int(rng.integers(0, flat.item_stride))):
        args = (flat, nodes, trees, fetched, item, 2)
        want = dw_ref.decision_walk_ref(*args)
        once = dw_ops.decision_walk(jf, *args, max_contexts=C)
        begun = dw_ops.start_walk(jf, *args, max_contexts=C)
        kept = nodes.copy(), fetched.copy()
        nodes[:], fetched[:] = 7, 9
        got = dw_ops.finish_walk(begun)
        nodes[:], fetched[:] = kept
        for key in STATE_KEYS:
            for out in (once, got):
                np.testing.assert_array_equal(out[key], want[key],
                                              err_msg=key)
                assert out[key].dtype == want[key].dtype, key


def test_the_escape_paths_begin_no_device_walk():
    flat = random_index(0, n_patterns=12).flatten()
    jf = dw_ops.device_forest(flat)
    nodes, trees, fetched = live_states(flat, np.random.default_rng(0), 3)
    args = (flat, nodes, trees, fetched, 1, 2)
    begun, prof = _profiled(dw_ops.start_walk, jf, *args, max_contexts=C,
                            interpret=True)
    assert begun.out is None and not prof.calls and not prof.counters
    want = dw_ref.decision_walk_ref(*args)
    got = dw_ops.finish_walk(begun)
    for key in STATE_KEYS:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


class Walks:
    """Counts the walk calls of ``ops``: one-call, begun and finished."""

    def __init__(self, monkeypatch):
        self.n = {"decision_walk": 0, "start_walk": 0, "finish_walk": 0}
        for name in self.n:
            monkeypatch.setattr(dw_ops, name, self._counted(name))

    def _counted(self, name):
        fn = getattr(dw_ops, name)

        def counted(*a, **kw):
            self.n[name] += 1
            return fn(*a, **kw)
        return counted


def _engines(index, n_contexts=8):
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=2)
    return (VectorizedPrefetchEngine(index, cfg, n_contexts),
            VectorizedPrefetchEngine(index, cfg, n_contexts, backend="jax"))


def _open_contexts(host, dev, ops):
    for item in ops:
        assert host.on_request(item) == dev.on_request(item)
    assert dev.n_live > 0


def test_an_engine_consumes_the_walk_begun_for_its_next_request(
        monkeypatch):
    index = random_index(3, n_patterns=10)
    host, dev = _engines(index)
    walks = Walks(monkeypatch)
    ops = seqb_stream(5, index, n_ops=120)
    begun = 0
    for i, item in enumerate(ops):
        if i % 2 and dev.n_live:
            dev.begin_walk(item)
            begun += 1
        assert host.on_request(item) == dev.on_request(item), (i, item)
        assert host.n_live == dev.n_live
    assert begun > 10
    assert walks.n["start_walk"] == walks.n["finish_walk"] == begun


@pytest.mark.parametrize("plant", ["item", "generation", "reinstall",
                                   "earlier_op"])
def test_a_walk_begun_for_another_request_is_never_consumed(plant,
                                                            monkeypatch):
    """A begun walk for another item, another generation, the contexts
    a re-install of the same generation dropped, or an earlier op is
    discarded: the engine walks anew and answers as the numpy engine
    does."""
    idx1, idx2 = random_index(11), random_index(12)
    host, dev = _engines(idx1)
    ops = seqb_stream(7, idx1, n_ops=40)
    _open_contexts(host, dev, ops[:-1])
    item = ops[-1]
    walks = Walks(monkeypatch)
    if plant == "item":
        dev.begin_walk(item + 1)
    elif plant == "generation":
        dev.begin_walk(item)
        planted = dev._pending
        host.replace_index(idx2)
        dev.replace_index(idx2)
        _open_contexts(host, dev, seqb_stream(8, idx2, n_ops=40))
        dev._pending = planted[:1] + (idx1,) + (dev._op,) + planted[3:]
    elif plant == "reinstall":
        dev.begin_walk(item)
        host.replace_index(idx1)
        dev.replace_index(idx1)
    else:
        dev.begin_walk(item)
        planted = dev._pending
        assert host.on_request(item) == dev.on_request(item)
        dev._pending = planted
    assert walks.n["start_walk"] == 1
    assert host.on_request(item) == dev.on_request(item)
    assert host.n_live == dev.n_live
    assert walks.n["finish_walk"] == (plant == "earlier_op")
    assert dev._pending is None


def test_begin_walk_is_a_no_op_off_the_device_path(monkeypatch):
    index = random_index(3, n_patterns=10)
    host, dev = _engines(index)
    scalar = PrefetchEngine(index, host.cfg, 8)
    walks = Walks(monkeypatch)
    for item in seqb_stream(5, index, n_ops=40):
        host.begin_walk(item)
        assert host.on_request(item) == scalar.on_request(item)
    dev.begin_walk(0)                   # no live context yet
    assert dev._pending is None
    assert walks.n["start_walk"] == 0


def test_a_begun_walk_makes_no_program_once_warm():
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=2)
    eng = VectorizedPrefetchEngine(PTreeIndex.build([]), cfg,
                                   max_contexts=8, backend="jax")
    eng.warm_walk(2_000)
    with ProgramEvents() as ev:
        for seed in (21, 22):
            index = random_index(seed, n_patterns=30)
            eng.replace_index(index)
            for item in seqb_stream(seed, index, n_ops=60):
                eng.begin_walk(item)
                eng.on_request(item)
    assert ev.n == 0


# ---------------------------------------------------------------------------
# a TPC-C client: the column engine's walk begun before the main one
# ---------------------------------------------------------------------------

WORKLOAD = TPCC(TPCCConfig(customers_per_district=40, items=300,
                           orders_per_district=30, value_bytes=64))


def _sessions(seed, n):
    rng = np.random.default_rng(seed)
    return [WORKLOAD.transaction(rng) for _ in range(n)]


def tpcc_client(backend):
    """A column-mining client over a small TPC-C store.  The cache is
    small and the backlog cap low, so prefetches are evicted and shed."""
    store = SimulatedDKVStore(LatencyModel(seed=5))
    store.load(WORKLOAD.dataset())
    return PalpatineClient(store, PalpatineConfig(
        mining=MiningParams(minsup=0.05, min_len=2, max_len=8, maxgap=1),
        cache_bytes=4096, backlog_cap=0.0002, column_mining=True,
        decision_backend=backend, min_patterns=8,
        dynamic_minsup_start=0.3, dynamic_minsup_floor=0.02))


def serve_tpcc(client):
    """Backlog, a round, traffic, a second round (a new generation), more
    traffic; returns every read's answer and latency, every write's
    latency, each engine's targets and the cache counters."""
    out = {"reads": [], "writes": [], "main": [], "col": []}
    for eng, key in ((client.engine, "main"), (client.col_engine, "col")):
        def recorded(item, fn=eng.on_request, log=out[key]):
            targets = fn(item)
            log.append(targets)
            return targets
        eng.on_request = recorded

    def serve(sessions):
        for sess in sessions:
            for op, key in sess:
                if op == "w":
                    out["writes"].append(client.write(key, bytes(64)))
                else:
                    out["reads"].append(client.read(key))
            client.end_session()

    serve(_sessions(1, 120))
    client.mine_now()
    serve(_sessions(2, 60))
    gen = client.col_engine.index
    client.mine_now()
    assert client.col_engine.index is not gen
    serve(_sessions(3, 60))
    out["stats"] = vars(client.cache.stats).copy()
    return out


def test_a_tpcc_client_overlaps_its_walks_and_decides_the_same(
        monkeypatch):
    """The device path, with the column engine's walk begun before the
    main engine's, gives the numpy path's targets, answers, latencies
    and cache counters through writes, shed prefetches and a new
    generation; under a host profile each engine walk is one
    ``palp.walk`` call, and the begun ones are counted."""
    want = serve_tpcc(tpcc_client("numpy"))
    walks = Walks(monkeypatch)
    prof = obs.HostProfile()
    old = obs.set_host_profile(prof)
    try:
        got = serve_tpcc(tpcc_client("jax"))
    finally:
        obs.set_host_profile(old)
    assert got == want
    reads = len(want["reads"])
    assert want["writes"] and 0 < len(want["main"]) < reads   # shed
    assert len(want["col"]) == reads
    assert any(want["col"]) and any(want["main"])
    begun = walks.n["start_walk"]
    assert begun > 20 and walks.n["finish_walk"] == begun
    n = walks.n["decision_walk"] + begun
    assert prof.calls[obs.SPAN_HOST_WALK] == n
    assert all(prof.calls[p] == n for p in WALK_PHASES)
    assert prof.counters[obs.METRIC_WALK_OVERLAPPED] == begun
    assert prof.counters[obs.METRIC_WALK_H2D_COPIES] == n
    assert prof.counters[obs.METRIC_WALK_D2H_COPIES] == n
