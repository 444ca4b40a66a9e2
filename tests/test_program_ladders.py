"""The device programs a mining round can use form a finite set, made
ahead: the frontier join pads onto its row ladder and the decision walk
onto its node ladder, each answer equals the plain path's at every
ladder edge, and once warm a round or a generation swap makes no
program (counted through ``jax.monitoring``).  CPU, interpret mode.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (
    HeuristicConfig,
    LatencyModel,
    MiningParams,
    PalpatineClient,
    PalpatineConfig,
    Pattern,
    PTreeIndex,
    SimulatedDKVStore,
    VectorizedPrefetchEngine,
)
from repro.core import obs
from repro.core.mining import _frontier_support
from repro.kernels.bitmap_support import ops as bm_ops
from repro.kernels.bitmap_support import ref as bm_ref
from repro.kernels.decision_walk import ops as dw_ops
from repro.kernels.decision_walk import ref as dw_ref

from test_decision_kernel import branching_forest, live_states

#: the events of JAX making a program: a trace, a backend compile, a
#: load from the persistent cache
PROGRAM_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class ProgramEvents:
    """Counts the program events JAX reports while it is open."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self.on_event)
        return self

    def on_event(self, event, secs, **_):
        self.n += event in PROGRAM_EVENTS

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self.on_event)
        return False


def _bits(rng, shape, density):
    words = rng.integers(0, 2 ** 32, size=shape, dtype=np.uint32)
    return np.where(rng.random(shape) < density, words, 0).astype(np.uint32)


#: (P, K, S, W) on both sides of every rung and tile edge of the rows,
#: the session block and the word powers of two
EDGES = [
    (1, 1, 1, 1), (7, 9, 511, 1), (8, 8, 512, 1), (9, 7, 513, 2),
    (31, 33, 100, 3), (32, 32, 64, 4), (33, 31, 700, 1),
    (127, 129, 40, 1), (128, 128, 513, 2), (129, 127, 30, 5),
    (511, 3, 20, 1), (512, 5, 20, 1), (513, 2, 20, 1),
    (3, 513, 20, 1), (600, 1030, 10, 1),
]


@pytest.mark.parametrize("p,k,s,w", EDGES)
def test_bucketed_frontier_join_equals_the_plain_paths(p, k, s, w):
    rng = np.random.default_rng(p * 7919 + k * 31 + s + w)
    slots = _bits(rng, (p, s, w), 0.3)
    cand = _bits(rng, (k, s, w), 0.3)
    got = bm_ops.frontier_join_support(slots, cand)
    assert got.dtype == np.int32 and got.shape == (p, k)
    np.testing.assert_array_equal(got, bm_ref.frontier_join_support(slots,
                                                                    cand))
    plain = _frontier_support(slots, cand, MiningParams())
    np.testing.assert_array_equal(got, plain)
    # a tail's padding changes the shape called, not the answer
    np.testing.assert_array_equal(
        bm_ops.frontier_join_support(slots, cand, min_sessions=s + 600), got)


@pytest.mark.parametrize("p,k", [(0, 4), (4, 0), (0, 0)])
def test_an_empty_frontier_join_makes_no_program(p, k):
    with ProgramEvents() as ev:
        out = bm_ops.frontier_join_support(np.zeros((p, 9, 1), np.uint32),
                                           np.zeros((k, 9, 1), np.uint32))
    assert out.shape == (p, k) and ev.n == 0


def test_every_join_calls_a_program_of_its_ladder():
    progs = {prog[:4] for prog in bm_ops.frontier_programs(300, 2)}
    assert len(progs) == len(bm_ops.ROW_LADDER) ** 2
    for p in range(1, 1200, 37):
        for k in (1, 8, 9, 129, 512, 513, 1100):
            assert set(bm_ops.frontier_calls(p, k, 300, 2)) <= progs


def test_a_warm_join_makes_no_program():
    bm_ops.warm_frontier_join(100, 1, min_sessions=200)
    rng = np.random.default_rng(3)
    with ProgramEvents() as ev:
        for p, k, s in [(1, 1, 100), (9, 200, 150), (40, 7, 200),
                        (600, 3, 10)]:
            slots, cand = _bits(rng, (p, s, 1), 0.5), _bits(rng, (k, s, 1), 0.5)
            got = bm_ops.frontier_join_support(slots, cand, min_sessions=200)
            np.testing.assert_array_equal(
                got, bm_ref.frontier_join_support(slots, cand))
    assert ev.n == 0


def multi_tree_forest(n_trees, depth=3):
    """``n_trees`` chains of ``depth`` + 1 nodes, so T + 1 and N cross
    the node ladder's edges apart."""
    pats = [Pattern(tuple(range(t * 10, t * 10 + depth + 1)), 3)
            for t in range(n_trees)]
    return PTreeIndex.build(pats).flatten()


FORESTS = ([("branching", n) for n in (31, 32, 33, 63, 64, 65, 127, 128, 129)]
           + [("trees", t) for t in (7, 8, 15, 16, 17, 31, 32, 33)])


@pytest.mark.parametrize("kind,n", FORESTS)
def test_bucketed_walk_equals_the_reference(kind, n):
    flat = branching_forest(n) if kind == "branching" else multi_tree_forest(n)
    jf = dw_ops.device_forest(flat)
    assert jf.n_padded == dw_ops.node_bucket(max(flat.n_nodes,
                                                 flat.n_trees + 1))
    rng = np.random.default_rng(n)
    for _ in range(6):
        nodes, trees, fetched = live_states(flat, rng, int(rng.integers(1, 9)))
        kids = flat.items[flat.first_child[nodes[0]]:
                          flat.first_child[nodes[0]]
                          + flat.n_children[nodes[0]]]
        item = (int(kids[0]) if rng.random() < 0.6
                else int(rng.integers(-2, flat.item_stride + 3)))
        a = dw_ops.decision_walk(jf, flat, nodes, trees, fetched, item, 2,
                                 max_contexts=16)
        b = dw_ref.decision_walk_ref(flat, nodes, trees, fetched, item, 2)
        for key in ("found", "stay", "nodes", "alive", "fetched",
                    "wave_nodes"):
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)


def test_the_node_ladder_covers_its_bound():
    ladder = dw_ops.node_ladder(150_000)
    assert ladder[0] == dw_ops.MIN_NODES and ladder[-1] == 262_144
    assert all(b == 2 * a for a, b in zip(ladder, ladder[1:]))
    for n in (1, 31, 32, 33, 1000, 65_536, 65_537, 149_999, 150_000):
        assert dw_ops.node_bucket(n) in ladder


def test_a_generation_swap_makes_no_program_once_warm():
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=2)
    eng = VectorizedPrefetchEngine(PTreeIndex.build([]), cfg,
                                   max_contexts=8, backend="jax")
    eng.warm_walk(200)
    gens = [PTreeIndex.build([Pattern(tuple(range(t * 10, t * 10 + d)), 3)
                              for t in range(trees)])
            for trees, d in [(2, 3), (9, 4), (30, 5), (12, 2)]]
    with ProgramEvents() as ev:
        for index in gens:
            eng.replace_index(index)
            assert eng.walk_program_made()
            for item in (0, 1, 2, 10, 11, 12, 13):
                eng.on_request(item)
    assert ev.n == 0


def _client(cfg: PalpatineConfig) -> PalpatineClient:
    store = SimulatedDKVStore(LatencyModel(jitter_sigma=0.0, stall_frac=0.0))
    store.load((i, bytes([i % 251]) * 8) for i in range(400))
    return PalpatineClient(store, cfg)


def _online(**kw) -> PalpatineConfig:
    return PalpatineConfig(
        mining=MiningParams(minsup=0.1, min_len=3, max_len=6, maxgap=1,
                            use_kernel=True),
        decision_backend="jax", online_mine_every=60,
        online_tail_sessions=30, metastore_capacity=40, min_patterns=4,
        dynamic_minsup_start=0.2, dynamic_minsup_floor=0.05, **kw)


def _sessions(rng, n, longest=6):
    base = [[1, 2, 3, 4, 5], [7, 8, 9, 10], [20, 21, 22, 23, 24, 25]]
    return [base[int(rng.integers(3))] if rng.random() < 0.7 else
            list(rng.integers(30, 400, size=int(rng.integers(3, longest))))
            for _ in range(n)]


def _serve(client, sessions):
    for s in sessions:
        for key in s:
            client.read(int(key))
        client.end_session()


def test_online_rounds_make_no_program_after_the_first():
    client = _client(_online())
    rng = np.random.default_rng(0)
    _serve(client, _sessions(rng, 20))
    assert client.mining_runs >= 1
    runs = client.mining_runs
    with ProgramEvents() as ev:
        _serve(client, _sessions(rng, 60))
    assert client.mining_runs >= runs + 3
    assert ev.n == 0 and client.cold_programs == 0


def test_a_shape_past_the_warm_ladder_is_counted():
    """Sessions past 32 accesses need two words a session: the join's
    programs for them were not made ahead, and the round counts them.
    (A round cuts the open session, so the long one spans two rounds; a
    tail of its own keeps other tests' programs out of its count.)"""
    client = _client(dataclasses.replace(_online(), online_tail_sessions=2500))
    rng = np.random.default_rng(1)
    _serve(client, _sessions(rng, 20))
    prof = obs.HostProfile()
    old = obs.set_host_profile(prof)
    try:
        _serve(client, [list(range(100, 220))] + _sessions(rng, 20))
    finally:
        obs.set_host_profile(old)
    assert client.cold_programs > 0
    assert prof.counters[obs.METRIC_MINE_COLD_PROGRAMS] == client.cold_programs


def test_online_rounds_book_their_spans():
    client = _client(_online())
    rng = np.random.default_rng(2)
    prof = obs.HostProfile()
    old = obs.set_host_profile(prof)
    try:
        _serve(client, _sessions(rng, 40))
    finally:
        obs.set_host_profile(old)
    rounds = prof.calls[obs.SPAN_HOST_MINE]
    assert rounds == client.mining_runs >= 2
    assert prof.calls[obs.SPAN_HOST_MINE_WARM] == 1
    assert prof.calls[obs.SPAN_HOST_MINE_REBUILD] == rounds
    assert prof.calls[obs.SPAN_HOST_MINE_JOIN] >= rounds
    assert prof.calls[obs.SPAN_HOST_MINE_BITMAPS] >= rounds
    assert prof.counters[obs.METRIC_MINE_JOIN_H2D_BYTES] > 0
    # the round's parts are inside it
    inside = sum(prof.seconds[s] for s in (
        obs.SPAN_HOST_MINE_WARM, obs.SPAN_HOST_MINE_REBUILD,
        obs.SPAN_HOST_MINE_JOIN, obs.SPAN_HOST_MINE_BITMAPS))
    assert inside <= prof.seconds[obs.SPAN_HOST_MINE]


def test_an_offline_client_warms_nothing():
    cfg = dataclasses.replace(_online(), online_mine_every=None)
    client = _client(cfg)
    prof = obs.HostProfile()
    old = obs.set_host_profile(prof)
    try:
        _serve(client, _sessions(np.random.default_rng(3), 30))
        client.mine_now()
    finally:
        obs.set_host_profile(old)
    assert obs.SPAN_HOST_MINE_WARM not in prof.calls
    assert client.cold_programs == 0 and prof.calls[obs.SPAN_HOST_MINE] == 1
