"""decision_walk kernel parity: jitted ops vs the numpy reference, and
the jax-backed engine vs the scalar oracle end to end.

Not tier1 (imports jax); the numpy-only differential grid lives in
``test_decision.py``.
"""

import numpy as np
import pytest

from repro.core import (
    HeuristicConfig,
    Pattern,
    PrefetchEngine,
    PTreeIndex,
    VectorizedPrefetchEngine,
)
from repro.core import obs
from repro.kernels.decision_walk import ops as dw_ops
from repro.kernels.decision_walk import ref as dw_ref

from test_decision import HEURISTIC_CFGS, random_index, seqb_stream, \
    tpcc_stream


def live_states(flat, rng, n):
    """Random plausible context states over ``flat``: any non-leaf node,
    fetched between its depth and the tree max."""
    cand = np.flatnonzero(flat.n_children > 0)
    nodes = cand[rng.integers(0, len(cand), size=n)]
    trees = flat.tree_of[nodes]
    lo = flat.depth[nodes]
    hi = flat.tree_max_depth[trees]
    fetched = lo + (rng.random(n) * (hi - lo + 1)).astype(np.int64)
    return nodes, trees, fetched


@pytest.mark.parametrize("seed", range(5))
def test_decision_walk_ops_match_ref(seed):
    rng = np.random.default_rng(seed)
    flat = random_index(seed, n_patterns=12).flatten()
    if flat.n_nodes == 0 or not (flat.n_children > 0).any():
        pytest.skip("degenerate forest")
    jf = dw_ops.device_forest(flat)
    for trial in range(8):
        n = int(rng.integers(1, 9))
        nodes, trees, fetched = live_states(flat, rng, n)
        item = int(rng.integers(-2, flat.item_stride + 3))
        p_depth = int(rng.integers(1, 4))
        a = dw_ops.decision_walk(jf, flat, nodes, trees, fetched,
                                 item, p_depth, max_contexts=16)
        b = dw_ref.decision_walk_ref(flat, nodes, trees, fetched,
                                     item, p_depth)
        for key in ("found", "stay", "nodes", "alive", "fetched",
                    "wave_nodes"):
            np.testing.assert_array_equal(
                np.asarray(a[key]), np.asarray(b[key]), err_msg=key)


@pytest.mark.parametrize("seed", range(3))
def test_decision_walk_interpret_escape_hatch(seed):
    """`interpret=True` routes through the numpy reference and must
    agree with the jitted path bit for bit (palplint PALP203: every
    kernel entry point carries this escape hatch)."""
    rng = np.random.default_rng(seed)
    flat = random_index(seed, n_patterns=12).flatten()
    if flat.n_nodes == 0 or not (flat.n_children > 0).any():
        pytest.skip("degenerate forest")
    jf = dw_ops.device_forest(flat)
    for _ in range(4):
        n = int(rng.integers(1, 9))
        nodes, trees, fetched = live_states(flat, rng, n)
        item = int(rng.integers(-2, flat.item_stride + 3))
        jitted = dw_ops.decision_walk(jf, flat, nodes, trees, fetched,
                                      item, 2, max_contexts=16)
        interp = dw_ops.decision_walk(jf, flat, nodes, trees, fetched,
                                      item, 2, max_contexts=16,
                                      interpret=True)
        for key in ("found", "stay", "nodes", "alive", "fetched",
                    "wave_nodes"):
            np.testing.assert_array_equal(
                np.asarray(jitted[key]), np.asarray(interp[key]),
                err_msg=key)


WALK_PHASES = (obs.SPAN_HOST_WALK_UPLOAD, obs.SPAN_HOST_WALK_DISPATCH,
               obs.SPAN_HOST_WALK_WAIT, obs.SPAN_HOST_WALK_READBACK,
               obs.SPAN_HOST_WALK_UNPACK)


def _profiled(fn, *a, **kw):
    """``fn(*a, **kw)`` under a fresh active host profile; returns
    (result, profile) and puts the previous profile back."""
    prof = obs.HostProfile()
    old = obs.set_host_profile(prof)
    try:
        return fn(*a, **kw), prof
    finally:
        obs.set_host_profile(old)


def packed_bytes(c, n_nodes):
    """Bytes of the walk's packed output: per context five state columns
    and ceil(n_nodes / 32) wave words, all int32."""
    return c * (5 + -(-n_nodes // 32)) * 4


@pytest.mark.parametrize("seed", range(3))
def test_decision_walk_same_with_host_profile_on(seed, monkeypatch):
    """The profile changes no output; each phase is one span per call,
    inside ``palp.walk``; the counters count the one packed upload and
    the one packed read-back, by their bytes."""
    rng = np.random.default_rng(seed)
    flat = random_index(seed, n_patterns=12).flatten()
    if flat.n_nodes == 0 or not (flat.n_children > 0).any():
        pytest.skip("degenerate forest")
    jf = dw_ops.device_forest(flat)
    out_bytes = []
    step = dw_ops.decision_walk_step

    def recording(*a, **kw):
        out = step(*a, **kw)
        out_bytes.append(np.asarray(out).nbytes)
        return out
    monkeypatch.setattr(dw_ops, "decision_walk_step", recording)
    for _ in range(4):
        n = int(rng.integers(1, 9))
        nodes, trees, fetched = live_states(flat, rng, n)
        item = int(rng.integers(-2, flat.item_stride + 3))
        args = (jf, flat, nodes, trees, fetched, item, 2)
        off = dw_ops.decision_walk(*args, max_contexts=16)
        on, prof = _profiled(dw_ops.decision_walk, *args, max_contexts=16)
        for key in ("found", "stay", "nodes", "alive", "fetched",
                    "wave_nodes"):
            np.testing.assert_array_equal(off[key], on[key], err_msg=key)
            assert off[key].dtype == on[key].dtype, key
        assert prof.calls == {obs.SPAN_HOST_WALK: 1,
                              **{p: 1 for p in WALK_PHASES}}
        walk = prof.seconds[obs.SPAN_HOST_WALK]
        phases = sum(prof.seconds[p] for p in WALK_PHASES)
        assert prof.child_seconds[obs.SPAN_HOST_WALK] == phases <= walk
        assert prof.counters == {
            obs.METRIC_WALK_H2D_COPIES: 1,
            obs.METRIC_WALK_H2D_BYTES: (3 * 16 + 2) * 4,
            obs.METRIC_WALK_D2H_COPIES: 1,
            obs.METRIC_WALK_D2H_BYTES: packed_bytes(16, jf.n_padded),
        }
    # the step's one output is the bit-packed array over the forest's
    # rung of the node ladder, nothing more
    assert out_bytes[-1] == packed_bytes(16, jf.n_padded)


def branching_forest(n_nodes):
    """One tree of exactly ``n_nodes`` (>= 2): root 0, its child 1, and
    the leaves 2 .. n_nodes - 1 under 1, so stepping a root context by 1
    emits a wave over every node but the root."""
    pats = ([Pattern((0, 1), 3)] if n_nodes == 2 else
            [Pattern((0, 1, k), 3) for k in range(2, n_nodes)])
    flat = PTreeIndex.build(pats).flatten()
    assert flat.n_nodes == n_nodes
    return flat


#: items: none (-1), past the vocabulary, and the one that emits a wave
EDGE_ITEMS = {"none": lambda flat: -1,
              "outside": lambda flat: flat.item_stride + 5,
              "match": lambda flat: 1}


@pytest.mark.parametrize("item_kind", list(EDGE_ITEMS))
@pytest.mark.parametrize("live", ["one", "all"])
@pytest.mark.parametrize("n_nodes", [2, 31, 32, 33, 64, 65])
def test_decision_walk_bit_packing_edges(n_nodes, live, item_kind):
    """The packed read-back at the word edges of the wave mask: forests
    one node short of, at and past 32 and 64 nodes (2 is the smallest a
    pattern builds), one live context or all C, and every outcome of the
    item.  Every output equals the reference's, dtype included, and the
    wave comes back row-major."""
    c = 16
    flat = branching_forest(n_nodes)
    jf = dw_ops.device_forest(flat)
    rng = np.random.default_rng(n_nodes)
    n = 1 if live == "one" else c
    nodes, trees, fetched = live_states(flat, rng, n)
    nodes[0], fetched[0] = 0, 0         # a root context, nothing fetched
    item = EDGE_ITEMS[item_kind](flat)
    a = dw_ops.decision_walk(jf, flat, nodes, trees, fetched, item, 2,
                             max_contexts=c)
    b = dw_ref.decision_walk_ref(flat, nodes, trees, fetched, item, 2)
    for key in ("found", "stay", "nodes", "alive", "fetched",
                "wave_nodes"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
        assert a[key].dtype == b[key].dtype, key
    if item_kind == "match":
        # context 0's wave is every node but the root, the last word's
        # last node included; later contexts' waves follow it
        np.testing.assert_array_equal(a["wave_nodes"][:n_nodes - 1],
                                      np.arange(1, n_nodes))
    else:
        assert a["wave_nodes"].size == 0


def test_decision_walk_escape_paths_record_nothing():
    flat = random_index(0, n_patterns=12).flatten()
    jf = dw_ops.device_forest(flat)
    nodes, trees, fetched = live_states(flat, np.random.default_rng(0), 3)
    _, prof = _profiled(dw_ops.decision_walk, jf, flat, nodes, trees,
                        fetched, 1, 2, max_contexts=16, interpret=True)
    assert not prof.calls and not prof.counters
    empty = PTreeIndex.build([]).flatten()
    z = np.empty(0, np.int64)
    _, prof = _profiled(dw_ops.decision_walk, dw_ops.device_forest(empty),
                        empty, z, z, z, 3, 2, max_contexts=4)
    assert not prof.calls and not prof.counters


def test_decision_walk_spans_nest_in_a_profiler_trace(tmp_path):
    """The profile's spans are profiler annotations: a trace holds each
    ``palp.walk`` with its five phases inside it, in order."""
    import jax
    from jax.profiler import ProfileData

    flat = random_index(0, n_patterns=12).flatten()
    jf = dw_ops.device_forest(flat)
    nodes, trees, fetched = live_states(flat, np.random.default_rng(1), 4)
    args = (jf, flat, nodes, trees, fetched, 1, 2)
    dw_ops.decision_walk(*args, max_contexts=16)          # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            _profiled(dw_ops.decision_walk, *args, max_contexts=16)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {obs.SPAN_HOST_WALK, *WALK_PHASES}
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for plane in ProfileData.from_file(str(path)).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name in names)
    walks = [sp for sp in spans if sp[2] == obs.SPAN_HOST_WALK]
    assert len(walks) == 3
    for w0, w1, _ in walks:
        inside = [sp for sp in spans
                  if sp[2] != obs.SPAN_HOST_WALK and w0 <= sp[0]
                  and sp[1] <= w1]
        assert [name for _, _, name in inside] == list(WALK_PHASES)
        for (_, end, _), (start, _, _) in zip(inside, inside[1:]):
            assert end <= start


#: a paper-scale item vocabulary: with ~10^3 nodes, node id × item_stride
#: passes 2^31, where a packed ``parent * item_stride + item`` int32 key
#: would wrap
BIG_VOCAB = 3_000_000


def big_vocab_index(seed, n_patterns=300):
    """Random patterns over BIG_VOCAB items; a few shared roots and second
    items make the trees branch."""
    rng = np.random.default_rng(seed)
    roots = rng.integers(0, BIG_VOCAB, size=6)
    seconds = rng.integers(0, BIG_VOCAB, size=4)
    pats = []
    for _ in range(n_patterns):
        tail = rng.integers(0, BIG_VOCAB, size=int(rng.integers(1, 6)))
        items = (int(roots[rng.integers(len(roots))]),
                 int(seconds[rng.integers(len(seconds))]),
                 *(int(x) for x in tail))
        pats.append(Pattern(items, int(rng.integers(2, 40))))
    return PTreeIndex.build(pats)


@pytest.mark.parametrize("seed", range(2))
def test_decision_walk_ops_match_ref_past_int32_keys(seed):
    rng = np.random.default_rng(seed)
    flat = big_vocab_index(seed).flatten()
    assert (flat.n_nodes - 1) * flat.item_stride > 2 ** 31
    jf = dw_ops.device_forest(flat)
    for _ in range(8):
        nodes, trees, fetched = live_states(flat, rng, 16)
        # half the trials step by a real child item of a live node
        v = int(nodes[rng.integers(len(nodes))])
        kids = flat.items[flat.first_child[v]:
                          flat.first_child[v] + flat.n_children[v]]
        item = (int(kids[rng.integers(len(kids))]) if rng.random() < 0.5
                else int(rng.integers(-2, BIG_VOCAB + 3)))
        a = dw_ops.decision_walk(jf, flat, nodes, trees, fetched,
                                 item, 2, max_contexts=32)
        b = dw_ref.decision_walk_ref(flat, nodes, trees, fetched, item, 2)
        for key in ("found", "stay", "nodes", "alive", "fetched",
                    "wave_nodes"):
            np.testing.assert_array_equal(
                np.asarray(a[key]), np.asarray(b[key]), err_msg=key)


@pytest.mark.parametrize("p_depth", [1, 2, 3])
def test_jax_backend_lockstep_past_int32_keys(p_depth):
    """The device walk agrees with the numpy engine op for op on a forest
    whose packed edge keys would overflow int32."""
    index = big_vocab_index(7)
    flat = index.flatten()
    assert (flat.n_nodes - 1) * flat.item_stride > 2 ** 31
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=p_depth)
    host = VectorizedPrefetchEngine(index, cfg, max_contexts=16)
    dev = VectorizedPrefetchEngine(index, cfg, max_contexts=16,
                                   backend="jax")
    waves = 0
    for i, item in enumerate(seqb_stream(p_depth, index, n_ops=240,
                                         alphabet=BIG_VOCAB)):
        a, b = host.on_request(item), dev.on_request(item)
        assert a == b, (i, item, a, b)
        assert host.n_live == dev.n_live
        waves += bool(a)
    assert waves > 20


@pytest.mark.parametrize("cfg", HEURISTIC_CFGS, ids=lambda c: c.name)
def test_jax_backend_lockstep_on_empty_forest(cfg):
    index = PTreeIndex.build([])
    host = VectorizedPrefetchEngine(index, cfg, max_contexts=8)
    dev = VectorizedPrefetchEngine(index, cfg, max_contexts=8,
                                   backend="jax")
    for item in (-1, 0, 3, 2 ** 31 + 5, 7):
        assert host.on_request(item) == dev.on_request(item) == []
        assert host.n_live == dev.n_live == 0


def test_device_forest_refuses_ids_past_int32():
    flat = PTreeIndex.build([Pattern((1, 2, 2 ** 31 + 5), 3)]).flatten()
    with pytest.raises(OverflowError):
        dw_ops.device_forest(flat)


def test_decision_walk_empty_edge_table():
    flat = PTreeIndex.build([]).flatten()
    jf = dw_ops.device_forest(flat)
    out = dw_ops.decision_walk(jf, flat, np.empty(0, np.int64),
                               np.empty(0, np.int64),
                               np.empty(0, np.int64), 3, 2,
                               max_contexts=4)
    assert out["wave_nodes"].size == 0 and out["alive"].size == 0


def test_top_k_frontier_matches_oracle():
    idx = PTreeIndex.build([
        Pattern((0, 1, 2), 70),
        Pattern((0, 3, 4), 21),
        Pattern((0, 3, 5), 9),
    ])
    tree = idx.match_root(0)
    flat = idx.flatten()
    s, e = int(flat.tree_start[0]), int(flat.tree_start[1])
    for k in (1, 2, 3, 5, 10):
        sel = np.asarray(dw_ops.top_k_frontier(
            flat.cum_prob[s + 1:e], flat.depth[s + 1:e], k=min(k, e - s - 1)))
        got = flat.items[s + 1 + sel].tolist()
        want = [n.item for n in tree.top_n_cumulative(k)]
        assert got == want, k


@pytest.mark.parametrize("cfg", HEURISTIC_CFGS, ids=lambda c: c.name)
@pytest.mark.parametrize("stream", [seqb_stream, tpcc_stream],
                         ids=["seqb", "tpcc"])
def test_jax_backend_engine_matches_scalar(cfg, stream):
    for seed in range(2):
        index = random_index(seed, n_patterns=10)
        ref = PrefetchEngine(index, cfg, max_contexts=8)
        vec = VectorizedPrefetchEngine(index, cfg, max_contexts=8,
                                       backend="jax")
        for i, item in enumerate(stream(seed + 3, index, n_ops=120)):
            a, b = ref.on_request(item), vec.on_request(item)
            assert a == b, (seed, i, item, a, b)
            assert ref.n_live == vec.n_live


def test_jax_backend_replace_index_mid_stream():
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=2)
    idx1, idx2 = random_index(11), random_index(12)
    ref = PrefetchEngine(idx1, cfg, max_contexts=8)
    vec = VectorizedPrefetchEngine(idx1, cfg, max_contexts=8, backend="jax")
    ops = seqb_stream(7, idx1, n_ops=60) + seqb_stream(8, idx2, n_ops=60)
    for i, item in enumerate(ops):
        if i == 60:
            ref.replace_index(idx2)
            vec.replace_index(idx2)
        assert ref.on_request(item) == vec.on_request(item), (i, item)
