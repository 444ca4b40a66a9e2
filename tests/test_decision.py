"""Differential suite: the vectorized decision engine vs the scalar oracle.

The two engines must agree *exactly* — same prefetch items, same wave
order, same live-context count after every op — across the heuristic grid
(fetch_all / fetch_top_n / fetch_progressive), SEQB-like scan streams and
TPC-C-like transaction streams, and the context-churn edge cases the
per-op path is most likely to get wrong: divergence, leaf exhaustion,
``replace_index`` mid-stream, out-of-vocab items, and tiny
``max_contexts`` (eviction pressure).

Also pins the three context-management fixes:

* saturation no longer silently drops a fresh progressive context (the
  stalest one is evicted, so follow-up waves keep flowing);
* a re-confirmed root dedupes onto the live context instead of dying and
  reopening (no duplicate contexts, no recomputed initial wave);
* length-1 patterns never become depth-0 trees / do-nothing contexts.
"""

import numpy as np
import pytest

from repro.core import (
    HeuristicConfig,
    Pattern,
    PrefetchEngine,
    PTree,
    PTreeIndex,
    VectorizedPrefetchEngine,
)
from repro.core.heuristics import PrefetchContext

pytestmark = pytest.mark.tier1


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------


def random_index(seed, n_patterns=None, alphabet=24, max_len=6):
    """Random pattern set → PTreeIndex (shared prefixes arise naturally
    from the small alphabet, so trees branch)."""
    rng = np.random.default_rng(seed)
    n = n_patterns or int(rng.integers(3, 14))
    pats = []
    for _ in range(n):
        ln = int(rng.integers(1, max_len + 1))  # length-1 included: guarded
        items = tuple(int(x) for x in rng.integers(0, alphabet, size=ln))
        pats.append(Pattern(items, int(rng.integers(1, 40))))
    return PTreeIndex.build(pats)


def seqb_stream(seed, index, n_ops=160, alphabet=24):
    """SEQB-like: mostly replays of mined sequences (sequential scans)
    with occasional divergence and out-of-vocab noise."""
    rng = np.random.default_rng(seed)
    roots = sorted(index.trees)
    ops, i = [], 0
    while i < n_ops:
        if roots and rng.random() < 0.8:
            tree = index.trees[roots[int(rng.integers(len(roots)))]]
            node, path = tree.root, [tree.root.item]
            while node.children and rng.random() < 0.85:
                ch = sorted(node.children)
                node = node.children[ch[int(rng.integers(len(ch)))]]
                path.append(node.item)
            if rng.random() < 0.3:  # diverge mid-walk
                cut = int(rng.integers(1, len(path) + 1))
                path = path[:cut] + [int(rng.integers(alphabet))]
            ops.extend(path)
            i += len(path)
        else:
            ops.append(int(rng.integers(-2, alphabet + 4)))  # incl. OOV
            i += 1
    return ops[:n_ops]


def tpcc_stream(seed, index, n_ops=160, alphabet=24):
    """TPC-C-like: a few hot transaction motifs interleaved per 'client',
    plus uniform noise — exercises many concurrent contexts."""
    rng = np.random.default_rng(seed)
    motifs = [list(rng.integers(0, alphabet, size=int(rng.integers(2, 6))))
              for _ in range(4)]
    cursors = [0] * len(motifs)
    ops = []
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.75:
            m = int(rng.integers(len(motifs)))
            ops.append(int(motifs[m][cursors[m]]))
            cursors[m] = (cursors[m] + 1) % len(motifs[m])
        else:
            ops.append(int(rng.integers(0, alphabet)))
    return ops


HEURISTIC_CFGS = [
    HeuristicConfig("fetch_all"),
    HeuristicConfig("fetch_top_n", top_n=3),
    HeuristicConfig("fetch_progressive", progressive_depth=2),
]


def assert_lockstep(index, cfg, ops, max_contexts=256, replace_at=None,
                    replacement=None):
    """Drive both engines through ``ops`` and require exact agreement."""
    ref = PrefetchEngine(index, cfg, max_contexts)
    vec = VectorizedPrefetchEngine(index, cfg, max_contexts)
    for i, item in enumerate(ops):
        if replace_at is not None and i == replace_at:
            ref.replace_index(replacement)
            vec.replace_index(replacement)
        a, b = ref.on_request(item), vec.on_request(item)
        assert a == b, (i, item, a, b)
        assert ref.n_live == vec.n_live, (i, item)
    return ref, vec


# ---------------------------------------------------------------------------
# the differential grid
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", HEURISTIC_CFGS, ids=lambda c: c.name)
@pytest.mark.parametrize("stream", [seqb_stream, tpcc_stream],
                         ids=["seqb", "tpcc"])
@pytest.mark.parametrize("seed", range(6))
def test_engines_agree_on_stream_grid(cfg, stream, seed):
    index = random_index(seed)
    ops = stream(seed + 1000, index)
    assert_lockstep(index, cfg, ops)


@pytest.mark.parametrize("cfg", HEURISTIC_CFGS, ids=lambda c: c.name)
@pytest.mark.parametrize("max_contexts", [1, 2, 5])
def test_engines_agree_under_eviction_pressure(cfg, max_contexts):
    for seed in range(4):
        index = random_index(seed, n_patterns=10)
        ops = tpcc_stream(seed + 7, index, n_ops=200)
        assert_lockstep(index, cfg, ops, max_contexts=max_contexts)


@pytest.mark.parametrize("cfg", HEURISTIC_CFGS, ids=lambda c: c.name)
def test_engines_agree_across_replace_index(cfg):
    for seed in range(4):
        index = random_index(seed)
        nxt = random_index(seed + 50)
        ops = seqb_stream(seed, index, n_ops=80) + \
            seqb_stream(seed + 1, nxt, n_ops=80)
        ref, vec = assert_lockstep(index, cfg, ops, replace_at=80,
                                   replacement=nxt)
        assert vec.index is nxt and ref.index is nxt


def test_engines_agree_on_empty_index():
    empty = PTreeIndex.build([])
    for cfg in HEURISTIC_CFGS:
        assert_lockstep(empty, cfg, [0, 1, -3, 10**9, 2])


def test_leaf_exhaustion_reaps_context_in_both():
    # single chain a->b->c: confirming to the leaf must kill the context
    index = PTreeIndex.build([Pattern((0, 1, 2), 10)])
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=1)
    ref, vec = assert_lockstep(index, cfg, [0, 1, 2, 1, 2])
    assert ref.n_live == 0 and vec.n_live == 0


def test_divergence_kills_context_in_both():
    index = PTreeIndex.build([Pattern((0, 1, 2, 3), 10)])
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=2)
    ref, vec = assert_lockstep(index, cfg, [0, 1, 99])
    assert ref.n_live == 0 and vec.n_live == 0


# ---------------------------------------------------------------------------
# bugfix regressions (both engines)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", [PrefetchEngine, VectorizedPrefetchEngine],
                         ids=["scalar", "vectorized"])
def test_saturation_evicts_stalest_not_newest(engine):
    """At max_contexts, a fresh root match used to be silently dropped —
    its follow-up progressive waves never fired.  Now the stalest
    context is evicted and the new one keeps waving.

    The new root item (1) is also the next step of the live chain, so
    the old context is genuinely alive when saturation is hit — the
    eviction path, not the divergence reaper, must make room."""
    index = PTreeIndex.build([
        Pattern((0, 1, 2, 3, 4), 10),
        Pattern((1, 5, 6, 7), 10),
    ])
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=1)
    eng = engine(index, cfg, max_contexts=1)
    assert eng.on_request(0) == [1]      # ctx A opened (saturated now)
    # A advances (wave [2]) AND root 1 opens ctx B, evicting live A
    assert eng.on_request(1) == [2, 5]
    assert eng.n_live == 1
    # the regression: B's follow-up waves must fire
    assert eng.on_request(5) == [6]
    assert eng.on_request(6) == [7]
    # and A really is gone — its old continuation does nothing
    assert eng.on_request(2) == []
    assert eng.n_live == 0


@pytest.mark.parametrize("engine", [PrefetchEngine, VectorizedPrefetchEngine],
                         ids=["scalar", "vectorized"])
def test_eviction_removes_oldest_keeps_rest(engine):
    """Three overlapping chains keep two contexts live when the third
    root arrives; the oldest is evicted, the newcomer is appended, and
    both survivors keep waving.  (Live progressive contexts are all
    re-stamped every op they survive, so 'stalest' resolves to the
    oldest list position — pinned here.)"""
    index = PTreeIndex.build([
        Pattern((0, 1, 2, 3, 4, 5), 10),
        Pattern((2, 3, 4, 9, 6), 10),
        Pattern((4, 8, 7), 10),
    ])
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=1)
    eng = engine(index, cfg, max_contexts=2)
    assert eng.on_request(0) == [1]          # ctx A
    assert eng.on_request(1) == [2]
    assert eng.on_request(2) == [3]          # ctx B opens (wave deduped)
    assert eng.on_request(3) == [4]          # A and B advance together
    # A and B advance, root 4 opens ctx C: A (oldest) is evicted
    assert eng.on_request(4) == [5, 9, 8]
    assert eng.n_live == 2
    # B survived the eviction (A would have died on 9 silently)
    assert eng.on_request(9) == [6]
    assert eng.n_live == 1


@pytest.mark.parametrize("engine", [PrefetchEngine, VectorizedPrefetchEngine],
                         ids=["scalar", "vectorized"])
def test_root_reconfirm_dedupes_instead_of_reopening(engine):
    """Re-requesting the root an open context sits on must neither kill
    it, duplicate it, nor replay the initial wave."""
    index = PTreeIndex.build([Pattern((0, 1, 2, 3), 10)])
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=1)
    eng = engine(index, cfg, max_contexts=4)
    assert eng.on_request(0) == [1]
    for _ in range(3):                   # hammer the root
        assert eng.on_request(0) == []   # no recomputed wave
        assert eng.n_live == 1           # no duplicates
    assert eng.on_request(1) == [2]      # still advances normally


@pytest.mark.parametrize("engine", [PrefetchEngine, VectorizedPrefetchEngine],
                         ids=["scalar", "vectorized"])
def test_root_reconfirm_survives_alongside_advancing_context(engine):
    """The stay rule composes with batch advancement: one context
    advances on the item while another sits on it as a re-confirmed
    root."""
    index = PTreeIndex.build([
        Pattern((5, 0, 0, 6), 10),       # chain that passes through 0
        Pattern((0, 1, 2), 10),          # tree rooted at 0
    ])
    cfg = HeuristicConfig("fetch_progressive", progressive_depth=1)
    eng = engine(index, cfg, max_contexts=4)
    assert eng.on_request(5) == [0]          # ctx B on the chain
    assert eng.on_request(0) == [0, 1]       # B advances; ctx A opens
    assert eng.n_live == 2
    # 0 again: B advances 0->0 (wave [6]), A re-confirms its root (stays)
    assert eng.on_request(0) == [6]
    assert eng.n_live == 2
    assert eng.on_request(1) == [2]          # A still advances normally
    assert eng.n_live == 1                   # B diverged on 1


def test_length_one_patterns_never_build_trees():
    idx = PTreeIndex.build([Pattern((5,), 100), Pattern((7,), 3)])
    assert len(idx) == 0
    idx = PTreeIndex.build([Pattern((5,), 100), Pattern((5, 6), 3)])
    assert len(idx) == 1 and idx.match_root(5).max_depth == 1


def test_initial_refuses_depth_zero_tree():
    tree = PTree(9)
    tree.insert((9,), 10)
    tree.finalize()
    assert tree.max_depth == 0
    ctx = PrefetchContext(tree, HeuristicConfig("fetch_progressive"))
    assert ctx.initial() == [] and not ctx.alive


def test_do_nothing_contexts_never_open():
    # engine built atop an index where one root would be depth-0 if the
    # build guard regressed
    idx = PTreeIndex.build([Pattern((5,), 100), Pattern((0, 1), 10)])
    for engine in (PrefetchEngine, VectorizedPrefetchEngine):
        eng = engine(idx, HeuristicConfig("fetch_progressive"))
        assert eng.on_request(5) == []
        assert eng.n_live == 0


# ---------------------------------------------------------------------------
# FlatForest structure
# ---------------------------------------------------------------------------


def fig3_index():
    a, b, c, d, e, i, j, k = range(8)
    return PTreeIndex.build([
        Pattern((a, d, i), 70),
        Pattern((a, e, j), 21),
        Pattern((a, e, k), 9),
        Pattern((b, d, i), 10),
        Pattern((c, d, i), 10),
    ])


def test_flatten_structure_invariants():
    flat = fig3_index().flatten()
    n = flat.n_nodes
    assert n == 6 + 3 + 3 and flat.n_trees == 3
    # ids are level-order per tree: depth non-decreasing inside a tree
    for t in range(flat.n_trees):
        s, e = flat.tree_start[t], flat.tree_start[t + 1]
        assert flat.depth[s] == 0
        assert (np.diff(flat.depth[s:e]) >= 0).all()
        assert (flat.tree_of[s:e] == t).all()
    # children are contiguous and consistent with the edge table
    for v in range(n):
        for c in range(flat.first_child[v],
                       flat.first_child[v] + flat.n_children[v]):
            key = v * flat.item_stride + flat.items[c]
            pos = np.searchsorted(flat.edge_keys, key)
            assert flat.edge_keys[pos] == key
            assert flat.edge_child[pos] == c
    # DFS preorder intervals nest properly
    assert (flat.post > flat.pre).all()
    sizes = flat.post - flat.pre
    roots = flat.tree_start[:-1]
    assert (sizes[roots] == np.diff(flat.tree_start)).all()
    # edge keys strictly sorted (parent, item) pairs are unique
    assert (np.diff(flat.edge_keys) > 0).all()


def test_flatten_matches_scalar_walks():
    idx = fig3_index()
    flat = idx.flatten()
    for root, tree in idx.trees.items():
        t = flat.root_tree[root]
        rid = flat.tree_start[t]
        assert flat.items[rid] == root
        for nd in tree.root.level_order():
            if nd.parent is None:
                continue
            key_hits = np.flatnonzero(
                (flat.tree_of == t) & (flat.items == nd.item)
                & (flat.depth == nd.depth))
            assert any(
                abs(flat.prob[h] - nd.prob) < 1e-12
                and abs(flat.cum_prob[h] - nd.cum_prob) < 1e-12
                for h in key_hits)


def test_level_band_slices_match_levels():
    idx = fig3_index()
    flat = idx.flatten()
    for root, tree in idx.trees.items():
        t = np.array([flat.root_tree[root]])
        a, b = flat.level_band(t, np.array([1]), np.array([2]))
        got = sorted(int(flat.items[i]) for i in range(a[0], b[0]))
        want = sorted(n.item for n in tree.levels(1, 2))
        assert got == want
