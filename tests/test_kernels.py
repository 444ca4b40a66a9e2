"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bitmap_support import ops as bm_ops
from repro.kernels.bitmap_support import ref as bm_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref


# ---------------------------------------------------------------------------
# bitmap_support
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k_items,n_sessions,n_words", [
    (1, 7, 1),
    (5, 100, 3),
    (8, 512, 1),     # exact block
    (9, 513, 2),     # off-by-one padding both dims
    (32, 1000, 4),
    (3, 1, 1),
])
def test_bitmap_support_matches_ref(k_items, n_sessions, n_words):
    rng = np.random.default_rng(k_items * 1000 + n_sessions)
    slots = rng.integers(0, 2 ** 32, size=(n_sessions, n_words), dtype=np.uint32)
    cand = rng.integers(
        0, 2 ** 32, size=(k_items, n_sessions, n_words), dtype=np.uint32
    )
    j1, s1 = bm_ops.sstep_join_support(slots, cand)
    j2, s2 = bm_ref.sstep_join_support(jnp.asarray(slots), jnp.asarray(cand))
    np.testing.assert_array_equal(np.asarray(j1), np.asarray(j2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_bitmap_support_sparse_and_empty():
    slots = np.zeros((64, 2), np.uint32)
    cand = np.zeros((4, 64, 2), np.uint32)
    cand[1, 3, 0] = 1  # cand bit that slots don't have -> no support
    j, s = bm_ops.sstep_join_support(slots, cand)
    assert np.asarray(s).tolist() == [0, 0, 0, 0]
    assert not np.asarray(j).any()
    # zero candidates edge case
    j, s = bm_ops.sstep_join_support(slots, np.zeros((0, 64, 2), np.uint32))
    assert np.asarray(s).shape == (0,)


@pytest.mark.parametrize("p_prefixes,k_items,n_sessions,n_words", [
    (1, 1, 7, 1),
    (5, 9, 100, 2),
    (8, 8, 128, 1),      # whole-dim blocks (P, K below the defaults)
    (9, 17, 130, 3),     # off-by-one padding in all three dims
    (16, 32, 512, 1),
    (3, 2, 1, 1),
    (8, 128, 512, 1),    # exact default blocks
    (9, 130, 700, 2),    # K past one 128-lane block, not a multiple
    (3, 257, 1030, 4),   # K and S padded, every word of a 4-word session
    (2, 600, 64, 1),     # K >= 512, as in a SEQB level-1 join
])
def test_frontier_join_support_matches_ref(p_prefixes, k_items, n_sessions,
                                           n_words):
    rng = np.random.default_rng(p_prefixes * 1000 + k_items + n_sessions)
    slots = rng.integers(
        0, 2 ** 32, size=(p_prefixes, n_sessions, n_words), dtype=np.uint32)
    cand = rng.integers(
        0, 2 ** 32, size=(k_items, n_sessions, n_words), dtype=np.uint32)
    got = np.asarray(bm_ops.frontier_join_support(slots, cand))
    want = bm_ref.frontier_join_support(slots, cand)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def test_frontier_join_support_empty_edges():
    zero = np.zeros((0, 16, 1), np.uint32)
    some = np.zeros((4, 16, 1), np.uint32)
    assert np.asarray(bm_ops.frontier_join_support(zero, some)).shape == (0, 4)
    assert np.asarray(bm_ops.frontier_join_support(some, zero)).shape == (4, 0)
    # padded sessions/prefixes/candidates contribute zero support
    slots = np.zeros((2, 5, 1), np.uint32)
    cand = np.zeros((3, 5, 1), np.uint32)
    slots[1, 4, 0] = cand[2, 4, 0] = 1
    sup = np.asarray(bm_ops.frontier_join_support(slots, cand))
    want = np.zeros((2, 3), np.int32)
    want[1, 2] = 1
    np.testing.assert_array_equal(sup, want)


def _planted_db():
    from repro.core import SequenceDatabase

    rng = np.random.default_rng(5)
    sessions = []
    for _ in range(64):
        s = list(rng.integers(0, 8, size=rng.integers(3, 9)))
        if rng.random() < 0.5:
            s[:4] = [1, 2, 3, 4]  # planted frequent sequence
        sessions.append(s)
    return SequenceDatabase.from_sessions(sessions)


def test_bitmap_kernel_agrees_with_mining_numpy_path():
    """The mining engine gives identical results with and without the
    frontier kernel."""
    from repro.core import ALGORITHMS, MiningParams
    import dataclasses

    db = _planted_db()
    params = MiningParams(minsup=0.1, min_len=3, max_len=6, maxgap=1)
    plain = {(p.items, p.support) for p in ALGORITHMS["vmsp"](db, params)}
    kern = {(p.items, p.support) for p in ALGORITHMS["vmsp"](
        db, dataclasses.replace(params, use_kernel=True))}
    assert plain == kern and plain


def test_bitmap_kernel_spill_path_agrees():
    """frontier_budget=1 forces the DFS spill, which drives the per-prefix
    sstep kernel instead of the fused frontier kernel — same patterns."""
    from repro.core import ALGORITHMS, MiningParams
    import dataclasses

    db = _planted_db()
    params = MiningParams(minsup=0.1, min_len=3, max_len=6, maxgap=1)
    plain = {(p.items, p.support) for p in ALGORITHMS["vmsp"](db, params)}
    spill = {(p.items, p.support) for p in ALGORITHMS["vmsp"](
        db, dataclasses.replace(params, use_kernel=True, frontier_budget=1))}
    assert plain == spill and plain


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


def _mk_qkv(rng, b, hq, hkv, lq, lk, d, dtype):
    q = rng.standard_normal((b, hq, lq, d)).astype(dtype)
    k = rng.standard_normal((b, hkv, lk, d)).astype(dtype)
    v = rng.standard_normal((b, hkv, lk, d)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("b,hq,hkv,l,d", [
    (1, 2, 2, 128, 64),     # MHA, exact blocks
    (2, 4, 2, 128, 64),     # GQA group 2
    (1, 8, 1, 256, 32),     # MQA
    (1, 2, 2, 96, 64),      # non-divisible seq (padding path)
    (1, 4, 4, 130, 128),    # prime-ish length
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref(b, hq, hkv, l, d, causal):
    rng = np.random.default_rng(0)
    q, k, v = _mk_qkv(rng, b, hq, hkv, l, l, d, np.float32)
    got = fa_ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = fa_ref.gqa_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_alignment_lq_lt_lk():
    """Decode-style: few q rows attending a long end-aligned KV prefix."""
    rng = np.random.default_rng(1)
    q, k, v = _mk_qkv(rng, 1, 2, 2, 8, 192, 64, np.float32)
    got = fa_ops.flash_attention(q, k, v, causal=True, block_q=8, block_k=64)
    want = fa_ref.gqa_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5), ("bfloat16", 2e-2)])
def test_flash_dtypes(dtype, tol):
    rng = np.random.default_rng(2)
    dt = jnp.bfloat16 if dtype == "bfloat16" else np.float32
    q, k, v = _mk_qkv(rng, 1, 2, 1, 128, 128, 64, np.float32)
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    got = fa_ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = fa_ref.gqa_attention(q, k, v, causal=True)
    assert got.dtype == dt
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_block_shape_independence():
    """Different BlockSpec tilings must give identical math."""
    rng = np.random.default_rng(3)
    q, k, v = _mk_qkv(rng, 1, 2, 2, 256, 256, 64, np.float32)
    a = fa_ops.flash_attention(q, k, v, block_q=32, block_k=128)
    b = fa_ops.flash_attention(q, k, v, block_q=128, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_flash_causality_property():
    """Changing future kv must not change past outputs."""
    rng = np.random.default_rng(4)
    q, k, v = _mk_qkv(rng, 1, 2, 2, 128, 128, 64, np.float32)
    out1 = fa_ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    k2 = k.at[:, :, 100:, :].set(99.0)
    v2 = v.at[:, :, 100:, :].set(-99.0)
    out2 = fa_ops.flash_attention(q, k2, v2, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(
        np.asarray(out1[:, :, :100]), np.asarray(out2[:, :, :100]),
        rtol=1e-6, atol=1e-6,
    )
