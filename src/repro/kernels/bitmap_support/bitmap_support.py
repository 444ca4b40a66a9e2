"""Pallas TPU kernels: VMSP s-step join + per-session support count.

The mining hot loop (paper §3.2: candidate support counting dominates
sequential-pattern-mining runtime) is a bitwise AND of a prefix's extension
slots against every candidate item's occurrence bitmap, followed by an
"any bit set per session" reduction.  The work is bitwise, so it runs on
the VPU; the support accumulator is carried across the sequential session
grid dimension in the revisited output block, the standard Pallas
reduction pattern.

Two kernels:

* ``sstep_join_support_pallas`` — per-prefix (1×K) join over the
  (K candidates, S sessions, W packed words) layout, returning joined
  bitmaps + support (the DFS spill walker's primitive).  Blocks are
  (8 candidates × 512 sessions × W words); W is the whole minor dim.
* ``frontier_join_support_pallas`` — the level-synchronous miner's fused
  (P×K) support join over a whole frontier of prefixes.  Sessions are the
  minor (lane) dimension: inputs are laid out (W, P, S) and (W, K, S), so
  a (bP, bS) / (bK, bS) block fills whole (8, 128) vregs however few words
  W a session needs, and the (bP, bK) support tile has K on lanes.  The
  (bP, bK, bS) int32 hit temporary is 8·128·512·4 B = 2 MiB, well inside
  the 16 MiB scoped-VMEM limit for any W (the words are OR-ed one at a
  time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["sstep_join_support_pallas", "frontier_join_support_pallas"]

DEFAULT_BLOCK_K = 8
DEFAULT_BLOCK_S = 512

# frontier kernel tiles: bS is the input blocks' lane dim and bK the
# output block's, so both are multiples of 128 (or the whole dim)
DEFAULT_BLOCK_P = 8
DEFAULT_BLOCK_FK = 128
DEFAULT_BLOCK_FS = 512


def _kernel(slots_ref, cand_ref, joined_ref, support_ref):
    s_idx = pl.program_id(1)
    slots = slots_ref[...]                      # (bS, W) uint32
    cand = cand_ref[...]                        # (bK, bS, W) uint32
    joined = jnp.bitwise_and(slots[None, :, :], cand)
    joined_ref[...] = joined
    any_bit = jnp.any(joined != 0, axis=-1)     # (bK, bS)
    counts = jnp.sum(any_bit.astype(jnp.int32), axis=-1, keepdims=True)  # (bK,1)

    @pl.when(s_idx == 0)
    def _init():
        support_ref[...] = counts

    @pl.when(s_idx != 0)
    def _acc():
        support_ref[...] += counts


@functools.partial(
    jax.jit, static_argnames=("block_k", "block_s", "interpret")
)
def sstep_join_support_pallas(
    slots: jnp.ndarray,
    cand: jnp.ndarray,
    *,
    block_k: int = DEFAULT_BLOCK_K,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
):
    """See :func:`repro.kernels.bitmap_support.ref.sstep_join_support`.

    Inputs must be pre-padded: K % block_k == 0 and S % block_s == 0
    (the ops.py wrapper pads and unpads).
    """
    k_items, n_sessions, n_words = cand.shape
    assert slots.shape == (n_sessions, n_words)
    assert k_items % block_k == 0 and n_sessions % block_s == 0
    grid = (k_items // block_k, n_sessions // block_s)

    joined, support = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, n_words), lambda k, s: (s, 0)),
            pl.BlockSpec((block_k, block_s, n_words), lambda k, s: (k, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_k, block_s, n_words), lambda k, s: (k, s, 0)),
            # revisited across the s grid dim -> accumulates
            pl.BlockSpec((block_k, 1), lambda k, s: (k, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_items, n_sessions, n_words), jnp.uint32),
            jax.ShapeDtypeStruct((k_items, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(slots, cand)
    return joined, support[:, 0]


def _frontier_kernel(slots_ref, cand_ref, support_ref):
    s_idx = pl.program_id(2)
    hit = None
    for w in range(slots_ref.shape[0]):         # W is tiny: unrolled
        slots = slots_ref[w]                    # (bP, bS) uint32
        cand = cand_ref[w]                      # (bK, bS) uint32
        word = (slots[:, None, :] & cand[None, :, :]) != 0   # (bP, bK, bS)
        hit = word if hit is None else hit | word
    counts = jnp.sum(hit.astype(jnp.int32), axis=-1)         # (bP, bK)

    @pl.when(s_idx == 0)
    def _init():
        support_ref[...] = counts

    @pl.when(s_idx != 0)
    def _acc():
        support_ref[...] += counts


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_k", "block_s", "interpret")
)
def frontier_join_support_pallas(
    slots: jnp.ndarray,
    cand: jnp.ndarray,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    block_k: int = DEFAULT_BLOCK_FK,
    block_s: int = DEFAULT_BLOCK_FS,
    interpret: bool = False,
):
    """Frontier-batched support join: (W,P,S) × (W,K,S) -> (P,K) int32.

    The level-synchronous miner's fused join — one launch counts support for
    every (prefix, candidate-item) pair of a whole lattice level.  The grid
    tiles (P, K) in parallel and runs the session dimension sequentially,
    accumulating into the revisited (bP, bK) output block.  Joined bitmaps
    are deliberately not written back: the miner materializes them only for
    the surviving pairs.

    Inputs are session-minor and must be pre-padded: P % block_p ==
    K % block_k == S % block_s == 0 (the ops.py wrapper transposes and
    pads; padding rows/sessions contribute zero support).
    """
    n_words, p_prefixes, n_sessions = slots.shape
    k_items = cand.shape[1]
    assert cand.shape == (n_words, k_items, n_sessions)
    assert (p_prefixes % block_p == 0 and k_items % block_k == 0
            and n_sessions % block_s == 0)
    grid = (p_prefixes // block_p, k_items // block_k, n_sessions // block_s)

    support = pl.pallas_call(
        _frontier_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_words, block_p, block_s), lambda p, k, s: (0, p, s)),
            pl.BlockSpec((n_words, block_k, block_s), lambda p, k, s: (0, k, s)),
        ],
        # revisited across the s grid dim -> accumulates
        out_specs=pl.BlockSpec((block_p, block_k), lambda p, k, s: (p, k)),
        out_shape=jax.ShapeDtypeStruct((p_prefixes, k_items), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(slots, cand)
    return support
