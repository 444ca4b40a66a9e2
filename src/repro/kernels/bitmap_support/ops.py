"""Jit'd public wrappers for the bitmap support kernels.

Pad to block multiples, dispatch to the Pallas kernels (interpret mode on
CPU hosts, compiled on TPU), and unpad — one jitted program per input
shape.  ``frontier_join_support`` is the entry point the level-synchronous
miner uses when ``use_kernel=True``; ``sstep_join_support`` serves the
per-prefix DFS spill path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bitmap_support import (
    DEFAULT_BLOCK_FK,
    DEFAULT_BLOCK_FS,
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_P,
    DEFAULT_BLOCK_S,
    frontier_join_support_pallas,
    sstep_join_support_pallas,
)

__all__ = ["sstep_join_support", "frontier_join_support"]

_STATIC = ("block_p", "block_k", "block_s", "interpret")


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def _interpret(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


@functools.partial(jax.jit, static_argnames=_STATIC[1:])
def sstep_join_support(
    slots,
    cand,
    *,
    block_k: int | None = None,
    block_s: int | None = None,
    interpret: bool | None = None,
):
    """(S, W) × (K, S, W) -> joined (K, S, W), support (K,) int32."""
    slots = jnp.asarray(slots, jnp.uint32)
    cand = jnp.asarray(cand, jnp.uint32)
    k_items, n_sessions, _ = cand.shape
    if k_items == 0:
        return cand, jnp.zeros((0,), jnp.int32)
    bk = block_k or min(DEFAULT_BLOCK_K, k_items)
    bs = block_s or DEFAULT_BLOCK_S
    slots_p = _pad_to(slots, 0, bs)
    cand_p = _pad_to(_pad_to(cand, 1, bs), 0, bk)
    joined, support = sstep_join_support_pallas(
        slots_p, cand_p, block_k=bk, block_s=bs,
        interpret=_interpret(interpret),
    )
    return joined[:k_items, :n_sessions], support[:k_items]


@functools.partial(jax.jit, static_argnames=_STATIC)
def frontier_join_support(
    slots,
    cand,
    *,
    block_p: int | None = None,
    block_k: int | None = None,
    block_s: int | None = None,
    interpret: bool | None = None,
):
    """(P, S, W) × (K, S, W) -> support (P, K) int32.

    The kernel takes sessions as the minor dimension, so both operands are
    transposed to (W, P, S) / (W, K, S) here.  Zero-padding is
    support-neutral: padded prefixes/candidates/sessions contribute no set
    bits, so their counts are 0 and are sliced off."""
    slots = jnp.asarray(slots, jnp.uint32)
    cand = jnp.asarray(cand, jnp.uint32)
    p_prefixes, n_sessions, _ = slots.shape
    k_items = cand.shape[0]
    if p_prefixes == 0 or k_items == 0:
        return jnp.zeros((p_prefixes, k_items), jnp.int32)
    # a block smaller than the default is the whole (unpadded) dim
    bp = block_p or min(DEFAULT_BLOCK_P, p_prefixes)
    bk = block_k or min(DEFAULT_BLOCK_FK, k_items)
    bs = block_s or DEFAULT_BLOCK_FS
    slots_t = _pad_to(_pad_to(slots.transpose(2, 0, 1), 2, bs), 1, bp)
    cand_t = _pad_to(_pad_to(cand.transpose(2, 0, 1), 2, bs), 1, bk)
    support = frontier_join_support_pallas(
        slots_t, cand_t, block_p=bp, block_k=bk, block_s=bs,
        interpret=_interpret(interpret),
    )
    return support[:p_prefixes, :k_items]
