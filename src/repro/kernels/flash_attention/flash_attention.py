"""Pallas TPU kernel: blocked online-softmax (Flash) attention with GQA.

The serving/training hot spot of the LM stack.  TPU adaptation notes:

* Blocks are (block_q × head_dim) and (block_k × head_dim) VMEM tiles; the
  q·kᵀ and p·v contractions run on the MXU with f32 accumulation
  (``preferred_element_type``) — block sizes default to 512/512 so the MXU
  matmul dims are multiples of 128.
* Grid = (batch·q_heads, q_blocks, k_blocks); the k dimension is innermost
  and sequential ("arbitrary"), carrying the online-softmax state (running
  max m, normalizer l, accumulator acc) in VMEM scratch across iterations.
* GQA without materializing repeated KV: the k/v BlockSpec index maps divide
  the head index by the group size, so each kv head's tiles are streamed
  from HBM once per group.
* Padding is handled in-kernel: the static true lengths (q_valid, kv_valid)
  mask padded kv columns; the causal mask is end-aligned
  (row r sees cols <= r + kv_valid - q_valid).
* Causal masking is applied with block-level granularity: fully-masked
  k-blocks are skipped (no MXU work), diagonal blocks apply an iota mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_LANES = 128  # VPU lane width: scratch carries use a full lane tile

_NEG_INF = float("-inf")


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, causal: bool, sm_scale: float, block_q: int, block_k: int,
            q_valid: int, kv_valid: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    offset = kv_valid - q_valid  # end-aligned causal offset (static)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: fully padded or fully future kv blocks do no work
    k_start = ki * block_k
    run = k_start < kv_valid
    if causal:
        last_visible = (qi + 1) * block_q - 1 + offset
        run = jnp.logical_and(run, k_start <= last_visible)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)       # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                               # (bq, bk)

        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_valid
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, qpos + offset >= kpos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, 0]                       # (bq,)
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # guard fully-masked rows: exp(-inf - -inf) would be NaN
        m_safe = jnp.where(m_cur == _NEG_INF, 0.0, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_safe[:, None]), 0.0)  # (bq, bk)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # (bq, d)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "block_q", "block_k", "q_valid", "kv_valid",
        "interpret",
    ),
)
def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_valid: int | None = None,
    kv_valid: int | None = None,
    interpret: bool = False,
):
    """q: (B, Hq, Lq, D); k/v: (B, Hkv, Lk, D), block-divisible (padded by
    ops.py); q_valid/kv_valid are the true unpadded lengths."""
    b, hq, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d ** -0.5
    q_valid = q_valid or lq
    kv_valid = kv_valid or lk
    assert lq % block_q == 0 and lk % block_k == 0
    grid = (b * hq, lq // block_q, lk // block_k)

    kernel = functools.partial(
        _kernel, causal=causal, sm_scale=float(sm_scale),
        block_q=block_q, block_k=block_k,
        q_valid=q_valid, kv_valid=kv_valid,
    )

    def q_map(bh, qi, ki):
        return (bh // hq, bh % hq, qi, 0)

    def kv_map(bh, qi, ki):
        return (bh // hq, (bh % hq) // group, ki, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),       # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out
