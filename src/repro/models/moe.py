"""Mixture-of-Experts layer: top-k routing with capacity-bounded,
index-based dispatch (GShard-style, TPU-adapted).

Instead of materializing the (tokens × experts × capacity) one-hot dispatch
tensor (infeasible at 1M tokens × 128 experts), we compute per-token expert
slots with a sort-based rank and move tokens with gather/scatter:

  1. top-k gates per token;
  2. position-in-expert via stable sort of the flat expert choices
     (rank within each expert's segment);
  3. tokens whose position exceeds the capacity are dropped (standard
     capacity-factor semantics — the residual path carries them);
  4. gather tokens into (E, C, D), run the expert SwiGLU as a batched
     einsum over the expert dim (MXU-friendly), scatter back weighted by
     the renormalized gate probabilities.

Routing happens per batch row (vmap), so position computation never crosses
the data-parallel shards — the only cross-shard movement is the expert
einsum itself, which the sharding rules place on the model/expert axis.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from .layers import dense_init

__all__ = ["moe_init", "moe_apply", "moe_capacity"]


def moe_init(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, (d, e), dtype=jnp.float32),
        "w1": dense_init(k1, (e, d, f), dtype=dtype),
        "w3": dense_init(k2, (e, d, f), dtype=dtype),
        "w2": dense_init(k3, (e, f, d), dtype=dtype),
    }


def moe_capacity(cfg, seq_len: int) -> int:
    cap = int(seq_len * cfg.experts_per_token * cfg.capacity_factor
              / cfg.n_experts)
    return max(cap, cfg.experts_per_token)


def _ep_constraint(cfg, t):
    """Pin (B, E, C, D) dispatch/combine tensors onto the expert-parallel
    axis so the SPMD partitioner moves tokens with all-to-alls instead of
    replicating and all-reducing the whole buffer (measured on
    qwen3-moe train_4k: 2.3 TB/device of all-reduce without this)."""
    if cfg.moe_shard == "ep":
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            t, P(None, "model", None, None))
    return t


def moe_apply(p, cfg, x, capacity: int | None = None):
    """x: (B, S, D) -> (B, S, D).  Batched index-based dispatch; the
    moe_shard="ep" policy switches to the explicit all-to-all path."""
    if (cfg.moe_shard in ("ep", "ep_infer") and _MESH is not None
            and cfg.n_experts % _MESH.shape.get("model", 1) == 0):
        return moe_apply_ep(p, cfg, x, fsdp_weights=cfg.moe_shard == "ep")
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    capacity = capacity or moe_capacity(cfg, s)
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                    # (B, S, E)
    topv, topi = jax.lax.top_k(gates, k)                       # (B, S, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)        # renormalize

    # position-in-expert by stable sort of flat choices (per batch row)
    ef = topi.reshape(b, s * k)                                # (B, S*k)
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s * k))
    order = jnp.argsort(ef, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(ef, order, axis=1)
    counts = jnp.zeros((b, e), jnp.int32).at[bidx, ef].add(1)
    starts = jnp.cumsum(counts, axis=1) - counts               # exclusive
    pos_sorted = (jnp.arange(s * k)[None, :]
                  - jnp.take_along_axis(starts, sorted_e, axis=1))
    pos = jnp.zeros((b, s * k), jnp.int32).at[bidx, order].set(
        pos_sorted.astype(jnp.int32))

    keep = pos < capacity
    slot = jnp.where(keep, ef * capacity + pos, e * capacity)  # drop bucket

    # dispatch: (B, E*C+1, D) buffer; last row swallows drops
    token_of_choice = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s), k)[None, :], (b, s * k))
    xin = jnp.zeros((b, e * capacity + 1, d), x.dtype).at[bidx, slot].set(
        x[bidx, token_of_choice])
    xin = _ep_constraint(cfg, xin[:, :-1].reshape(b, e, capacity, d))

    # expert SwiGLU, batched over the expert dim
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xin, p["w1"])) * jnp.einsum(
        "becd,edf->becf", xin, p["w3"])
    y = jnp.einsum("becf,efd->becd", h, p["w2"])               # (B, E, C, D)
    y = _ep_constraint(cfg, y)

    # combine: gather each kept choice's output, weight, sum over k
    y_flat = jnp.concatenate(
        [y.reshape(b, e * capacity, d), jnp.zeros((b, 1, d), y.dtype)],
        axis=1)
    w = (topv.reshape(b, s * k)[..., None].astype(y.dtype)
         * keep[..., None])
    per_choice = y_flat[bidx, slot] * w
    return jnp.sum(per_choice.reshape(b, s, k, d), axis=2)


# ---------------------------------------------------------------------------
# Expert-parallel MoE via shard_map (all-to-all dispatch)
# ---------------------------------------------------------------------------
#
# The jit-level scatter/gather dispatch above leaves the SPMD partitioner to
# move tokens, and it chooses replicate+all-reduce of the whole (B,E,C,D)
# buffer (measured 2.3 TB/device on qwen3-moe train_4k).  The token-movement
# lower bound is one all-to-all each way; this path spells it out:
#
#   per device (data i, model j): local tokens (B/|data|, S/|model|) route
#   locally -> dispatch (E, C_l, D) -> all_to_all over 'model' regroups to
#   (E/|model|, |model|·C_l, D) -> expert FFN (weights E-sharded over
#   'model', D-sharded over 'data', all-gathered on entry: FSDP) ->
#   reverse all_to_all -> local combine.  Tokens never cross the 'data'
#   axis: every data shard holds the full (gathered) weights of its model
#   shard's experts.

_MESH = None  # set by launchers around lowering (see launch/dryrun.py)


def set_mesh(mesh):
    global _MESH
    _MESH = mesh


def moe_apply_ep(p, cfg, x, fsdp_weights: bool = True):
    """x: (B, S, D) -> (B, S, D), explicit expert-parallel all-to-all.

    ``fsdp_weights=False`` (inference): weights are only expert-sharded,
    so no per-layer gather over 'data' is needed."""
    from jax.sharding import PartitionSpec as P

    mesh = _MESH
    assert mesh is not None, "moe_shard='ep' needs set_mesh(...)"
    n_model = mesh.shape["model"]
    n_data = mesh.shape.get("data", 1)
    e, k = cfg.n_experts, cfg.experts_per_token
    assert e % n_model == 0
    # decode steps have S=1: tokens shard over 'data' only
    b_all, s_all, _ = x.shape
    x_spec = P("data" if b_all % n_data == 0 else None,
               "model" if s_all % n_model == 0 else None, None)

    def local_moe(xb, router, w1, w3, w2):
        # xb: (B_l, S_l, D); w1/w3: (E_l, D_l, F); w2: (E_l, F, D_l)
        b_l, s_l, d = xb.shape
        t = b_l * s_l
        xt = xb.reshape(t, d)
        cap = max(k, int(t * k * cfg.capacity_factor / e))

        gates = jax.nn.softmax(
            xt.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1)
        topv, topi = jax.lax.top_k(gates, k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

        ef = topi.reshape(-1)                              # (T*k,)
        order = jnp.argsort(ef, stable=True)
        counts = jnp.bincount(ef, length=e)
        starts = jnp.cumsum(counts) - counts
        pos_sorted = jnp.arange(t * k) - starts[ef[order]]
        pos = jnp.zeros((t * k,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
        keep = pos < cap
        slot = jnp.where(keep, ef * cap + pos, e * cap)

        tok = jnp.repeat(jnp.arange(t), k)
        xin = jnp.zeros((e * cap + 1, d), xb.dtype).at[slot].set(xt[tok])
        xin = xin[:-1].reshape(e, cap, d)

        # ship token blocks to their expert's model-shard
        xin = jax.lax.all_to_all(
            xin, "model", split_axis=0, concat_axis=1, tiled=True
        )                                                   # (E_l, n*cap, D)

        # FSDP: gather the experts' weights over 'data' for the contraction
        if gather_weights:
            w1f = jax.lax.all_gather(w1, "data", axis=1, tiled=True)
            w3f = jax.lax.all_gather(w3, "data", axis=1, tiled=True)
            w2f = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
        else:
            w1f, w3f, w2f = w1, w3, w2
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, w1f)) * jnp.einsum(
            "ecd,edf->ecf", xin, w3f)
        y = jnp.einsum("ecf,efd->ecd", h, w2f)             # (E_l, n*cap, D)

        # ship results back to the owning token shard
        y = jax.lax.all_to_all(
            y, "model", split_axis=1, concat_axis=0, tiled=True
        )                                                   # (E, cap, D)

        y_flat = jnp.concatenate(
            [y.reshape(e * cap, d), jnp.zeros((1, d), y.dtype)], axis=0)
        out = (y_flat[slot]
               * (topv.reshape(-1)[:, None].astype(y.dtype) * keep[:, None]))
        return jnp.sum(out.reshape(t, k, d), axis=1).reshape(b_l, s_l, d)

    d_model = p["w1"].shape[1]
    w_d = "data" if (fsdp_weights and d_model % n_data == 0) else None
    gather_weights = w_d == "data"
    return jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(x_spec, P(),
                  P("model", w_d, None), P("model", w_d, None),
                  P("model", None, w_d)),
        out_specs=x_spec,
        # decode (S=1): tokens are replicated over 'model'; the round-trip
        # all_to_all provably restores that replication, which the static
        # varying-axes check cannot see
        check_vma=False,
    )(x, p["router"], p["w1"], p["w3"], p["w2"])
