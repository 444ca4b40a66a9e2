"""Jitted prefetch-decision walk (the accelerator twin of
:mod:`repro.core.decision`).

One XLA program advances every live prefetch context by the requested
item — a probability-matrix walk over the flattened pattern forest:

* each context's confirmed node owns the contiguous, item-sorted slice
  ``[edge_first[v], edge_first[v] + n_children[v])`` of the edge table;
  all C contexts look the item up in their slices with one batched
  fixed-depth binary search.  Every index and item stays an int32 — no
  ``parent * item_stride + item`` key, which would wrap once node ids ×
  vocabulary pass 2^31 with x64 off;
* wave selection broadcasts each emitting context's depth band and DFS
  preorder interval against the whole node table, yielding a dense
  (C, N) wave mask whose row-major nonzeros are exactly the scalar
  engine's (context order, level order) emission.  The preorder
  interval keeps the wave inside the context's subtree, so the band is
  a plain depth range;
* :func:`top_k_frontier` is the jitted top-k frontier selection used for
  ``fetch_top_n`` initial waves (stable lexicographic (cum_prob desc,
  depth asc, level-order asc) pick, re-emitted (depth asc, cum desc)).

Each call moves one array each way.  In: one int32 vector of 3·C + 2,
``[nodes | trees | fetched | n, item]`` with each context column padded
to C; the step rebuilds ``alive`` as the first ``n`` rows.  Out: one
int32 (C, 5 + W) array, W = ceil(N / 32): columns 0-4 are ``new_nodes``,
``new_fetched``, ``new_alive``, ``found`` and ``stay``; columns 5.. hold
the wave mask bit-packed along N, little-endian — node ``32·w + b`` is
bit ``b`` of word ``w`` (each uint32 word bitcast to int32), and the
bits past N in the last word are 0.

The host may run a call in two halves (:func:`.ops.start_walk`, then
:func:`.ops.finish_walk`): dispatch the step and ask for the output's
copy back at once, then collect the output later, after other host
work.  An engine consumes such a begun walk only in its next request,
for the item it was begun for, on the generation it was begun on
(:meth:`repro.core.decision.VectorizedPrefetchEngine.begin_walk`).  The
step is the same program either way.

Every node, edge and tree array is padded to one length N, a rung of
a power-of-two ladder (:func:`.ops.node_bucket`), and C is the engine's
``max_contexts``; the static arguments are ``p_depth`` and a search
depth that follows N.  So a mining generation whose forest lands on a
rung already made reuses its program.  The numpy
reference in :mod:`.ref` delegates to the core engine's pure step
functions; ``tests/test_decision_kernel.py`` pins jit-vs-reference
parity.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["decision_walk_step", "top_k_frontier"]

#: wave-mask bits per packed word
WORD_BITS = 32


@partial(jax.jit, static_argnames=("p_depth", "search_steps"))
def decision_walk_step(edge_item, edge_child, edge_first, items, depth,
                       pre, post, n_children, tree_start, tree_max_depth,
                       ctx, *, p_depth: int, search_steps: int):
    """Advance C (padded) contexts by ``item``; returns the packed
    (C, 5 + W) int32 output laid out in the module docstring.

    ``ctx`` is the packed (3·C + 2,) input.  Its ``item`` is -1 when the
    item lies outside the forest's vocabulary (it then matches no edge
    and no root).  ``search_steps`` must be at least the bit length of
    the largest ``n_children``.  Rows at and past ``n`` are dead: they
    never match, emit, or resurrect — zero-padding is decision-neutral,
    mirroring the support-neutral padding contract of
    ``frontier_join_support``.  Padded nodes have a preorder rank past
    every real subtree's end, so no wave reaches them."""
    c = (ctx.shape[0] - 2) // 3
    nodes, trees, fetched = ctx[:c], ctx[c:2 * c], ctx[2 * c:3 * c]
    alive = jnp.arange(c) < ctx[3 * c]
    item = ctx[3 * c + 1]
    last = edge_item.shape[0] - 1
    first = edge_first[nodes]
    end = first + n_children[nodes]
    lo, hi = first, end
    for _ in range(search_steps):       # lower bound of item in [lo, hi)
        active = lo < hi
        mid = (lo + hi) // 2
        less = edge_item[jnp.minimum(mid, last)] < item
        lo = jnp.where(active & less, mid + 1, lo)
        hi = jnp.where(active & ~less, mid, hi)
    pos = jnp.minimum(lo, last)
    found = alive & (lo < end) & (edge_item[pos] == item)
    child = edge_child[pos]
    roots = tree_start[trees]
    stay = alive & ~found & (nodes == roots) & (items[nodes] == item)
    new_nodes = jnp.where(found, child, nodes)
    cdepth = depth[new_nodes]
    target = cdepth + p_depth
    emit = found & (target > fetched)
    dies_after = found & ((cdepth >= tree_max_depth[trees])
                          | (n_children[new_nodes] == 0))
    new_alive = (found & ~dies_after) | stay
    new_fetched = jnp.where(emit, target, fetched)
    band = ((depth[None, :] > fetched[:, None])
            & (depth[None, :] <= target[:, None]))
    sub = ((pre[None, :] >= pre[new_nodes][:, None])
           & (pre[None, :] < post[new_nodes][:, None]))
    wave_mask = band & sub & emit[:, None]
    n = wave_mask.shape[1]
    words = -(-n // WORD_BITS)
    bits = jnp.pad(wave_mask, ((0, 0), (0, words * WORD_BITS - n)))
    bits = bits.reshape(c, words, WORD_BITS).astype(jnp.uint32)
    # distinct powers of two: the sum is their bitwise or
    packed = (bits << jnp.arange(WORD_BITS, dtype=jnp.uint32)).sum(
        axis=-1, dtype=jnp.uint32)
    cols = jnp.stack([new_nodes, new_fetched, new_alive, found, stay],
                     axis=1).astype(jnp.int32)
    return jnp.concatenate(
        [cols, jax.lax.bitcast_convert_type(packed, jnp.int32)], axis=1)


@partial(jax.jit, static_argnames=("k",))
def top_k_frontier(cum_prob, depth, *, k: int):
    """Top-k frontier of one tree's non-root slice: select by (cum_prob
    desc, depth asc, level-order asc), emit by (depth asc, cum_prob
    desc, selection order) — both stable, the oracle's ``heapq.nlargest``
    + stable-sort contract."""
    ids = jnp.arange(cum_prob.shape[0])
    order = jnp.lexsort((ids, depth, -cum_prob))
    sel = order[:k]
    fin = jnp.lexsort((jnp.arange(sel.shape[0]), -cum_prob[sel],
                       depth[sel]))
    return sel[fin]
