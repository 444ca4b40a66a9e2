"""Public wrappers for the jitted decision walk.

``device_forest`` ships one mining generation's :class:`FlatForest` to
the device as int32 arrays (the device runs with x64 off) and refuses a
forest whose ids would not fit; ``decision_walk`` packs the live context
state, padded to the engine's ``max_contexts`` — keeping every shape
static per generation, one compile each — with the live count and the
item into one int32 vector, uploads it with one copy, runs the jitted
step, reads its one packed output back with one copy, and unpacks that
(the layout is in :mod:`.decision_walk`'s docstring) to the compact
numpy state dict the core engine consumes.  Under an active host
profile (:mod:`repro.core.obs`) a jitted call is the span ``palp.walk``,
split into upload, dispatch, wait, readback and unpack, with its copies
each way and their bytes counted.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import obs

from . import ref as _ref
from .decision_walk import WORD_BITS, decision_walk_step, top_k_frontier

__all__ = ["device_forest", "decision_walk", "top_k_frontier"]

_INT32_MAX = np.iinfo(np.int32).max


class DeviceForest:
    """Per-generation device-resident FlatForest arrays (int32).

    The edge table is kept in its ``(parent, item)`` sort order as two
    parallel arrays, ``edge_item`` and ``edge_child``; ``edge_first[v]``
    is where node ``v``'s slice of it starts (its ``n_children`` edges
    are contiguous because the table is sorted by parent first)."""

    def __init__(self, flat):
        n = flat.n_nodes
        # 2 * n bounds the search's lo + hi and every id, pre/post rank
        # and edge index; level_key and the items bound the rest
        biggest = max(2 * n, flat.item_stride,
                      int(flat.level_key.max()) if n else 0)
        if biggest > _INT32_MAX:
            raise OverflowError(
                f"forest of {n} nodes over a vocabulary of "
                f"{flat.item_stride} items does not fit the device walk's "
                f"int32 ids")
        # an empty edge table gets one unreachable entry (no node owns
        # it: every n_children is 0) so the gathers stay shape-safe
        edge_child = flat.edge_child if flat.edge_child.size else np.zeros(1)
        edge_item = (flat.items[flat.edge_child] if flat.edge_child.size
                     else np.full(1, -1))
        self.edge_item = _i32(edge_item)
        self.edge_child = _i32(edge_child)
        self.edge_first = _i32(np.cumsum(flat.n_children) - flat.n_children)
        self.search_steps = max(1, int(flat.n_children.max(initial=0))
                                .bit_length())
        self.items = _i32(flat.items)
        self.depth = _i32(flat.depth)
        self.pre = _i32(flat.pre)
        self.post = _i32(flat.post)
        self.n_children = _i32(flat.n_children)
        self.tree_start = _i32(flat.tree_start)
        self.tree_max_depth = _i32(flat.tree_max_depth)
        self.level_key = _i32(flat.level_key)


def _i32(a) -> jnp.ndarray:
    return jnp.asarray(np.asarray(a).astype(np.int32))


def device_forest(flat) -> DeviceForest:
    return DeviceForest(flat)


def _pad_and_pack(c: int, nodes, trees, fetched, item: int) -> np.ndarray:
    """The walk's one upload: ``nodes | trees | fetched``, each padded
    with zeros to ``c`` rows, then the live count and ``item``."""
    n = len(nodes)
    ctx = np.zeros(3 * c + 2, np.int32)
    for k, a in enumerate((nodes, trees, fetched)):
        ctx[k * c:k * c + n] = a
    ctx[3 * c:] = n, item
    return ctx


def decision_walk(jf: DeviceForest, flat, nodes, trees, fetched,
                  item: int, p_depth: int,
                  max_contexts: int | None = None,
                  interpret: bool | None = None) -> dict:
    """Advance the ``n`` live contexts by ``item`` on the jitted path.

    Returns the same state dict as :func:`repro.core.decision.
    advance_step`, plus the already-selected ``wave_nodes`` (row-major
    nonzeros of the wave mask = the scalar engine's context-major,
    level-ordered emission).

    ``interpret=True`` is the escape hatch: it routes through the pure
    numpy reference (:func:`ref.decision_walk_ref`) — no jit, no device
    — for debugging and for environments where tracing itself is the
    suspect.  The default (``None``/``False``) keeps the jitted path,
    which runs on any backend (CPU-jit included)."""
    if interpret:
        return _ref.decision_walk_ref(flat, nodes, trees, fetched,
                                      item, p_depth)
    n = len(nodes)
    if flat.n_nodes == 0:
        # zero-node forest: nothing to gather against — every context is
        # dead by construction (none could have been opened)
        z = np.zeros(n, np.int64)
        f = np.zeros(n, bool)
        return {"found": f, "stay": f.copy(), "nodes": z,
                "alive": f.copy(), "fetched": z.copy(),
                "wave_nodes": np.empty(0, np.int64)}
    prof = obs.host_profile
    with prof.span(obs.SPAN_HOST_WALK):
        with prof.span(obs.SPAN_HOST_WALK_UPLOAD):
            ctx = _pad_and_pack(max_contexts or max(n, 1), nodes, trees,
                                fetched,
                                item if 0 <= item < flat.item_stride else -1)
            dev_ctx = jax.device_put(ctx)
        with prof.span(obs.SPAN_HOST_WALK_DISPATCH):
            out = decision_walk_step(
                jf.edge_item, jf.edge_child, jf.edge_first, jf.items,
                jf.depth, jf.pre, jf.post, jf.n_children, jf.tree_start,
                jf.tree_max_depth, jf.level_key, dev_ctx,
                p_depth=p_depth, depth_stride=flat.depth_stride,
                search_steps=jf.search_steps)
        if prof.active:
            # unprofiled, the read-back below waits instead
            with prof.span(obs.SPAN_HOST_WALK_WAIT):
                jax.block_until_ready(out)
        with prof.span(obs.SPAN_HOST_WALK_READBACK):
            host_out = np.asarray(out)
        with prof.span(obs.SPAN_HOST_WALK_UNPACK):
            live = host_out[:n]
            words = live[:, 5:]
            # expand only the words with bits set: (context, word, bit)
            # order is the wave's row-major order, and the device leaves
            # the bits past N at 0
            _, w = np.nonzero(words)
            le_bytes = words[words != 0].astype("<i4").view(np.uint8)
            i, b = np.nonzero(np.unpackbits(le_bytes.reshape(-1, 4), axis=1,
                                            bitorder="little"))
            wave_nodes = w[i] * WORD_BITS + b
            i64 = np.int64
            state = {
                "found": live[:, 3].astype(bool),
                "stay": live[:, 4].astype(bool),
                "nodes": live[:, 0].astype(i64),
                "alive": live[:, 2].astype(bool),
                "fetched": live[:, 1].astype(i64),
                "wave_nodes": wave_nodes.astype(i64),
            }
    if prof.active:
        prof.count(obs.METRIC_WALK_H2D_COPIES, 1)
        prof.count(obs.METRIC_WALK_H2D_BYTES, ctx.nbytes)
        prof.count(obs.METRIC_WALK_D2H_COPIES, 1)
        prof.count(obs.METRIC_WALK_D2H_BYTES, host_out.nbytes)
    return state
