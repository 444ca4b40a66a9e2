"""Public wrappers for the jitted decision walk.

``device_forest`` ships one mining generation's :class:`FlatForest` to
the device as int32 arrays (the device runs with x64 off), each padded
to the forest's rung of the node ladder (:func:`node_bucket`), and
refuses a forest whose ids would not fit; ``decision_walk`` packs the
live context state, padded to the engine's ``max_contexts``, with the
live count and the item into one int32 vector, uploads it with one
copy, runs the jitted
step, reads its one packed output back with one copy, and unpacks that
(the layout is in :mod:`.decision_walk`'s docstring) to the compact
numpy state dict the core engine consumes.  Under an active host
profile (:mod:`repro.core.obs`) a jitted call is the span ``palp.walk``,
split into upload, dispatch, wait, readback and unpack, with its copies
each way and their bytes counted.

So the walk's programs are one per (rung, ``max_contexts``,
``p_depth``), and a new generation on a rung already made starts none:
:func:`warm_decision_walk` makes every rung up to a bound ahead.  A
client's bound is its metastore's: at most ``metastore_capacity``
patterns of at most ``max_len`` items, so at most ``capacity * max_len``
nodes.
"""

from __future__ import annotations

import numpy as np
import jax

from repro.core import obs

from . import ref as _ref
from .decision_walk import WORD_BITS, decision_walk_step, top_k_frontier

__all__ = ["device_forest", "decision_walk", "top_k_frontier",
           "node_bucket", "node_ladder", "warm_decision_walk",
           "programs_made", "program_made"]

_INT32_MAX = np.iinfo(np.int32).max

#: the smallest rung of the node ladder: one packed wave word
MIN_NODES = 32

#: the walk programs (rung, contexts, p_depth) this process has called
#: or warmed
_made: set = set()


def node_bucket(n: int) -> int:
    """The padded length of a forest's arrays: the next power of two
    from ``MIN_NODES``."""
    return max(MIN_NODES, 1 << (max(n, 1) - 1).bit_length())


def node_ladder(max_nodes: int) -> list[int]:
    """Every rung a forest of up to ``max_nodes`` nodes can land on."""
    top = node_bucket(max_nodes)
    return [1 << i for i in range(MIN_NODES.bit_length() - 1,
                                  top.bit_length())]


def programs_made() -> int:
    """How many walk programs this process has made."""
    return len(_made)


def program_made(jf: "DeviceForest", max_contexts: int,
                 p_depth: int) -> bool:
    """Whether the walk over ``jf`` is a program this process has made."""
    return (jf.n_padded, 3 * max_contexts + 2, p_depth) in _made


class DeviceForest:
    """Per-generation device-resident FlatForest arrays (int32), each
    padded to ``n_padded`` rows.

    The edge table is kept in its ``(parent, item)`` sort order as two
    parallel arrays, ``edge_item`` and ``edge_child``; ``edge_first[v]``
    is where node ``v``'s slice of it starts (its ``n_children`` edges
    are contiguous because the table is sorted by parent first).
    Padded rows are unreachable: no real node's edge slice covers a
    padded edge, no context holds a padded node or tree, and a padded
    node's preorder rank lies past every real subtree's end."""

    def __init__(self, flat):
        n = flat.n_nodes
        self.n_padded = m = node_bucket(max(n, flat.n_trees + 1))
        # 2 * m bounds the search's lo + hi and every id, pre/post rank
        # and edge index; the items bound the rest
        biggest = max(2 * m, flat.item_stride)
        if biggest > _INT32_MAX:
            raise OverflowError(
                f"forest of {n} nodes over a vocabulary of "
                f"{flat.item_stride} items does not fit the device walk's "
                f"int32 ids")
        self.search_steps = m.bit_length()
        self.edge_item = _padded(flat.items[flat.edge_child], m, -1)
        self.edge_child = _padded(flat.edge_child, m)
        self.edge_first = _padded(np.cumsum(flat.n_children)
                                  - flat.n_children, m)
        self.items = _padded(flat.items, m, -1)
        self.depth = _padded(flat.depth, m)
        self.pre = _padded(flat.pre, m, m)
        self.post = _padded(flat.post, m)
        self.n_children = _padded(flat.n_children, m)
        self.tree_start = _padded(flat.tree_start, m)
        self.tree_max_depth = _padded(flat.tree_max_depth, m)
        # the upload belongs to the generation's install, not its first
        # walk
        jax.block_until_ready(self.arrays())

    def arrays(self) -> tuple:
        """The step's forest arguments, in its order."""
        return (self.edge_item, self.edge_child, self.edge_first,
                self.items, self.depth, self.pre, self.post,
                self.n_children, self.tree_start, self.tree_max_depth)


def _padded(a, m: int, fill: int = 0) -> jax.Array:
    """``a`` as int32, filled to ``m`` entries, on the device."""
    out = np.full(m, fill, np.int32)
    out[:len(a)] = a
    return jax.device_put(out)


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
            _made.add((jf.n_padded, len(ctx), p_depth))
            out = decision_walk_step(*jf.arrays(), dev_ctx,
                                     p_depth=p_depth,
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
            # the bits of padded nodes at 0
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


def warm_decision_walk(max_nodes: int, max_contexts: int, p_depth: int,
                       interpret: bool | None = None) -> int:
    """Make the walk program of every rung up to ``max_nodes`` now, by
    stepping no live context over an empty forest padded to each rung;
    returns how many rungs there are.  ``interpret=True`` walks on the
    numpy reference, as :func:`decision_walk` does, so it makes none."""
    ladder = node_ladder(max_nodes)
    if interpret:
        return len(ladder)
    ctx = jax.device_put(np.zeros(3 * max_contexts + 2, np.int32))
    for m in ladder:
        key = (m, len(ctx), p_depth)
        if key in _made:
            continue
        z = _padded(np.zeros(0, np.int32), m)
        jax.block_until_ready(decision_walk_step(
            *([z] * 10), ctx, p_depth=p_depth,
            search_steps=m.bit_length()))
        _made.add(key)
    return len(ladder)
