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

A walk can also run in two halves, so that the host does other work,
another walk's round trip included, while the device steps and the
output comes back.  :func:`start_walk` uploads, dispatches and asks for
the output's copy to the host, then returns a :class:`PendingWalk`;
:func:`finish_walk` waits for it, reads it back and unpacks it, and
gives what ``decision_walk`` gives for the same arguments.  Both halves
call the same step on the same rungs, so they start no program of
their own.  A begun walk books one ``palp.walk`` call, upload and
dispatch at the start, wait, read-back and unpack at the finish, the
same two copies, and one ``palp.walk.overlapped``.
:meth:`repro.core.decision.VectorizedPrefetchEngine.begin_walk` says
when an engine consumes a begun walk.
"""

from __future__ import annotations

import numpy as np
import jax

from repro.core import obs

from . import ref as _ref
from .decision_walk import WORD_BITS, decision_walk_step, top_k_frontier

__all__ = ["device_forest", "decision_walk", "start_walk", "finish_walk",
           "PendingWalk", "top_k_frontier",
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


class PendingWalk:
    """A walk :func:`start_walk` began: its packed output on its way back
    to the host, or the state of a walk that took no device."""

    __slots__ = ("out", "n", "h2d_bytes", "state")

    def __init__(self, out=None, n: int = 0, h2d_bytes: int = 0,
                 state: dict | None = None):
        self.out = out
        self.n = n
        self.h2d_bytes = h2d_bytes
        self.state = state


def _on_host(flat, nodes, trees, fetched, item: int, p_depth: int,
             interpret: bool | None) -> dict | None:
    """The state of a walk that takes no device (the reference when
    ``interpret``, or a forest with no node), else None."""
    if interpret:
        return _ref.decision_walk_ref(flat, nodes, trees, fetched,
                                      item, p_depth)
    if flat.n_nodes:
        return None
    # zero-node forest: nothing to gather against — every context is
    # dead by construction (none could have been opened)
    n = len(nodes)
    z = np.zeros(n, np.int64)
    f = np.zeros(n, bool)
    return {"found": f, "stay": f.copy(), "nodes": z, "alive": f.copy(),
            "fetched": z.copy(), "wave_nodes": np.empty(0, np.int64)}


def _start(jf: DeviceForest, flat, nodes, trees, fetched, item: int,
           p_depth: int, max_contexts: int | None, ahead: bool,
           prof) -> PendingWalk:
    """Upload the packed context with one copy and dispatch the step;
    ``ahead`` also asks for the output's copy to the host, which then
    starts when the step ends."""
    n = len(nodes)
    with prof.span(obs.SPAN_HOST_WALK_UPLOAD):
        ctx = _pad_and_pack(max_contexts or max(n, 1), nodes, trees,
                            fetched,
                            item if 0 <= item < flat.item_stride else -1)
        dev_ctx = jax.device_put(ctx)
    with prof.span(obs.SPAN_HOST_WALK_DISPATCH):
        _made.add((jf.n_padded, len(ctx), p_depth))
        out = decision_walk_step(*jf.arrays(), dev_ctx, p_depth=p_depth,
                                 search_steps=jf.search_steps)
        if ahead:
            out.copy_to_host_async()
    return PendingWalk(out, n, ctx.nbytes)


def _finish(walk: PendingWalk, prof) -> dict:
    """Read the packed output back with one copy and unpack it."""
    out = walk.out
    if prof.active:
        # unprofiled, the read-back below waits instead
        with prof.span(obs.SPAN_HOST_WALK_WAIT):
            jax.block_until_ready(out)
    with prof.span(obs.SPAN_HOST_WALK_READBACK):
        host_out = np.asarray(out)
    with prof.span(obs.SPAN_HOST_WALK_UNPACK):
        live = host_out[:walk.n]
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
        return {
            "found": live[:, 3].astype(bool),
            "stay": live[:, 4].astype(bool),
            "nodes": live[:, 0].astype(i64),
            "alive": live[:, 2].astype(bool),
            "fetched": live[:, 1].astype(i64),
            "wave_nodes": wave_nodes.astype(i64),
        }


def _count_copies(walk: PendingWalk, prof) -> None:
    if prof.active:
        prof.count(obs.METRIC_WALK_H2D_COPIES, 1)
        prof.count(obs.METRIC_WALK_H2D_BYTES, walk.h2d_bytes)
        prof.count(obs.METRIC_WALK_D2H_COPIES, 1)
        prof.count(obs.METRIC_WALK_D2H_BYTES, walk.out.nbytes)


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
    state = _on_host(flat, nodes, trees, fetched, item, p_depth, interpret)
    if state is not None:
        return state
    prof = obs.host_profile
    with prof.span(obs.SPAN_HOST_WALK):
        walk = _start(jf, flat, nodes, trees, fetched, item, p_depth,
                      max_contexts, False, prof)
        state = _finish(walk, prof)
    _count_copies(walk, prof)
    return state


def start_walk(jf: DeviceForest, flat, nodes, trees, fetched, item: int,
               p_depth: int, max_contexts: int | None = None,
               interpret: bool | None = None) -> PendingWalk:
    """The first half of :func:`decision_walk`: upload, dispatch and ask
    for the output's copy to the host, then return without waiting for
    either.  :func:`finish_walk` gives the state ``decision_walk`` would
    have given for the same arguments.  The context arrays are copied
    here, so the caller may change them before the finish."""
    state = _on_host(flat, nodes, trees, fetched, item, p_depth, interpret)
    if state is not None:
        return PendingWalk(state=state)
    prof = obs.host_profile
    with prof.span(obs.SPAN_HOST_WALK):
        walk = _start(jf, flat, nodes, trees, fetched, item, p_depth,
                      max_contexts, True, prof)
    prof.count(obs.METRIC_WALK_OVERLAPPED, 1)
    return walk


def finish_walk(walk: PendingWalk) -> dict:
    """The second half: wait for a begun walk's output, read it back and
    unpack it.  Its seconds go on the ``palp.walk`` call that
    :func:`start_walk` booked."""
    if walk.state is not None:
        return walk.state
    prof = obs.host_profile
    with prof.span(obs.SPAN_HOST_WALK, call=False):
        state = _finish(walk, prof)
    _count_copies(walk, prof)
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
