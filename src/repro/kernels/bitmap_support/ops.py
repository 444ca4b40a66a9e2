"""Public wrappers for the bitmap support kernels.

``frontier_join_support`` is the entry point the level-synchronous miner
uses when ``use_kernel=True``; ``sstep_join_support`` serves the
per-prefix DFS spill path (jitted per input shape, padded to blocks).

The frontier join makes a finite set of programs.  It pads on the host,
before any jitted call, onto a fixed ladder, and calls the one jitted
program of each padded shape:

* prefixes P and candidates K: a side of up to ``ROW_TILE`` rows pads
  to the next rung of ``ROW_LADDER``; a longer side is cut into tiles of
  ``ROW_TILE`` rows (the last one padded to its rung), and each (prefix
  tile, candidate tile) pair is one call.  The support of a pair is the
  (P, K) block it covers, so the tiles' answers are put side by side;
* sessions S: to a multiple of the kernel's session block, and to at
  least ``min_sessions`` (an online client passes its tail, so every
  round over up to that many sessions shares one S);
* words W: to the next power of two.

Zero padding is support-neutral: padded prefixes, candidates, sessions
and words hold no set bits, so their counts are 0 and are sliced off.
So for one (S, W) the programs are the ``len(ROW_LADDER) ** 2`` pairs
of rungs, whatever P and K are, and :func:`warm_frontier_join` makes
all of them ahead.

How far a configuration reaches along the tiles: with ``maxgap=1``
every frequent pattern of length d occurs in at least ``floor_count``
sessions (the dynamic-minsup floor's count), and a session of length L
holds at most L contiguous subsequences of one length, so a level holds
at most ``S * L / floor_count`` prefixes or candidate items.  For
SEQB's 2,000-session tail (sessions of up to 10 accesses, a floor of
0.002, so a count of 4) that is 5,000: at most ten tiles a side.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs

from .bitmap_support import (
    DEFAULT_BLOCK_FK,
    DEFAULT_BLOCK_FS,
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_P,
    DEFAULT_BLOCK_S,
    frontier_join_support_pallas,
    sstep_join_support_pallas,
)

__all__ = ["sstep_join_support", "frontier_join_support", "ROW_LADDER",
           "ROW_TILE", "frontier_shape", "frontier_calls",
           "frontier_programs", "warm_frontier_join", "programs_made"]

_STATIC = ("block_p", "block_k", "block_s", "interpret")

#: the rungs a side of the frontier join pads to (multiples of the
#: kernel's 8-row prefix block); a side past the last rung is tiled
ROW_LADDER = (8, 32, 128, 512)
ROW_TILE = ROW_LADDER[-1]

#: the padded frontier-join shapes (P, K, S, W, interpret) this process
#: has called or warmed: the programs it has made
_made: set = set()


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


def _rung(rows: int) -> int:
    return next(r for r in ROW_LADDER if r >= rows)


def _tiles(rows: int) -> list[tuple[int, int]]:
    """(first row, padded rows) of each tile covering ``rows`` rows."""
    full, rest = divmod(rows, ROW_TILE)
    out = [(i * ROW_TILE, ROW_TILE) for i in range(full)]
    if rest:
        out.append((full * ROW_TILE, _rung(rest)))
    return out


def frontier_shape(n_sessions: int, n_words: int,
                   min_sessions: int = 0) -> tuple[int, int]:
    """The padded (S, W) of a join over ``n_sessions`` sessions of
    ``n_words`` words."""
    s = max(n_sessions, min_sessions, 1)
    return (-(-s // DEFAULT_BLOCK_FS) * DEFAULT_BLOCK_FS,
            1 << (n_words - 1).bit_length())


def frontier_calls(p_prefixes: int, k_items: int, n_sessions: int,
                   n_words: int, min_sessions: int = 0) -> list[tuple]:
    """The distinct padded shapes (P, K, S, W) a join of these logical
    shapes calls :func:`frontier_program` with."""
    s, w = frontier_shape(n_sessions, n_words, min_sessions)
    return sorted({(p, k, s, w) for _, p in _tiles(p_prefixes)
                   for _, k in _tiles(k_items)})


def frontier_programs(n_sessions: int, n_words: int, min_sessions: int = 0,
                      interpret: bool | None = None) -> list[tuple]:
    """Every padded shape (P, K, S, W, interpret) a join of this (S, W)
    can call, whatever its P and K."""
    s, w = frontier_shape(n_sessions, n_words, min_sessions)
    it = _interpret(interpret)
    return [(p, k, s, w, it) for p in ROW_LADDER for k in ROW_LADDER]


def programs_made() -> int:
    """How many padded frontier-join shapes this process has made."""
    return len(_made)


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_program(slots_t, cand_t, *, interpret: bool):
    """One padded call: (W, P, S) × (W, K, S) -> (P, K) int32, both sides
    on the ladder, S a multiple of the session block.  Not an entry
    point: :func:`frontier_join_support` pads onto the ladder and calls
    it with the shapes :func:`frontier_calls` lists."""
    return frontier_join_support_pallas(
        slots_t, cand_t, block_p=DEFAULT_BLOCK_P,
        block_k=min(DEFAULT_BLOCK_FK, cand_t.shape[1]),
        block_s=DEFAULT_BLOCK_FS, interpret=interpret)


def _session_minor(x: np.ndarray, first: int, rows: int, s: int,
                   w: int) -> np.ndarray:
    """Rows ``[first, first + rows)`` of ``x`` (R, S, W) as a zero-padded
    (w, rows, s) tile, sessions the minor dimension."""
    part = x[first:first + rows]
    out = np.zeros((w, rows, s), np.uint32)
    out[:part.shape[2], :part.shape[0], :part.shape[1]] = part.transpose(2, 0, 1)
    return out


def frontier_join_support(slots, cand, *, min_sessions: int = 0,
                          interpret: bool | None = None) -> np.ndarray:
    """(P, S, W) × (K, S, W) -> support (P, K) int32, on the host.

    Pads and tiles on the host as the module docstring says, uploads
    each tile once, calls the padded program per pair of tiles and
    reads every answer back.  Under an active host profile
    (:mod:`repro.core.obs`) the bytes of the tiles copied to the device
    are counted."""
    slots = np.asarray(slots, np.uint32)
    cand = np.asarray(cand, np.uint32)
    p_prefixes, n_sessions, n_words = slots.shape
    k_items = cand.shape[0]
    if p_prefixes == 0 or k_items == 0:
        return np.zeros((p_prefixes, k_items), np.int32)
    s, w = frontier_shape(n_sessions, n_words, min_sessions)
    it = _interpret(interpret)

    prof = obs.host_profile

    def upload(x, tiles):
        out = []
        for first, rows in tiles:
            tile = _session_minor(x, first, rows, s, w)
            prof.count(obs.METRIC_MINE_JOIN_H2D_BYTES, tile.nbytes)
            out.append(jax.device_put(tile))
        return out

    p_tiles, k_tiles = _tiles(p_prefixes), _tiles(k_items)
    slots_d, cand_d = upload(slots, p_tiles), upload(cand, k_tiles)
    # every call is dispatched before the first answer is read back
    calls = []
    for (p0, pr), sd in zip(p_tiles, slots_d):
        for (k0, kr), cd in zip(k_tiles, cand_d):
            _made.add((pr, kr, s, w, it))
            calls.append((p0, k0, frontier_program(sd, cd, interpret=it)))
    out = np.empty((p_prefixes, k_items), np.int32)
    for p0, k0, part in calls:
        dst = out[p0:p0 + ROW_TILE, k0:k0 + ROW_TILE]
        dst[...] = np.asarray(part)[:dst.shape[0], :dst.shape[1]]
    return out


def warm_frontier_join(n_sessions: int, n_words: int, min_sessions: int = 0,
                       interpret: bool | None = None) -> int:
    """Make every program of :func:`frontier_programs` now, by running
    each on zeros; returns how many there are."""
    progs = frontier_programs(n_sessions, n_words, min_sessions, interpret)
    for p, k, s, w, it in progs:
        if (p, k, s, w, it) not in _made:
            jax.block_until_ready(frontier_program(
                jax.device_put(np.zeros((w, p, s), np.uint32)),
                jax.device_put(np.zeros((w, k, s), np.uint32)),
                interpret=it))
            _made.add((p, k, s, w, it))
    return len(progs)
