"""Sequential pattern mining (Palpatine §3.2).

Implements the algorithm families the paper compares, over a shared packed
vertical-bitmap engine (the SPAM/VMSP representation):

* ``gsp``        — Apriori, breadth-first candidate generation.
* ``spam``       — Apriori over vertical bitmaps (all patterns).
* ``prefixspan`` — pattern-growth, depth-first projected databases.
* ``vmsp``       — the paper's choice: vertical bitmaps + *maximal* filtering.

Palpatine's configuration (paper §3.2/§5): single-item itemsets (an access
log is totally ordered), ``maxgap=1`` (consecutive pattern items must be
adjacent in the session), pattern length in [3, 15], dynamic minimum support.

Bitmaps are materialized for *frequent items only* (item support is counted
from the padded session matrix first), so memory is O(freq_items × sessions ×
words) — the back store may hold millions of containers but only the hot set
enters the vertical representation.

Frontier engine
---------------
The bitmap miners (``gsp``/``spam``/``vmsp``) walk the pattern lattice
*level-synchronously*: all surviving depth-``d`` prefixes are held as one
packed ``(P, S, W)`` uint32 tensor and the whole frontier is expanded in a
single fused ``(P, K)`` join against the candidate item bitmaps.  Extension
slots are computed once per level (not per candidate batch), support counting
visits only the sessions where a prefix actually occurs (the slot tensor is
~``support/S`` dense at low minsup), and joined bitmaps are materialized only
for the surviving ``(prefix, item)`` pairs.  Forward-extension maximality for
VMSP is a per-prefix boolean mask over the ``(P, K)`` support matrix.

``MiningParams.frontier_budget`` caps the transient join tensor in bytes:
oversized frontiers are processed in budget-sized slabs, and a walk whose
*single-prefix* ``K×S×W`` join already exceeds the cap (a walk-invariant
quantity) spills entirely to the legacy per-node DFS walker (``_dfs_mine``),
which remains the reference implementation for differential tests.

With ``use_kernel=True`` the fused join runs on the Pallas TPU kernel
``frontier_join_support`` in :mod:`repro.kernels.bitmap_support` (validated
in interpret mode on CPU); the DFS spill path uses the per-prefix
``sstep_join`` kernel.

Incremental dynamic minsup
--------------------------
``mine_dynamic_minsup`` descends minsup from its start toward its floor
until a rung holds ``min_patterns`` patterns.  A frontier pass at one
rung visits every sequence frequent at any rung above it, with its
support and its best forward extension's support, so each higher rung's
answer is a threshold of that one pass (``_emit``), in the walk's own
order.  A log's round therefore makes one pass, at the rung the log's
previous round settled on (``PalpatineClient`` keeps it per log), reads
every rung from the start down to it, and mines lower, one pass a rung,
only if none of them holds enough; a log's first round mines each rung
in turn.  The packed ``VerticalBitmaps`` of the passes are built once
a round, at the floor support (a first round's start rung builds its
own, so a backlog satisfied there never pays the floor build), and a
caller that re-mines an unchanged backlog (``PalpatineClient.mine_now``)
passes its cached ``vb`` to skip the build.  A prebuilt ``vb`` must have been constructed at a support count
no higher than the one mined at — rows below the current threshold are
filtered inside the engine.
"""

from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import Callable, Optional, Sequence

import numpy as np

from . import obs
from .sessions import SequenceDatabase

__all__ = [
    "MiningParams",
    "Pattern",
    "VerticalBitmaps",
    "BITMAP_ALGOS",
    "mine",
    "gsp",
    "spam",
    "prefixspan",
    "vmsp",
    "maximal_filter",
    "mine_dynamic_minsup",
    "dynamic_floor_count",
    "warm_join",
    "join_programs",
    "brute_force",
]

_WORD = 32  # packed uint32 words

#: byte cap on the boolean (n_sessions × n_items) dedup scratch in
#: VerticalBitmaps.__init__; larger databases fall back to row-local sorts
_SCATTER_BUDGET_BYTES = 64 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class MiningParams:
    """User-specific constraints (paper §3.2 / §5 'Pattern mining')."""

    minsup: float = 0.1          # fraction of sessions
    min_len: int = 3
    max_len: int = 15
    maxgap: Optional[int] = 1    # 1 = contiguous (paper default); None = any
    use_kernel: bool = False     # route support counting through Pallas
    # byte cap on the frontier engine's transient join tensor; a walk whose
    # single-prefix K×S×W join exceeds it falls back to the DFS walker
    frontier_budget: int = 64 * 1024 * 1024
    # the kernel join pads its session axis to at least this many
    # sessions, so rounds over up to that many share its programs (an
    # online client sets its tail)
    join_min_sessions: int = 0

    def minsup_count(self, n_sessions: int) -> int:
        return max(1, int(math.ceil(self.minsup * n_sessions)))


@dataclasses.dataclass(frozen=True)
class Pattern:
    items: tuple
    support: int

    def __len__(self) -> int:
        return len(self.items)


# ---------------------------------------------------------------------------
# Vertical packed-bitmap engine (SPAM / VMSP representation)
# ---------------------------------------------------------------------------


class VerticalBitmaps:
    """Per-item occurrence bitmaps for the frequent items, packed 32
    positions/word.

    ``bits[r]`` has shape (n_sessions, n_words); bit ``p % 32`` of word
    ``p // 32`` for session ``s`` is set iff item ``freq_items[r]`` occurs at
    position ``p`` of session ``s``.  Padding positions are never set, so
    joining with an item bitmap implicitly masks shifted-past-the-end bits.
    """

    def __init__(self, db: SequenceDatabase, minsup_count: int = 1):
        with obs.host_profile.span(obs.SPAN_HOST_MINE_BITMAPS):
            self._pack(db, minsup_count)

    def _pack(self, db: SequenceDatabase, minsup_count: int) -> None:
        mat, _ = db.padded_matrix()
        self.n_sessions = mat.shape[0]
        max_len = mat.shape[1] if mat.size else 0
        self.n_words = max(1, (max_len + _WORD - 1) // _WORD)

        if mat.size:
            sess, pos = np.nonzero(mat >= 0)
            item = mat[sess, pos]
            # item support = #sessions containing the item (count each
            # (sess, item) pair once).  Two dedup strategies replace the
            # global np.unique-over-encoded-pairs sort: a sort-free boolean
            # scatter when the (n_sessions × n_items) scratch fits the byte
            # budget, else per-row sorts of the (short) padded matrix —
            # n_items is the *cumulative* vocabulary (tail() views share
            # it), so the dense scratch must not scale with it unchecked.
            if self.n_sessions * db.n_items <= _SCATTER_BUDGET_BYTES:
                seen = np.zeros((self.n_sessions, db.n_items), bool)
                seen[sess, item] = True
                per_item = seen.sum(axis=0, dtype=np.int64)
            else:
                sm = np.sort(mat, axis=1)          # row-local: dups adjacent
                keep = sm >= 0                     # drop -1 padding
                keep[:, 1:] &= sm[:, 1:] != sm[:, :-1]
                per_item = np.bincount(
                    sm[keep], minlength=db.n_items
                ).astype(np.int64)
            self.freq_items = np.nonzero(per_item >= minsup_count)[0].astype(np.int32)
            self.freq_support = per_item[self.freq_items]
            row_of = np.full(db.n_items, -1, np.int32)
            row_of[self.freq_items] = np.arange(self.freq_items.size, dtype=np.int32)
            keep = row_of[item] >= 0
            sess, pos, item = sess[keep], pos[keep], item[keep]
            bits = np.zeros(
                (self.freq_items.size, self.n_sessions, self.n_words), np.uint32
            )
            word, bit = pos // _WORD, pos % _WORD
            np.bitwise_or.at(
                bits,
                (row_of[item], sess, word),
                (np.uint32(1) << bit.astype(np.uint32)),
            )
            self._row_of = row_of
        else:
            self.freq_items = np.zeros((0,), np.int32)
            self.freq_support = np.zeros((0,), np.int64)
            self._row_of = np.full(db.n_items, -1, np.int32)
            bits = np.zeros((0, self.n_sessions, self.n_words), np.uint32)
        self.bits = bits

    def row(self, item_id: int) -> int:
        r = int(self._row_of[item_id])
        if r < 0:
            raise KeyError(f"item {item_id} is not frequent")
        return r

    # -- primitive ops ------------------------------------------------------
    @staticmethod
    def shift1(b: np.ndarray) -> np.ndarray:
        """Move every set bit one position later (possible extension slots
        for maxgap=1).  Works on (..., n_words)."""
        carry = np.zeros_like(b)
        carry[..., 1:] = b[..., :-1] >> np.uint32(31)
        return ((b << np.uint32(1)) | carry).astype(np.uint32)

    @classmethod
    def smear_after(cls, b: np.ndarray) -> np.ndarray:
        """Set all positions strictly after the first set bit per session
        (SPAM's s-step transform for unconstrained gap)."""
        x = b.copy()
        for k in (1, 2, 4, 8, 16):  # within-word smear toward higher bits
            x |= x << np.uint32(k)
        after = cls.shift1(x)
        # any earlier word nonzero -> whole word saturates
        nz = (b != 0).astype(np.uint32)
        earlier = np.cumsum(nz, axis=-1) - nz  # count of nonzero earlier words
        after[earlier > 0] = np.uint32(0xFFFFFFFF)
        return after

    def extension_slots(self, b: np.ndarray, maxgap: Optional[int]) -> np.ndarray:
        if maxgap is None:
            return self.smear_after(b)
        out = self.shift1(b)
        acc = out
        for _ in range(maxgap - 1):
            acc = self.shift1(acc)
            out = out | acc
        return out

    @staticmethod
    def support(b: np.ndarray) -> np.ndarray:
        """#sessions with >=1 set bit.  (..., S, W) -> (...,)."""
        return np.any(b != 0, axis=-1).sum(axis=-1)

    # -- batched s-step join (per-prefix; used by the DFS spill path) -------
    def sstep_join(
        self,
        prefix_bits: np.ndarray,
        cand_rows: np.ndarray,
        maxgap: Optional[int],
        use_kernel: bool = False,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Join a prefix bitmap against candidate item bitmaps (by row).

        Returns ``(joined (K,S,W), support (K,))`` where ``joined[k]`` marks
        end positions of ``prefix + (freq_items[cand_rows[k]],)``.
        """
        slots = self.extension_slots(prefix_bits, maxgap)
        cand = self.bits[cand_rows]
        if use_kernel:
            from repro.kernels.bitmap_support import ops as _ops

            joined, sup = _ops.sstep_join_support(slots, cand)
            return np.asarray(joined), np.asarray(sup)
        joined = slots[None, :, :] & cand
        return joined, self.support(joined)


# ---------------------------------------------------------------------------
# Frontier engine — level-synchronous lattice walk, fused (P×K) support join
# ---------------------------------------------------------------------------


def _frontier_support(
    slots: np.ndarray,
    cand: np.ndarray,
    params: MiningParams,
    allowed: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Fused support count for a whole frontier: (P,S,W) × (K,S,W) -> (P,K).

    The numpy path is sparse over sessions: only ``(prefix, session)`` pairs
    with a nonzero slot word are joined (a prefix's slot tensor is
    ~``support/S`` dense, so this skips the vast majority of the dense
    ``P×K×S×W`` work at low minsup).  Chunked so the transient stays under
    ``params.frontier_budget`` bytes.  ``use_kernel=True`` routes the dense
    join through the Pallas ``frontier_join_support`` kernel instead, on
    the finite set of padded shapes that kernel's wrapper makes.  Each
    call is the host-profile span ``palp.mine.join``.

    ``allowed`` is an optional (P,K) bool mask of candidate extensions per
    prefix (apriori narrowing for maxgap=None: a child's frequent
    extensions are a subset of its parent's).  The numpy path joins only
    the column union of the mask — items no prefix still allows drop out
    of the whole level — and disallowed pairs report support 0; the kernel
    path computes the dense join and masks after.
    """
    with obs.host_profile.span(obs.SPAN_HOST_MINE_JOIN):
        return _frontier_join(slots, cand, params, allowed)


def warm_join(params: MiningParams,
              dbs: Sequence[SequenceDatabase]) -> None:
    """Make now every frontier-join program that a round over ``dbs``
    with these parameters can call: for the word count of each log's
    longest session, with sessions padded to ``params.join_min_sessions``.
    Nothing unless ``params.use_kernel``."""
    if not params.use_kernel:
        return
    from repro.kernels.bitmap_support import ops as _ops

    longest = (max(map(len, db.sessions), default=1) for db in dbs)
    for words in sorted({-(-n // _WORD) for n in longest}):
        _ops.warm_frontier_join(params.join_min_sessions, words)


def join_programs(params: MiningParams) -> int:
    """How many frontier-join programs this process has made (0 unless
    ``params.use_kernel``)."""
    if not params.use_kernel:
        return 0
    from repro.kernels.bitmap_support import ops as _ops

    return _ops.programs_made()


def _frontier_join(
    slots: np.ndarray,
    cand: np.ndarray,
    params: MiningParams,
    allowed: Optional[np.ndarray],
) -> np.ndarray:
    p_prefixes, n_sessions, n_words = slots.shape
    k_items = cand.shape[0]
    if p_prefixes == 0 or k_items == 0:
        return np.zeros((p_prefixes, k_items), np.int64)
    if params.use_kernel:
        from repro.kernels.bitmap_support import ops as _ops

        sup = _ops.frontier_join_support(
            slots, cand, min_sessions=params.join_min_sessions
        ).astype(np.int64)
        if allowed is not None:
            sup[~allowed] = 0
        return sup

    cols = None
    cand_cols = cand
    if allowed is not None:
        cols = np.nonzero(allowed.any(axis=0))[0]
        if cols.size == k_items:
            cols = None
        else:
            cand_cols = cand[cols]
    k_cols = cand_cols.shape[0]
    sup = np.zeros((p_prefixes, k_items), np.int64)
    pnz, snz = np.nonzero(slots.any(axis=-1))
    if pnz.size == 0 or k_cols == 0:
        return sup
    cand_t = np.ascontiguousarray(cand_cols.transpose(1, 0, 2))  # (S, Kc, W)
    chunk = max(1, int(params.frontier_budget) // (k_cols * n_words * 4))
    sup_view = sup if cols is None else np.zeros(
        (p_prefixes, k_cols), np.int64)
    for i in range(0, pnz.size, chunk):
        p_i, s_i = pnz[i : i + chunk], snz[i : i + chunk]
        sl = slots[p_i, s_i]                                 # (c, W)
        hit = ((sl[:, None, :] & cand_t[s_i]) != 0).any(-1)  # (c, Kc)
        # pnz is sorted, so equal-prefix entries form contiguous runs:
        # segment-reduce instead of scatter-add
        uniq, starts = np.unique(p_i, return_index=True)
        sup_view[uniq] += np.add.reduceat(hit.astype(np.int64), starts, axis=0)
    if cols is not None:
        sup[:, cols] = sup_view
    if allowed is not None:
        sup[~allowed] = 0
    return sup


def _dfs_expand(
    vb: VerticalBitmaps,
    params: MiningParams,
    msc: int,
    cand_rows: np.ndarray,
    cand_items: np.ndarray,
    pattern: tuple,
    pbits: np.ndarray,
    sup: int,
    maximal_only: bool,
    out: list,
) -> None:
    """Legacy per-node DFS from one lattice node (reference implementation;
    also the spill target when a frontier level exceeds the byte budget)."""
    has_freq_ext = False
    if len(pattern) < params.max_len and cand_rows.size:
        joined, sups = vb.sstep_join(pbits, cand_rows, params.maxgap, params.use_kernel)
        for k in np.nonzero(sups >= msc)[0]:
            has_freq_ext = True
            _dfs_expand(
                vb, params, msc, cand_rows, cand_items,
                pattern + (int(cand_items[k]),), joined[k], int(sups[k]),
                maximal_only, out,
            )
    if len(pattern) >= params.min_len and (not maximal_only or not has_freq_ext):
        out.append(Pattern(pattern, int(sup)))


def _dfs_mine(
    db: SequenceDatabase,
    params: MiningParams,
    maximal_only: bool,
    vb: Optional[VerticalBitmaps] = None,
) -> list[Pattern]:
    """Per-node DFS lattice walk (the pre-frontier engine, kept as the
    differential reference and the budget-spill fallback)."""
    msc = params.minsup_count(len(db))
    if vb is None:
        vb = VerticalBitmaps(db, msc)
    rows = np.nonzero(vb.freq_support >= msc)[0]
    cand_items = vb.freq_items[rows]
    out: list[Pattern] = []
    for i, r in enumerate(rows):
        _dfs_expand(
            vb, params, msc, rows, cand_items,
            (int(cand_items[i]),), vb.bits[r], int(vb.freq_support[r]),
            maximal_only, out,
        )
    return out


@dataclasses.dataclass(frozen=True)
class _Level:
    """One depth of a frontier pass: its frequent sequences in walk order
    (item ids lexicographic), their supports, and the most sessions any
    one-item forward extension of each reaches (0 at ``max_len``, where
    none is joined)."""

    patterns: list
    support: np.ndarray
    ext: np.ndarray


def _frontier_levels(
    vb: VerticalBitmaps, params: MiningParams, msc: int
) -> Optional[list[_Level]]:
    """The level-synchronous frontier walk at support count ``msc`` (see
    module docstring), keeping every frequent sequence it visits, level
    by level.  None when even a single prefix's ``K×S×W`` join exceeds
    ``params.frontier_budget`` (a walk-invariant quantity, so a
    whole-walk decision): the caller spills to the DFS walker."""
    rows = np.nonzero(vb.freq_support >= msc)[0]
    if rows.size == 0:
        return []
    cand = vb.bits[rows]                      # (K, S, W), fixed for the walk
    cand_items = vb.freq_items[rows]
    if rows.size * vb.n_sessions * vb.n_words * 4 > params.frontier_budget:
        return None

    patterns: list[tuple] = [(int(it),) for it in cand_items]
    fbits = cand                              # depth-1 frontier = item bitmaps
    fsups = vb.freq_support[rows].astype(np.int64)
    # per-branch candidate narrowing: for unconstrained gap a child's
    # frequent extensions are a subset of its parent's (dropping the last
    # prefix item keeps any occurrence a subsequence), so each frontier
    # entry only joins against its parent's surviving extension set.  The
    # containment argument needs gap-free subsequence semantics — a
    # contiguous (maxgap-constrained) occurrence of the child need not
    # contain one of the parent+item — so the gap rule gates it and
    # contiguous walks keep the full candidate set.
    narrow = params.maxgap is None
    allowed: Optional[np.ndarray] = None      # (P, K) mask; None = all
    levels: list[_Level] = []
    while patterns:
        if len(levels) + 1 >= params.max_len:
            # no further expansion possible (the DFS likewise skips the
            # forward-extension check at max_len)
            levels.append(_Level(patterns, fsups, np.zeros_like(fsups)))
            break
        # extension slots for the whole frontier, once per level (reused
        # across every support chunk below)
        slots = vb.extension_slots(fbits, params.maxgap)
        sup = _frontier_support(slots, cand, params, allowed=allowed)  # (P, K)
        levels.append(_Level(patterns, fsups, sup.max(axis=1)))
        surv = sup >= msc
        pidx, kidx = np.nonzero(surv)
        if pidx.size == 0:
            break
        # materialize joined bitmaps only for the surviving (prefix, item)
        # pairs — they *are* the next frontier
        fbits = slots[pidx] & cand[kidx]
        fsups = sup[pidx, kidx]
        patterns = [
            patterns[p] + (int(cand_items[k]),) for p, k in zip(pidx, kidx)
        ]
        if narrow:
            # child (p, k) inherits p's surviving extension row
            allowed = surv[pidx]
    return levels


def _emit(
    levels: list[_Level], params: MiningParams, msc: int, maximal_only: bool
) -> list[Pattern]:
    """The frontier miner's output at support count ``msc``, from a pass
    at that count or below: the sequences of ``min_len`` items or more
    with support >= ``msc`` (with ``maximal_only``, those with no
    extension that reaches ``msc``), level by level in walk order.  The
    frontier at ``msc`` is the pass's frequent sequences that reach it,
    in the same order, so this is the walk's own emission."""
    out: list[Pattern] = []
    for depth, lv in enumerate(levels, 1):
        if depth < params.min_len:
            continue
        keep = lv.support >= msc
        if maximal_only:
            keep &= lv.ext < msc
        out.extend(Pattern(lv.patterns[p], int(lv.support[p]))
                   for p in np.nonzero(keep)[0])
    return out


def _frontier_mine(
    db: SequenceDatabase,
    params: MiningParams,
    maximal_only: bool,
    vb: Optional[VerticalBitmaps] = None,
) -> list[Pattern]:
    """Level-synchronous frontier miner (see module docstring).

    Byte-identical Pattern output to :func:`_dfs_mine` (set-wise; emission
    order is per-level instead of depth-first)."""
    msc = params.minsup_count(len(db))
    if vb is None:
        vb = VerticalBitmaps(db, msc)
    levels = _frontier_levels(vb, params, msc)
    if levels is None:
        return _dfs_mine(db, params, maximal_only, vb)
    return _emit(levels, params, msc, maximal_only)


# ---------------------------------------------------------------------------
# SPAM — vertical bitmaps, all frequent sequential patterns
# ---------------------------------------------------------------------------


def spam(
    db: SequenceDatabase,
    params: MiningParams,
    vb: Optional[VerticalBitmaps] = None,
) -> list[Pattern]:
    return _frontier_mine(db, params, maximal_only=False, vb=vb)


# ---------------------------------------------------------------------------
# VMSP — maximal sequential patterns (the paper's adopted algorithm)
# ---------------------------------------------------------------------------


def maximal_filter(
    patterns: Sequence[Pattern], maxgap: Optional[int]
) -> list[Pattern]:
    """Keep patterns not strictly included in another frequent pattern.

    For the contiguous case (maxgap=1) inclusion = contiguous subsequence;
    otherwise classic subsequence inclusion.  The non-contiguous branch
    buckets accepted maximal patterns by item, so a candidate only scans the
    supersets sharing its rarest item (with an item-multiset prefilter)
    instead of every accepted pattern.
    """
    if not patterns:
        return []
    ordered = sorted(patterns, key=len, reverse=True)
    maximal: list[Pattern] = []
    if maxgap == 1:
        covered: set = set()
        for p in ordered:
            if p.items not in covered:
                maximal.append(p)
                n = len(p.items)
                for i in range(n):
                    for j in range(i + 1, n + 1):
                        if (j - i) < n:
                            covered.add(p.items[i:j])
    else:
        def subseq(a: tuple, b: tuple) -> bool:
            it = iter(b)
            return all(x in it for x in a)

        mcounts: list[Counter] = []       # item multiset per accepted pattern
        buckets: dict = {}                # item -> indices into `maximal`
        for p in ordered:
            pc = Counter(p.items)
            scan: Optional[list] = None   # smallest bucket among p's items
            for it in pc:
                bl = buckets.get(it)
                if bl is None:
                    scan = None           # no accepted pattern contains `it`
                    break
                if scan is None or len(bl) < len(scan):
                    scan = bl
            contained = False
            if scan:
                for mi in scan:
                    m = maximal[mi]
                    if len(m.items) <= len(p.items):
                        continue
                    mc = mcounts[mi]
                    if all(mc[it] >= c for it, c in pc.items()) and subseq(
                        p.items, m.items
                    ):
                        contained = True
                        break
            if not contained:
                idx = len(maximal)
                maximal.append(p)
                mcounts.append(pc)
                for it in pc:
                    buckets.setdefault(it, []).append(idx)
    return maximal


def vmsp(
    db: SequenceDatabase,
    params: MiningParams,
    vb: Optional[VerticalBitmaps] = None,
) -> list[Pattern]:
    """VMSP-style mining: frontier engine + maximality.

    Non-maximal patterns are pruned during the frontier walk via the
    forward-extension mask (a pattern with a frequent s-extension cannot be
    maximal); a global inclusion filter removes backward/infix containment,
    matching VMSP's output semantics.
    """
    candidates = _frontier_mine(db, params, maximal_only=True, vb=vb)
    return maximal_filter(candidates, params.maxgap)


# ---------------------------------------------------------------------------
# PrefixSpan — pattern growth with projected databases
# ---------------------------------------------------------------------------


def prefixspan(db: SequenceDatabase, params: MiningParams) -> list[Pattern]:
    msc = params.minsup_count(len(db))
    sessions = db.sessions
    out: list[Pattern] = []

    # initial projection: item -> list of (session, end_position)
    first: dict = {}
    for sid, seq in enumerate(sessions):
        for pos, it in enumerate(seq):
            first.setdefault(it, []).append((sid, pos))

    def proj_support(proj: list) -> int:
        return len({sid for sid, _ in proj})

    def grow(pattern: tuple, proj: list) -> None:
        if len(pattern) >= params.min_len:
            out.append(Pattern(pattern, proj_support(proj)))
        if len(pattern) >= params.max_len:
            return
        nxt: dict = {}
        for sid, pos in proj:
            seq = sessions[sid]
            if params.maxgap is None:
                rng = range(pos + 1, len(seq))
            else:
                rng = range(pos + 1, min(pos + 1 + params.maxgap, len(seq)))
            for q in rng:
                nxt.setdefault(seq[q], []).append((sid, q))
        for it, p in nxt.items():
            if proj_support(p) >= msc:
                grow(pattern + (it,), p)

    for it, proj in first.items():
        if proj_support(proj) >= msc:
            grow((it,), proj)
    return out


# ---------------------------------------------------------------------------
# GSP — Apriori BFS over the frontier engine
# ---------------------------------------------------------------------------


def gsp(
    db: SequenceDatabase,
    params: MiningParams,
    vb: Optional[VerticalBitmaps] = None,
) -> list[Pattern]:
    """GSP's level-wise walk *is* the frontier engine: each level holds all
    frequent length-d sequences, candidates are their one-item extensions,
    and the apriori property holds by construction (only frequent prefixes
    are extended, only frequent items are candidate tails).  Support counting
    uses the fused vertical-bitmap join instead of horizontal scans."""
    return _frontier_mine(db, params, maximal_only=False, vb=vb)


# ---------------------------------------------------------------------------
# Oracle + dispatch + dynamic minsup
# ---------------------------------------------------------------------------


def brute_force(db: SequenceDatabase, params: MiningParams) -> list[Pattern]:
    """Exhaustive window/subsequence counter — the test oracle."""
    counts: dict = {}
    for seq in db.sessions:
        seen: set = set()
        if params.maxgap == 1:
            for i in range(len(seq)):
                for j in range(
                    i + params.min_len, min(i + params.max_len, len(seq)) + 1
                ):
                    seen.add(seq[i:j])
        else:
            def expand(path: tuple, pos: int) -> None:
                if len(path) >= params.min_len:
                    seen.add(path)
                if len(path) >= params.max_len:
                    return
                hi = len(seq) if params.maxgap is None else min(
                    pos + 1 + params.maxgap, len(seq)
                )
                for q in range(pos + 1, hi):
                    expand(path + (seq[q],), q)

            for p0 in range(len(seq)):
                expand((seq[p0],), p0)
        # sorted: dict insertion order must not depend on hash-seeded
        # set iteration
        for s in sorted(seen):
            counts[s] = counts.get(s, 0) + 1
    msc = params.minsup_count(len(db))
    # sorted output: the oracle's pattern order is a function of the
    # data alone, never of per-process hash seeds
    return sorted((Pattern(k, v) for k, v in counts.items() if v >= msc),
                  key=lambda p: p.items)


ALGORITHMS: dict[str, Callable] = {
    "gsp": gsp,
    "spam": spam,
    "prefixspan": prefixspan,
    "vmsp": vmsp,
}

#: algorithms that run on the shared VerticalBitmaps engine and accept a
#: prebuilt ``vb`` (incremental dynamic-minsup / backlog-unchanged reuse)
BITMAP_ALGOS = frozenset({"gsp", "spam", "vmsp"})


def mine(
    db: SequenceDatabase,
    params: MiningParams,
    algo: str = "vmsp",
    vb: Optional[VerticalBitmaps] = None,
) -> list[Pattern]:
    fn = ALGORITHMS[algo]
    if vb is not None and algo in BITMAP_ALGOS:
        return fn(db, params, vb=vb)
    return fn(db, params)


def dynamic_floor_count(
    params: MiningParams, n_sessions: int, start: float, floor: float
) -> int:
    """The support count :func:`mine_dynamic_minsup` builds its bitmaps at —
    callers that cache a ``vb`` for it MUST use this same count (a cache
    built at a higher count would silently drop frequent items).  The
    ``min(floor, start)`` clamp guards the start < floor corner, where the
    first (and only) retry mines below the floor."""
    return dataclasses.replace(
        params, minsup=min(floor, start)
    ).minsup_count(n_sessions)


def _ladder(start: float, floor: float, decay: float) -> list[float]:
    """The descent's rungs: ``start``, decayed until it reaches ``floor``
    (the first rung at or below it is the last)."""
    rungs = [start]
    while rungs[-1] > floor:
        rungs.append(max(floor, rungs[-1] * decay))
    return rungs


def _read_rung(
    levels: list[_Level], params: MiningParams, n_sessions: int, algo: str
) -> list[Pattern]:
    """``mine(db, params, algo)`` for a bitmap algorithm, read from a
    frontier pass over ``db`` at ``params.minsup`` or below."""
    maximal = algo == "vmsp"
    out = _emit(levels, params, params.minsup_count(n_sessions), maximal)
    return maximal_filter(out, params.maxgap) if maximal else out


def mine_dynamic_minsup(
    db: SequenceDatabase,
    params: MiningParams,
    algo: str = "vmsp",
    start: float = 0.5,
    floor: float = 0.01,
    decay: float = 0.5,
    min_patterns: int = 16,
    vb: Optional[VerticalBitmaps] = None,
    vb_factory: Optional[Callable[[], VerticalBitmaps]] = None,
    last: Optional[float] = None,
) -> tuple[list[Pattern], float]:
    """Paper §4.2: start with a high minsup and decay it until enough
    frequent sequences are discovered.  Returns (patterns, used_minsup):
    the first rung from ``start`` down with ``min_patterns`` patterns,
    else the floor.

    ``last`` is the minsup the log's previous descent settled on.  With
    it, a bitmap algorithm makes one frontier pass, at the highest rung
    not above ``last`` (the floor if every rung is), and reads every
    rung from ``start`` down to it from that pass; only if none of them
    holds enough does the descent mine on below, one pass a rung.
    Without it (a log's first round), each rung is its own pass.  Either
    way each rung's answer is exactly ``mine(db, rung, algo)``, so the
    result does not depend on ``last``.  Each pass is one
    ``palp.mine.passes`` on the host profile.

    For the bitmap algorithms the packed ``VerticalBitmaps`` are built
    once, at the floor support (:func:`dynamic_floor_count`): lazily, at
    the first decay, when there is no ``last`` (a backlog satisfied at
    ``start`` never pays the floor build), else before the pass.  Pass
    ``vb``, built at or below that count, to reuse bitmaps across calls
    on an unchanged backlog, or ``vb_factory`` to build them at that
    count when needed and capture the build for caching.
    """
    ladder = _ladder(start, floor, decay)
    bitmap = algo in BITMAP_ALGOS and len(db) > 0

    def floor_bitmaps() -> VerticalBitmaps:
        if vb_factory is not None:
            return vb_factory()
        return VerticalBitmaps(
            db, dynamic_floor_count(params, len(db), start, floor))

    # rungs ladder[:read] are read from one pass at ladder[read - 1]
    read, levels = 0, None
    if bitmap and last is not None:
        read = next((i for i, m in enumerate(ladder) if m <= last),
                    len(ladder) - 1) + 1
        if vb is None:
            vb = floor_bitmaps()
        at = dataclasses.replace(params, minsup=ladder[read - 1])
        levels = _frontier_levels(vb, at, at.minsup_count(len(db)))
        if levels is None:                    # spills: a pass per rung
            read = 0
        else:
            obs.host_profile.count(obs.METRIC_MINE_PASSES)
    for i, minsup in enumerate(ladder):
        rung = dataclasses.replace(params, minsup=minsup)
        if i < read:
            patterns = _read_rung(levels, rung, len(db), algo)
        else:
            obs.host_profile.count(obs.METRIC_MINE_PASSES)
            patterns = mine(db, rung, algo, vb=vb)
        if len(patterns) >= min_patterns or i == len(ladder) - 1:
            break
        if bitmap and vb is None:
            vb = floor_bitmaps()              # first decay: built once
    return patterns, minsup
