"""PALPATINE client facade (paper §4.1 work flow, steps a..m).

``PalpatineClient`` wraps the DKV store client API unchanged (transparent to
applications): reads are intercepted by the Controller, logged by Monitoring,
served from the two-space cache when possible, and trigger background
prefetching driven by the probabilistic trees.  ``BaselineClient`` is the
unmodified client (direct store access), used as the paper's baseline.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

from .backstore import Clock, SimulatedDKVStore
from .cache import TwoSpaceCache
from .decision import VectorizedPrefetchEngine
from .heuristics import HeuristicConfig
from . import obs
from .metastore import PatternMetastore
from .obs import (
    METRIC_MINE_COLD_PROGRAMS,
    SPAN_CACHE,
    SPAN_DECISION,
    SPAN_DEMAND,
    SPAN_HOST_DECIDE,
    SPAN_HOST_MINE,
    SPAN_HOST_MINE_REBUILD,
    SPAN_HOST_MINE_WARM,
    SPAN_OP,
    SPAN_PREFETCH,
    EVENT_SHED,
)
from .mining import (
    MiningParams,
    Pattern,
    VerticalBitmaps,
    dynamic_floor_count,
    join_programs,
    mine_dynamic_minsup,
    warm_join,
)
from .ptree import PTreeIndex
from .sessions import AccessLogger

__all__ = ["PalpatineConfig", "PalpatineClient", "BaselineClient"]

#: cache bookkeeping cost per request (in-memory hash + LRU on the paper's
#: 3.4 GHz Xeon) — what a cache hit costs instead of a network round trip.
CACHE_OVERHEAD = 2e-6


@dataclasses.dataclass
class PalpatineConfig:
    heuristic: HeuristicConfig = dataclasses.field(default_factory=HeuristicConfig)
    cache_bytes: int = 32 * 1024 * 1024          # paper default working point
    preemptive_frac: float = 0.10
    mining: MiningParams = dataclasses.field(default_factory=MiningParams)
    # where the vectorized engines walk the trees: "numpy" on the host or
    # "jax" for the jitted device walk (the decision-side twin of
    # ``mining.use_kernel``)
    decision_backend: str = "numpy"
    algo: str = "vmsp"
    metastore_capacity: int = 10_000
    session_gap: float = 1.0                      # virtual seconds
    prefetch_batch: int = 16                      # per-table batching (§4.5)
    prefetch_enabled: bool = True
    # timeliness/efficiency guards (paper §1: prefetching must be timely,
    # useful, efficient): a read racing an in-flight prefetch falls back to
    # a demand fetch beyond this wait; prefetch batches are dropped when
    # the background channel is backlogged (bounded I/O amplification)
    prefetch_wait_cap: float = 2e-3
    backlog_cap: float = 0.05
    # hybrid container mining (paper §3.1 pattern type 1): additionally
    # mine COLUMN-level containers (table, column) generalized across rows;
    # predictions are instantiated with the triggering request's row
    # ("a sequence of table and columns that are accessed for a given row")
    column_mining: bool = False
    # online mining (§4.2): re-mine every N logged operations (None = offline);
    # an online client makes every device program a round can use in its
    # first round, so later rounds (inside a user's read) start none
    online_mine_every: Optional[int] = None
    online_tail_sessions: int = 2_000             # mine recent chunk only
    dynamic_minsup_start: float = 0.5
    dynamic_minsup_floor: float = 0.01
    min_patterns: int = 16


class PalpatineClient:
    """Drop-in DKV client with monitoring, mining, prefetching and caching."""

    def __init__(self, store: SimulatedDKVStore, config: Optional[PalpatineConfig] = None,
                 clock: Optional[Clock] = None, cache_factory=None):
        self.store = store
        self.cfg = config or PalpatineConfig()
        self.clock = clock or Clock()
        self.logger = AccessLogger(self.cfg.session_gap)
        # cache_factory(self) may build any TwoSpaceCache-shaped object that
        # needs the client's own state — e.g. the cluster's per-shard cache
        # maps item ids back to keys through this client's vocabulary
        self.cache = (cache_factory(self) if cache_factory is not None else
                      TwoSpaceCache(self.cfg.cache_bytes, self.cfg.preemptive_frac))
        self.metastore = PatternMetastore(self.cfg.metastore_capacity,
                                          self.cfg.mining.max_len)
        self.engine = VectorizedPrefetchEngine(
            PTreeIndex.build([]), self.cfg.heuristic,
            backend=self.cfg.decision_backend)
        self.col_logger = AccessLogger(self.cfg.session_gap)
        # column patterns are instantiated with the *current* request's row,
        # so they are always walked progressively (one confirmed step ->
        # next level), regardless of the main heuristic
        self.col_engine = VectorizedPrefetchEngine(
            PTreeIndex.build([]),
            HeuristicConfig("fetch_progressive", progressive_depth=2),
            backend=self.cfg.decision_backend)
        self.col_metastore: Optional[PatternMetastore] = None
        self._ops_since_mine = 0
        self.mining_runs = 0
        #: host seconds of the mining rounds, from ``mine_now``'s entry to
        #: the new trees' install (``rebuild_wall_time``: the part spent
        #: in ``populate``, ``PTreeIndex.build`` and ``replace_index`` of
        #: both metastores) -- telemetry on ``obs.host_clock`` that never
        #: feeds simulated time or mined results
        self.mining_wall_time = 0.0
        self.rebuild_wall_time = 0.0
        #: device programs an online round needed that the first round's
        #: warm-up had not made (``palp.mine.cold_programs``)
        self.cold_programs = 0
        self._warmed = False
        # packed-bitmap reuse across mining runs: {"main"/"col": (fp, vb)}
        self._vb_cache: dict = {}
        # the minsup each log's last round settled on: {"main"/"col": m},
        # where its next round's one frontier pass starts
        self._settled: dict = {}
        self._last_mine_events: Optional[int] = None
        self._last_mine_generation: Optional[int] = None
        #: demand reads that paid >= 1 replica ack timeout before landing
        #: (the client-visible cost of not-yet-suspected crashed replicas:
        #: non-zero only during the failure detector's discovery window)
        self.demand_timeouts = 0
        store.watch(self._on_store_write)
        self._in_write = False
        # Palpascope: share the store's tracer (NULL_TRACER unless
        # enable_tracing was called on the store/cluster), and have both
        # engines name the pattern behind every emitted prefetch target
        self.tracer = store.tracer
        self.engine.attribute = True
        self.col_engine.attribute = True

    # ------------------------------------------------------------------
    # Client API (mirrors the store's get/put — transparent, §4.5)
    # ------------------------------------------------------------------
    def _demand_fetch(self, key, now: float):
        """One demand read as a future: (value, completion_time)."""
        fut = self.store.get_async(key, now)
        if fut.timed_out:
            self.demand_timeouts += 1
        return fut.value(), fut.done_at

    def read(self, container) -> tuple[Any, float]:
        """Returns (value, virtual latency).  Advances the virtual clock."""
        now = self.clock.now
        tr = self.tracer
        sp = tr.start(SPAN_OP, now)
        try:
            self.logger.record(now, container)
            iid = self.logger.db.item_id(container)
            if sp.live:
                sp.set(op="read", key=self._store_key(container))
            if self.cfg.column_mining:
                self.col_logger.record(now, self._generalize(container))

            csp = tr.span(SPAN_CACHE, now)
            hit = self.cache.lookup(iid, now)
            if csp.live:
                csp.set(hit=hit is not None)
            tr.end(csp, now)
            if hit is not None and hit[1] <= self.cfg.prefetch_wait_cap:
                value, wait = hit
                latency = CACHE_OVERHEAD + wait
            else:
                # miss, or the prefetch is too far in flight: demand-fetch
                # wins the race (timeliness failure, counted against
                # precision by the still-pending preemptive entry)
                dsp = tr.span(SPAN_DEMAND, now)
                try:
                    value, done_at = self._demand_fetch(
                        self._store_key(container), now)
                    dsp.finish(done_at)
                finally:
                    tr.end(dsp)
                latency = (done_at - now) + CACHE_OVERHEAD
                if value is not None:
                    self.cache.put_demand(iid, value, len(value))

            if self.cfg.prefetch_enabled:
                self._decide(container, iid, now)
            self._maybe_online_mine()
            self.clock.advance(latency)
            sp.finish(now + latency)
            return value, latency
        except BaseException:
            sp.mark("error")
            raise
        finally:
            tr.end(sp)

    def read_many(self, containers: Sequence) -> tuple[list, float]:
        """Batched read with overlapping in-flight demand fetches.

        All containers are logged in order (one monitoring event each, so
        mining sees the same sequence a loop of ``read`` would produce);
        cache hits are served locally and every miss joins one scatter-
        gather ``multi_get_async`` whose sub-batches pipeline concurrently
        across shards — the batch completes when the slowest node (or the
        longest still-in-flight prefetch) lands, not at the sum of
        per-key round trips.  Returns (values, batch latency)."""
        now = self.clock.now
        tr = self.tracer
        sp = tr.start(SPAN_OP, now)
        try:
            if sp.live:
                sp.set(op="read_many", n=len(containers))
            self.logger.record_many(now, containers)
            if self.cfg.column_mining:
                self.col_logger.record_many(
                    now, [self._generalize(c) for c in containers])
            values: list = [None] * len(containers)
            iids: list[int] = []
            misses: list[tuple[int, int, Any]] = []   # (position, iid, key)
            worst_wait = 0.0
            csp = tr.span(SPAN_CACHE, now)
            for pos, container in enumerate(containers):
                iid = self.logger.db.item_id(container)
                iids.append(iid)
                hit = self.cache.lookup(iid, now)
                if hit is not None and hit[1] <= self.cfg.prefetch_wait_cap:
                    values[pos] = hit[0]
                    worst_wait = max(worst_wait, hit[1])
                else:
                    misses.append((pos, iid, self._store_key(container)))
            if csp.live:
                csp.set(hits=len(containers) - len(misses),
                        misses=len(misses))
            tr.end(csp, now)

            done_at = now + worst_wait
            if misses:
                keys = [k for _, _, k in misses]
                dsp = tr.span(SPAN_DEMAND, now)
                try:
                    fut = self.store.multi_get_async(keys, now)
                    vals, batch_done = fut.result()
                    if fut.timed_out:
                        self.demand_timeouts += 1
                    dsp.finish(batch_done)
                finally:
                    tr.end(dsp)
                for (pos, iid, _), v in zip(misses, vals):
                    values[pos] = v
                    if v is not None:
                        self.cache.put_demand(iid, v, len(v))
                done_at = max(done_at, batch_done)

            latency = (done_at - now) + CACHE_OVERHEAD * len(containers)
            if self.cfg.prefetch_enabled:
                for iid, container in zip(iids, containers):
                    self._decide(container, iid, now)
            self._maybe_online_mine()
            self.clock.advance(latency)
            sp.finish(now + latency)
            return values, latency
        except BaseException:
            sp.mark("error")
            raise
        finally:
            tr.end(sp)

    def write(self, container, value: bytes) -> float:
        """Write-through cache update + async store write (§4.4); returns
        the (small) foreground latency."""
        now = self.clock.now
        tr = self.tracer
        sp = tr.start(SPAN_OP, now)
        iid = self.logger.db.item_id(container)
        if sp.live:
            sp.set(op="write", key=self._store_key(container))
        self._in_write = True
        try:
            self.store.put(self._store_key(container), value, now)
            sp.finish(now + CACHE_OVERHEAD)
        except BaseException:
            sp.mark("error")
            raise
        finally:
            self._in_write = False
            tr.end(sp)
        self.cache.write(iid, value, len(value))
        self.clock.advance(CACHE_OVERHEAD)
        return CACHE_OVERHEAD

    def end_session(self) -> None:
        """Explicit session cut (end of a transaction/request)."""
        self.logger.flush_session()
        self.col_logger.flush_session()

    # ------------------------------------------------------------------
    # Mining control (stage 1 -> stage 2 in the benchmarks)
    # ------------------------------------------------------------------
    def _params(self) -> MiningParams:
        """The mining parameters of a round: an online round's kernel join
        pads its sessions to the tail, so every round shares its shapes."""
        if self.cfg.online_mine_every is None:
            return self.cfg.mining
        return dataclasses.replace(
            self.cfg.mining, join_min_sessions=self.cfg.online_tail_sessions)

    def _tail(self, logger: AccessLogger):
        db = logger.snapshot()
        if self.cfg.online_mine_every is not None:
            db = db.tail(self.cfg.online_tail_sessions)
        return db

    def _engines(self) -> list:
        """The decision engines a round installs trees in."""
        return ([self.engine, self.col_engine] if self.cfg.column_mining
                else [self.engine])

    def _warm(self, params: MiningParams, dbs) -> None:
        """Make every device program a round of this configuration can
        use: the frontier join's ladder for the tail's sessions and each
        log's longest session, and the decision walk's node ladder up to
        the metastore's bound (``metastore_capacity`` patterns of up to
        ``max_len`` items)."""
        self._warmed = True
        with obs.host_profile.span(SPAN_HOST_MINE_WARM):
            warm_join(params, dbs)
            nodes = self.cfg.metastore_capacity * self.cfg.mining.max_len
            for eng in self._engines():
                eng.warm_walk(nodes)

    def mine_now(self) -> int:
        """Run the Data Mining Engine on the backlog, furnish the metastore,
        rebuild the probabilistic trees.  Returns #patterns stored."""
        with obs.host_profile.span(SPAN_HOST_MINE):
            t0 = obs.host_clock()
            params = self._params()
            col_db = (self._tail(self.col_logger) if self.cfg.column_mining
                      else None)
            db = self._tail(self.logger)
            if self.cfg.online_mine_every is not None and not self._warmed:
                self._warm(params, [db] if col_db is None else [db, col_db])
            joins = join_programs(params)
            if col_db is not None:
                # a column pattern needs >= 2 sessions whatever the floor;
                # each round installs a fresh column metastore
                floor = max(self.cfg.dynamic_minsup_floor,
                            2.0 / max(len(col_db), 1))
                ms = PatternMetastore(self.cfg.metastore_capacity,
                                      self.cfg.mining.max_len)
                self._install(self.col_engine, ms, self._mine_log(
                    self.col_logger, col_db, "col", floor, params))
                self.col_metastore = ms
            patterns = self._mine_log(self.logger, db, "main",
                                      self.cfg.dynamic_minsup_floor, params)
            self.mining_runs += 1
            self._last_mine_events = self.logger.n_events
            t1 = self._install(self.engine, self.metastore, patterns)
            self.mining_wall_time += t1 - t0
            self._last_mine_generation = self.metastore.generation
            if self._warmed:
                cold = join_programs(params) - joins + sum(
                    not eng.walk_program_made() for eng in self._engines())
                self.cold_programs += cold
                obs.host_profile.count(METRIC_MINE_COLD_PROGRAMS, cold)
            return len(self.metastore)

    def _mine_log(self, logger: AccessLogger, db, which: str, floor: float,
                  params: MiningParams) -> list[Pattern]:
        """One log's patterns for a round: the dynamic-minsup descent
        (paper §4.2) from ``dynamic_minsup_start`` down to ``floor``, kept
        to those seen in two sessions or more.  The descent makes one
        frontier pass at the minsup the log's previous round (``which``:
        "main" or "col") settled on, and reads the rungs above it from
        that pass.  That round's packed bitmaps are reused iff the tail
        is unchanged (same event count, session count, vocabulary and
        floor count), so a re-mine over an idle backlog skips the
        scatter/pack."""
        count = dynamic_floor_count(
            self.cfg.mining, len(db), self.cfg.dynamic_minsup_start, floor)
        fp = (logger.n_events, len(db.sessions), db.n_items, count)
        hit = self._vb_cache.get(which)

        def build() -> VerticalBitmaps:
            vb = VerticalBitmaps(db, count)
            self._vb_cache[which] = (fp, vb)
            return vb

        patterns, self._settled[which] = mine_dynamic_minsup(
            db, params, self.cfg.algo,
            start=self.cfg.dynamic_minsup_start,
            floor=floor,
            min_patterns=self.cfg.min_patterns,
            vb=hit[1] if hit is not None and hit[0] == fp else None,
            vb_factory=build,
            last=self._settled.get(which))
        # a sequence observed once is not a pattern: support >= 2 sessions
        return [p for p in patterns if p.support >= 2]

    def _install(self, engine: VectorizedPrefetchEngine,
                 metastore: PatternMetastore, patterns: list) -> float:
        """Put a round's patterns in ``metastore`` and their trees in
        ``engine``; returns the host clock at the end."""
        t0 = obs.host_clock()
        with obs.host_profile.span(SPAN_HOST_MINE_REBUILD):
            metastore.populate(patterns)
            engine.replace_index(PTreeIndex.build(metastore))
        t1 = obs.host_clock()
        self.rebuild_wall_time += t1 - t0
        return t1

    def backlog_unchanged_since_mine(self) -> bool:
        """True when no read has been logged since the last ``mine_now``
        AND nothing touched the metastore since (gossip merges / apriori
        adds bump its generation) — only then would a re-mine leave the
        metastore byte-identical (mine_now *replaces* contents, so merged
        foreign patterns must force the full run)."""
        return (self._last_mine_events is not None
                and self._last_mine_events == self.logger.n_events
                and self._last_mine_generation == self.metastore.generation)

    def _maybe_online_mine(self) -> None:
        if self.cfg.online_mine_every is None:
            return
        self._ops_since_mine += 1
        if self._ops_since_mine >= self.cfg.online_mine_every:
            self._ops_since_mine = 0
            self.mine_now()

    # ------------------------------------------------------------------
    # Hybrid column-level mining (paper §3.1 type 1)
    # ------------------------------------------------------------------
    @staticmethod
    def _generalize(container):
        key = container.key() if hasattr(container, "key") else container
        if isinstance(key, tuple) and len(key) == 3:
            return (key[0], None, key[2])     # (table, *, column)
        return key

    def _begin_column_walk(self, container) -> Optional[int]:
        """The column engine's item for a read of a ``(table, row,
        column)`` cell, with the engine's device walk for it begun, so
        that it runs while ``_prefetch`` walks the main engine; None
        where column mining is off or the key is not a cell.  Nothing
        ``_prefetch`` does changes what the column engine decides."""
        if not self.cfg.column_mining:
            return None
        key = self._store_key(container)
        if not (isinstance(key, tuple) and len(key) == 3):
            return None
        gen_iid = self.col_logger.db.item_id(self._generalize(container))
        self.col_engine.begin_walk(gen_iid)
        return gen_iid

    def _decide(self, container, iid: int, now: float) -> None:
        """Prefetch for one read: the column engine's walk is begun
        first, so that it runs under the main engine's."""
        gen_iid = self._begin_column_walk(container)
        self._prefetch(iid, now)
        if gen_iid is not None:
            self._prefetch_columns(container, gen_iid, now)

    def _prefetch_columns(self, container, gen_iid: int,
                          now: float) -> None:
        """Instantiate predicted (table, column) containers with the
        triggering request's row and prefetch the concrete cells."""
        row = self._store_key(container)[1]
        with obs.host_profile.span(SPAN_HOST_DECIDE):
            targets = self.col_engine.on_request(gen_iid)
        if not targets:
            return
        if self.store.backlog(now) > self.cfg.backlog_cap:
            return
        causes = self.col_engine.last_attribution() or [None] * len(targets)
        memo: dict = {}
        concrete = []
        for t, c in zip(targets, causes):
            table, _, col = self.col_logger.db.item(t)
            ckey = (table, row, col)
            if not self.store.contains(ckey):
                continue
            iid = self.logger.db.item_id(ckey)
            if not self.cache.contains(iid):
                concrete.append(
                    (iid, ckey, self._resolve_cause(c, memo, column=True)))
        for i in range(0, len(concrete), self.cfg.prefetch_batch):
            batch = concrete[i:i + self.cfg.prefetch_batch]
            keys = [k for _, k, _ in batch]
            vals, done_ats = self.store.background_multi_get(
                keys, now, self.cfg.backlog_cap)
            for (iid, _, cause), v, done_at in zip(batch, vals, done_ats):
                if v is not None:
                    self.cache.put_prefetch(iid, v, len(v), done_at,
                                            cause=cause)

    # ------------------------------------------------------------------
    # Prefetching (background, §4.1 step j / §4.5 batching)
    # ------------------------------------------------------------------
    def _resolve_cause(self, cause, memo: dict, column: bool = False):
        """Rewrite a cause's tree-root *item id* (client-local vocab) into
        the root *container key*, so attribution rows aggregate across
        tenants/shards that number items differently."""
        if cause is None:
            return None
        key = memo.get(cause.root)
        if key is None:
            db = self.col_logger.db if column else self.logger.db
            key = memo[cause.root] = db.item(cause.root)
        return dataclasses.replace(cause, root=key)

    def _prefetch(self, iid: int, now: float) -> None:
        tr = self.tracer
        if self.store.backlog(now) > self.cfg.backlog_cap:
            tr.event(EVENT_SHED, now)
            return  # background channel(s) saturated: shed prefetch load
        dsp = tr.span(SPAN_DECISION, now)
        with obs.host_profile.span(SPAN_HOST_DECIDE):
            targets = self.engine.on_request(iid)
        causes = (self.engine.last_attribution() or [None] * len(targets)) \
            if targets else []
        if dsp.live:
            dsp.set(targets=len(targets))
        tr.end(dsp, now)
        memo: dict = {}
        wanted = [(i, self._resolve_cause(c, memo))
                  for i, c in zip(targets, causes)
                  if not self.cache.contains(i)]
        if not wanted:
            return
        # First wave item goes unbatched (anticipate the next request,
        # §4.5); the rest batched per prefetch_batch.  A sharded store
        # splits each batch per owning node and sheds per-node past the
        # backlog cap; completion times are per key.
        batches = [wanted[:1]]
        rest = wanted[1:]
        for i in range(0, len(rest), self.cfg.prefetch_batch):
            batches.append(rest[i:i + self.cfg.prefetch_batch])
        psp = tr.span(SPAN_PREFETCH, now)
        try:
            admitted, last_done = 0, now
            for batch in batches:
                if not batch:
                    continue
                keys = [self._store_key_by_id(i) for i, _ in batch]
                vals, done_ats = self.store.background_multi_get(
                    keys, now, self.cfg.backlog_cap)
                for (i, cause), v, done_at in zip(batch, vals, done_ats):
                    if v is not None:
                        self.cache.put_prefetch(i, v, len(v), done_at,
                                                cause=cause)
                        admitted += 1
                        if done_at > last_done:
                            last_done = done_at
            if psp.live:
                psp.set(n_targets=len(wanted), n_admitted=admitted,
                        done_at=last_done)
        finally:
            # background work: the span closes at issue time (children
            # nest within the op) — batch completion is the done_at field
            tr.end(psp, now)

    # ------------------------------------------------------------------
    def _store_key(self, container):
        return container.key() if hasattr(container, "key") else container

    def _store_key_by_id(self, iid: int):
        return self.logger.db.item(iid)

    def on_keys_remapped(self, keys: Sequence) -> None:
        """Cluster membership change: these container keys moved to a new
        primary node.  A per-shard cache must drop their (now misfiled)
        entries and partition placement — a *targeted* invalidation, not a
        full flush.  Plain caches keep everything: the values themselves
        did not change, only their placement."""
        rehome = getattr(self.cache, "rehome", None)
        if rehome is None:
            return
        vocab = self.logger.db._vocab
        rehome([iid for k in keys
                if (iid := vocab.get(k)) is not None])

    def _on_store_write(self, key) -> None:
        """Coherence: the store-side monitor notifies on writes.  Our own
        writes update the cache in place; external writers invalidate."""
        if self._in_write:
            return
        vocab = self.logger.db._vocab
        iid = vocab.get(key)
        if iid is not None:
            self.cache.invalidate(iid)

    @property
    def stats(self):
        return self.cache.stats


class BaselineClient:
    """The unmodified DKV client: every read is a store round trip (issued
    through the same futures RPC layer, so baseline and Palpatine see
    identical channel contention)."""

    def __init__(self, store: SimulatedDKVStore, clock: Optional[Clock] = None):
        self.store = store
        self.clock = clock or Clock()

    def read(self, container) -> tuple[Any, float]:
        key = container.key() if hasattr(container, "key") else container
        now = self.clock.now
        fut = self.store.get_async(key, now)
        value, latency = fut.value(), fut.done_at - now
        self.clock.advance(latency)
        return value, latency

    def read_many(self, containers: Sequence) -> tuple[list, float]:
        """Scatter-gather demand read: sub-batches overlap across shards,
        the batch completes when the slowest node lands."""
        keys = [c.key() if hasattr(c, "key") else c for c in containers]
        now = self.clock.now
        values, done_at = self.store.multi_get_async(keys, now).result()
        latency = done_at - now
        self.clock.advance(latency)
        return values, latency

    def write(self, container, value: bytes) -> float:
        key = container.key() if hasattr(container, "key") else container
        self.store.put(key, value, self.clock.now)
        self.clock.advance(CACHE_OVERHEAD)
        return CACHE_OVERHEAD
