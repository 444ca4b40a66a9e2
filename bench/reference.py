"""The plain reference: Palpatine's client semantics written out in
straightforward Python, sharing no code with the program under test.

It replays a run's operations and gives, for each one, what the program
must answer: every read's value and virtual latency, the prefetch
targets of every decision, the patterns of every mining round and the
cache's counters.  The semantics are the paper's (§3-§4) as the
configuration file states them:

* the access log cuts sessions on ``session_gap`` and on explicit ends;
  items are numbered in the order they are first seen, and that order
  breaks ties wherever the program sorts patterns;
* mining finds the maximal contiguous sequences (``maxgap`` 1) of length
  ``min_len``..``max_len`` whose support (sessions containing them) is at
  least ``ceil(minsup * sessions)``; the dynamic minsup starts at
  ``dynamic_minsup_start`` and halves until ``min_patterns`` are found or
  it reaches the floor; patterns seen in fewer than ``min_support``
  sessions are dropped, and the metastore keeps the best by length x
  support;
* one tree per first item, children in first-insertion order;
  ``fetch_progressive`` opens a context on a root match, prefetches the
  next levels, advances on each continuing read and dies on divergence;
* a two-space LRU cache (main, and a preemptive share for prefetches);
* one store node whose demand, background and write channels queue on
  the virtual clock, with the seeded latency model's jitter;
* online mining (§4.2), where the configuration sets
  ``online_mine_every``: every that many reads, counted since the last
  online round, a round runs inside the read, after its prefetch and
  before the clock advances; writes and explicit rounds leave the count
  alone.  Every round, the explicit ones too, then mines only the last
  ``online_tail_sessions`` sessions of each log.

``control`` names a guarantee the reference breaks on purpose, to show
that the comparison catches it (see ``bench/control.py``).
"""

from __future__ import annotations

import math
from collections import OrderedDict, deque

import numpy as np

#: broken guarantees the control can take
CONTROLS = ("sampled_mining", "no_write_through")


class Store:
    """One node: values, three channels and the latency model."""

    def __init__(self, data: dict, latency: dict, seed: int,
                 demand_lanes: int):
        self.loaded = data
        self.written: dict = {}
        self.lat = latency
        self.rng = np.random.default_rng(seed)
        self.demand = [0.0] * demand_lanes
        self.background = [0.0]
        self.writes = [0.0]

    def value(self, key):
        v = self.written.get(key)
        return self.loaded.get(key) if v is None else v

    def contains(self, key) -> bool:
        return key in self.written or key in self.loaded

    def service(self, n_items: int, n_bytes: int) -> float:
        m = self.lat
        base = m["rtt"] + n_items * m["per_item_service"] + n_bytes / m["bandwidth"]
        j = float(np.exp(self.rng.normal(0.0, m["jitter_sigma"])))
        if self.rng.random() < m["stall_frac"]:
            j *= m["stall_mult"]
        return base * j

    @staticmethod
    def issue(lanes: list, now: float, service: float) -> float:
        i = min(range(len(lanes)), key=lanes.__getitem__)
        done = max(now, lanes[i]) + service
        lanes[i] = done
        return done

    def backlog(self, now: float) -> float:
        return max(0.0, min(self.background) - now)

    def demand_get(self, key, now: float):
        v = self.value(key)
        lat = self.service(1, len(v) if v is not None else 0)
        return v, self.issue(self.demand, now, lat)

    def background_get(self, keys: list, now: float, cap: float):
        if self.backlog(now) > cap:
            return [None] * len(keys), now
        vals = [self.value(k) for k in keys]
        lat = self.service(len(keys), sum(len(v) for v in vals if v is not None))
        return vals, self.issue(self.background, now, lat)

    def put(self, key, value: bytes, now: float) -> None:
        self.written[key] = value
        self.issue(self.writes, now, self.service(1, len(value)))


class LRU:
    def __init__(self, capacity: int):
        self.capacity = capacity
        self.used = 0
        self.od: OrderedDict = OrderedDict()

    def put(self, key, entry: tuple) -> None:
        """entry = (value, size, available_at)"""
        old = self.od.pop(key, None)
        if old is not None:
            self.used -= old[1]
        if entry[1] > self.capacity:
            return
        self.od[key] = entry
        self.used += entry[1]
        while self.used > self.capacity:
            _, e = self.od.popitem(last=False)
            self.used -= e[1]

    def remove(self, key) -> None:
        e = self.od.pop(key, None)
        if e is not None:
            self.used -= e[1]


STAT_NAMES = ("accesses", "hits", "misses", "prefetches", "prefetch_hits",
              "prefetch_waits", "invalidations", "writes")


class Cache:
    def __init__(self, main_bytes: int, preemptive_frac: float):
        self.main = LRU(main_bytes)
        self.pre = LRU(int(main_bytes * preemptive_frac))
        self.stats = dict.fromkeys(STAT_NAMES, 0)

    def lookup(self, key, now: float):
        s = self.stats
        s["accesses"] += 1
        e = self.main.od.get(key)
        if e is not None:
            self.main.od.move_to_end(key)
            s["hits"] += 1
            return e[0], 0.0
        e = self.pre.od.get(key)
        if e is not None:
            self.pre.remove(key)
            wait = max(0.0, e[2] - now)
            s["hits"] += 1
            s["prefetch_hits"] += 1
            if wait > 0:
                s["prefetch_waits"] += 1
            self.main.put(key, (e[0], e[1], 0.0))
            return e[0], wait
        s["misses"] += 1
        return None

    def contains(self, key) -> bool:
        return key in self.main.od or key in self.pre.od

    def put_demand(self, key, value) -> None:
        self.pre.remove(key)
        self.main.put(key, (value, len(value), 0.0))

    def put_prefetch(self, key, value, available_at: float) -> None:
        if self.contains(key):
            return
        self.stats["prefetches"] += 1
        self.pre.put(key, (value, len(value), available_at))

    def write(self, key, value) -> None:
        self.stats["writes"] += 1
        if key in self.pre.od:
            self.pre.put(key, (value, len(value), 0.0))
        else:
            self.main.put(key, (value, len(value), 0.0))


# -- mining ----------------------------------------------------------------

def substring_support(sessions: list, max_len: int) -> dict:
    """Sessions containing each contiguous subsequence of length
    1..max_len."""
    count: dict = {}
    for s in sessions:
        seen = set()
        n = len(s)
        for i in range(n):
            for j in range(i + 1, min(n, i + max_len) + 1):
                seen.add(s[i:j])
        for t in seen:
            count[t] = count.get(t, 0) + 1
    return count


def maximal_sequences(support: dict, n_sessions: int, minsup: float,
                      min_len: int, max_len: int) -> list:
    """The maximal frequent contiguous sequences as (items, support),
    longest first, then by item ids."""
    msc = max(1, math.ceil(minsup * n_sessions))
    freq = {t: c for t, c in support.items() if c >= msc}
    extended = {t[:-1] for t in freq if len(t) > 1}
    cand = [t for t in freq if len(t) >= min_len
            and (len(t) == max_len or t not in extended)]
    covered = set()
    for t in cand:
        n = len(t)
        for i in range(n):
            for j in range(i + 1, n + 1):
                if j - i < n:
                    covered.add(t[i:j])
    out = [(t, freq[t]) for t in cand if t not in covered]
    out.sort(key=lambda p: (-len(p[0]), p[0]))
    return out


def mine(sessions: list, c: dict, floor: float) -> list:
    """Dynamic minsup (§4.2), the support floor of two sessions and the
    metastore's ranking; returns the stored (items, support) in order."""
    m = c["mining"]
    if m["maxgap"] != 1:
        raise ValueError("the reference mines contiguous sequences only")
    support = substring_support(sessions, m["max_len"])
    minsup = c["dynamic_minsup_start"]
    while True:
        pats = maximal_sequences(support, len(sessions), minsup,
                                 m["min_len"], m["max_len"])
        if len(pats) >= c["min_patterns"] or minsup <= floor:
            break
        minsup = max(floor, minsup * c["minsup_decay"])
    pats = [p for p in pats if p[1] >= c["min_support"]
            and len(p[0]) <= m["max_len"]]
    pats.sort(key=lambda p: len(p[0]) * p[1], reverse=True)
    return pats[:c["metastore_capacity"]]


# -- trees and fetch_progressive --------------------------------------------

class Node:
    __slots__ = ("item", "depth", "children")

    def __init__(self, item: int, depth: int):
        self.item, self.depth, self.children = item, depth, {}

    def below(self, lo: int, hi: int) -> list:
        """Nodes of this subtree at depths lo..hi, level order."""
        out, queue = [], deque([self])
        while queue:
            nd = queue.popleft()
            if nd.depth > hi:
                break
            if nd.depth >= lo:
                out.append(nd)
            queue.extend(nd.children.values())
        return out


def build_trees(patterns: list) -> dict:
    """{root item: (root node, max depth)} in first-insertion order."""
    trees: dict = {}
    for items, _ in patterns:
        if len(items) < 2:
            continue
        root, depth = trees.get(items[0], (None, 0))
        if root is None:
            root = Node(items[0], 0)
        node = root
        for it in items[1:]:
            nxt = node.children.get(it)
            if nxt is None:
                nxt = node.children[it] = Node(it, node.depth + 1)
            node = nxt
        trees[items[0]] = (root, max(depth, len(items) - 1))
    return trees


class Progressive:
    """fetch_progressive with at most ``max_contexts`` live contexts; a
    context is [root, max depth, node, fetched depth, last op]."""

    def __init__(self, depth: int, max_contexts: int):
        self.depth = depth
        self.max_contexts = max_contexts
        self.trees: dict = {}
        self.ctx: list = []
        self.op = 0

    def replace(self, trees: dict) -> None:
        self.trees = trees
        self.ctx = []

    def on_request(self, item: int) -> list:
        self.op += 1
        wave: list = []
        live = []
        for c in self.ctx:
            root, maxd, node, fetched, _ = c
            child = node.children.get(item)
            if child is None:
                if node is root and root.item == item:
                    c[4] = self.op
                    live.append(c)
                continue
            c[2], c[4] = child, self.op
            target = child.depth + self.depth
            if target > fetched:
                wave += child.below(fetched + 1, target)
                c[3] = target
            if child.depth < maxd and child.children:
                live.append(c)
        self.ctx = live
        tree = self.trees.get(item)
        if tree is not None:
            root, maxd = tree
            dup = next((c for c in self.ctx if c[0] is root and c[2] is root),
                       None)
            if dup is not None:
                dup[4] = self.op
            elif maxd > 0:
                fetched = min(self.depth, maxd)
                wave += root.below(1, fetched)
                if len(self.ctx) >= self.max_contexts:
                    ev = min(range(len(self.ctx)), key=lambda i: self.ctx[i][4])
                    self.ctx.pop(ev)
                self.ctx.append([root, maxd, root, fetched, self.op])
        out, seen = [], set()
        for nd in wave:
            if nd.item not in seen:
                seen.add(nd.item)
                out.append(nd.item)
        return out


class Log:
    """Access log with first-seen item numbering."""

    def __init__(self, gap: float):
        self.gap = gap
        self.ids: dict = {}
        self.keys: list = []
        self.sessions: list = []
        self.open: list = []
        self.last = None

    def id(self, key) -> int:
        i = self.ids.get(key)
        if i is None:
            i = self.ids[key] = len(self.keys)
            self.keys.append(key)
        return i

    def record(self, t: float, key) -> None:
        if self.last is not None and t - self.last > self.gap:
            self.flush()
        self.open.append(key)
        self.last = t

    def flush(self) -> None:
        if self.open:
            self.sessions.append(tuple(self.id(k) for k in self.open))
            self.open = []


def generalize(key):
    return (key[0], None, key[2])


class Client:
    """The reference client.  Records what the comparison reads: each
    decision's targets as keys, each round's patterns as keys."""

    def __init__(self, data: dict, config: dict, seed: int,
                 control: str | None = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        c = dict(config["client"], **config["semantics"])
        self.c = c
        self.control = control
        self.now = 0.0
        self.store = Store(data, config["store"]["latency"], seed,
                           c["demand_lanes"])
        self.cache = Cache(c["cache_bytes"], c["preemptive_frac"])
        self.log = Log(c["session_gap"])
        self.col_log = Log(c["session_gap"])
        self.engine = Progressive(c["heuristic"]["progressive_depth"],
                                  c["max_contexts"])
        self.col_engine = Progressive(c["column_progressive_depth"],
                                      c["max_contexts"])
        self.targets: list = []        # main decisions, as key lists
        self.col_targets: list = []
        self.rounds: list = []         # per mining round: (main, col)
        self.every = c.get("online_mine_every")
        if self.every is not None and "online_tail_sessions" not in c:
            raise ValueError("a configuration that mines online states "
                             "online_tail_sessions")
        self.reads_since_round = 0

    def read(self, key):
        c, now = self.c, self.now
        self.log.record(now, key)
        self.log.id(key)
        col = c["column_mining"]
        if col:
            self.col_log.record(now, generalize(key))
        hit = self.cache.lookup(key, now)
        if hit is not None and hit[1] <= c["prefetch_wait_cap"]:
            value = hit[0]
            latency = c["cache_overhead_s"] + hit[1]
        else:
            value, done = self.store.demand_get(key, now)
            latency = (done - now) + c["cache_overhead_s"]
            if value is not None:
                self.cache.put_demand(key, value)
        if c["prefetch_enabled"]:
            self._prefetch(key, now)
            if col:
                self._prefetch_columns(key, now)
        if self.every is not None:
            self.reads_since_round += 1
            if self.reads_since_round >= self.every:
                self.reads_since_round = 0
                self.mine_now()
        self.now += latency
        return value, latency

    def write(self, key, value: bytes) -> None:
        self.log.id(key)
        self.store.put(key, value, self.now)
        if self.control != "no_write_through":
            self.cache.write(key, value)
        self.now += self.c["cache_overhead_s"]

    def end_session(self) -> None:
        self.log.flush()
        self.col_log.flush()

    def _fetch(self, batches: list, now: float) -> None:
        for batch in batches:
            vals, done = self.store.background_get(batch, now,
                                                   self.c["backlog_cap"])
            for k, v in zip(batch, vals):
                if v is not None:
                    self.cache.put_prefetch(k, v, done)

    def _prefetch(self, key, now: float) -> None:
        if self.store.backlog(now) > self.c["backlog_cap"]:
            return
        keys = [self.log.keys[i]
                for i in self.engine.on_request(self.log.id(key))]
        self.targets.append(keys)
        wanted = [k for k in keys if not self.cache.contains(k)]
        if not wanted:
            return
        b = self.c["prefetch_batch"]
        self._fetch([wanted[:1]] + [wanted[i:i + b]
                                    for i in range(1, len(wanted), b)], now)

    def _prefetch_columns(self, key, now: float) -> None:
        gid = self.col_log.id(generalize(key))
        keys = [self.col_log.keys[i] for i in self.col_engine.on_request(gid)]
        self.col_targets.append(keys)
        if not keys or self.store.backlog(now) > self.c["backlog_cap"]:
            return
        concrete = []
        for table, _, column in keys:
            ck = (table, key[1], column)
            if not self.store.contains(ck):
                continue
            self.log.id(ck)
            if not self.cache.contains(ck):
                concrete.append(ck)
        b = self.c["prefetch_batch"]
        self._fetch([concrete[i:i + b] for i in range(0, len(concrete), b)],
                    now)

    def _sessions(self, log: Log) -> list:
        log.flush()
        s = log.sessions
        if self.every is not None:
            s = s[-self.c["online_tail_sessions"]:]
        if self.control == "sampled_mining":
            # support counted on every other session: half the work
            s = s[::2]
        return s

    def mine_now(self) -> None:
        c = self.c
        col = []
        if c["column_mining"]:
            s = self._sessions(self.col_log)
            floor = max(c["dynamic_minsup_floor"], 2.0 / max(len(s), 1))
            pats = mine(s, c, floor)
            self.col_engine.replace(build_trees(pats))
            col = [(tuple(self.col_log.keys[i] for i in p), n)
                   for p, n in pats]
        s = self._sessions(self.log)
        pats = mine(s, c, c["dynamic_minsup_floor"])
        self.engine.replace(build_trees(pats))
        self.rounds.append(([(tuple(self.log.keys[i] for i in p), n)
                             for p, n in pats], col))

    @property
    def stats(self) -> dict:
        return dict(self.cache.stats)
