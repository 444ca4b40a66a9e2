"""Cluster quickstart: a replicated sharded DKV store, concurrent Palpatine
tenants, and gossiped patterns warming a cold client.

Three tenants browse a social-network store sharded over 4 storage nodes
with 2-way replication.  Tenant 0 and 1 see lots of traffic and mine
frequent sequences; tenant 2 is brand new.  After one pattern-exchange
round, the cold tenant prefetches along sequences it has *never observed* —
the paper's metastore (§3.2) scaled out across clients.  The finale kills a
storage node outright: every key stays readable from its surviving replica,
and a scatter-gather batch read overlaps its in-flight fetches across the
remaining nodes.

The membership walkthrough then exercises the elastic ring: a fifth node
joins under load (only its owed key ranges stream over, ~1/(N+1) of the
placements; caches take targeted invalidations, not a flush), and a node
crashes while writes land — hinted handoffs queue for it and drain on
rejoin, with read-repair as the backstop, converging every replica to
byte-identical state.

The finale never calls ``set_down`` at all: a node *crashes* and the
cluster must notice by itself.  The first reads pay the ack timeout and
feed the phi-accrual failure detector; the suspicion verdict lands within
two missed acks; a write owed to the suspect hands off to the next ring
successor (sloppy quorum, stamped with the intended owner); and when the
process comes back, probe acks clear the verdict and the hint hands the
write back — byte-identical convergence, end to end emergent.

The partition walkthrough closes the loop on causality: a seeded
:class:`ChaosSchedule` splits two coordinator front-ends onto opposite
sides of a symmetric partition, both write the *same key* inside the
window (dotted version vectors mint concurrent dots — siblings — where
the old int counter would silently collide), verdict gossip is blocked
mid-partition and converges after, and once the world heals two
``reconcile`` sweeps drain the hints, merge the siblings LWW-by-dot, and
leave every replica byte-identical with both writes' dots in the
surviving causal history.  Run:

    PYTHONPATH=src python examples/cluster_quickstart.py
"""

import numpy as np

from repro.core import (
    ChaosEngine, ChaosSchedule, ClusterClient, ClusterConfig, Fault,
    HeuristicConfig, MiningParams, PalpatineConfig, ShardedDKVStore,
    VerdictExchange,
)

COLS = ("profile", "photo", "friends", "feed")


def sessions(seed, n, hot_users=10):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        u = int(rng.integers(0, hot_users))
        if rng.random() < 0.8:
            yield [("users", f"u{u}", c) for c in COLS]
        else:
            yield [("users", f"u{int(rng.integers(0, 2000))}", "profile")]


def main():
    store = ShardedDKVStore(n_shards=4, replication=2,
                            failure_detection=True, sloppy_quorum=True)
    store.load(((("users", f"u{i}", col), f"{col}-of-u{i}".encode())
                for i in range(2_000) for col in COLS))
    print("containers per storage node (R=2, each key on 2 nodes):",
          [len(s.data) for s in store.shards])

    cluster = ClusterClient(store, ClusterConfig(
        n_clients=3,
        exchange_every_ops=None,          # gossip explicitly below
        palpatine=PalpatineConfig(
            heuristic=HeuristicConfig("fetch_progressive"),
            cache_bytes=64 * 1024,
            mining=MiningParams(minsup=0.05, min_len=3, max_len=10, maxgap=1),
        )))
    warm0, warm1, cold = cluster.tenants

    # -- stage 1: tenants 0 and 1 browse; tenant 2 stays idle -------------
    cluster.run([sessions(0, 400), sessions(1, 400), iter(())])
    print(f"mined {cluster.mine_all()} patterns across warm tenants")

    # -- gossip: cold tenant pulls the cluster's patterns -----------------
    cluster.exchange_patterns()
    print(f"exchange holds {len(cluster.exchange)} patterns; "
          f"cold tenant now indexes {len(cold.engine.index.trees)} trees")

    # -- stage 2: the cold tenant's first-ever session --------------------
    cluster.reset_stats()
    # the new tenant connects NOW: its virtual clock joins the store's
    # frontier (channels are shared, so clocks must not lag)
    cold.clock.sync(store.frontier())
    u, think = 3, 2e-3
    lats = []
    for col in COLS[:3]:
        v, lat = cold.read(("users", f"u{u}", col))
        lats.append(lat)
        cold.clock.advance(think)
    print(f"cold tenant reads: {lats[0]*1e6:7.1f} us (demand miss), "
          f"{lats[1]*1e6:7.1f} us, {lats[2]*1e6:7.1f} us (prefetched)")
    s = cold.stats
    print(f"cold tenant: {s.prefetch_hits} prefetch hits "
          f"without ever mining a pattern itself")

    # -- finale: lose a storage node, keep serving ------------------------
    store.set_down(0)
    batch = [("users", f"u{u}", c) for u in (1500, 1600, 1700) for c in COLS]
    values, batch_lat = warm0.read_many(batch)
    assert all(v is not None for v in values)
    serial = sum(warm0.read(k)[1] for k in
                 [("users", f"u{u}", c) for u in (1501, 1601, 1701)
                  for c in COLS])
    print(f"node 0 down: {len(batch)}-key scatter-gather served from "
          f"replicas in {batch_lat*1e6:.0f} us; the same dozen cold reads "
          f"issued one-by-one take {serial*1e6:.0f} us")
    store.set_down(0, False)

    # -- scale out: a fifth node joins under load -------------------------
    report = store.add_node(now=store.frontier())
    frac = report.placement_fraction
    print(f"scale-out: node 4 joined, {report.keys_streamed} keys "
          f"({report.bytes_streamed / 1e3:.0f} KB) streamed in "
          f"{(report.done_at - report.started_at) * 1e3:.1f} virtual ms — "
          f"{frac:.0%} of placements moved (~1/(N+1) = "
          f"{1 / store.n_shards:.0%}), zero keys lost")
    print("containers per node after the move:",
          [len(s.data) for s in store.shards])
    # tenants kept serving: their caches grew a partition and dropped only
    # the remapped keys (targeted invalidation, not a flush)
    v, lat = warm0.read(("users", "u3", "profile"))
    assert v is not None
    print(f"tenant cache now spans {len(warm0.cache.spaces)} partitions; "
          f"post-scale read: {lat*1e6:.1f} us")

    # -- crash + rejoin: hinted handoff converges the stragglers ----------
    key = ("users", "u7", "feed")
    crashed = store.replicas_of(key)[0]
    store.set_down(crashed)
    warm1.clock.sync(store.frontier())
    warm1.write(key, b"fresh-feed-for-u7")
    print(f"node {crashed} crashed; write landed on the surviving replica, "
          f"{store.hints.pending(crashed)} hinted handoff queued")
    replayed = store.set_down(crashed, False)      # rejoin: hints drain
    copies = {store.shards[s].data[key] for s in store.replicas_of(key)}
    assert copies == {b"fresh-feed-for-u7"}
    print(f"rejoin: {replayed} hint replayed on the write channel — all "
          f"replicas byte-identical (read-repair would catch lost hints: "
          f"{store.read_repairs} repairs so far)")

    # -- emergent failure detection: this time nobody calls set_down ------
    det = store.detector
    key = ("users", "u9", "feed")
    victim = store.replicas_of(key)[0]
    store.shards[victim].crash()                   # the process just dies
    t = store.frontier()
    # the write scatters to both replicas; the victim's ack never comes —
    # one timeout window later the coordinator hands its copy to the next
    # ring successor and stamps the hint with the intended owner
    store.put(key, b"sloppy-feed-for-u9", now=t)
    holder = store.hints.get_hint(victim, key)[2]
    print(f"\nnode {victim} crashed (undeclared): the write's ack expired "
          f"after {store.rpc_timeout * 1e3:.0f} virtual ms "
          f"(phi={det.phi(victim):.0f}), copy handed to ring successor "
          f"{holder} (sloppy quorum) with a hint for owner {victim}")
    on_victim = [("users", f"u{u}", c) for u in range(40) for c in COLS
                 if victim in store.replicas_of(("users", f"u{u}", c))]
    ops = 1
    while not det.suspected(victim):
        store.put(on_victim[ops % len(on_victim)], b"w" * 16, now=t + ops)
        ops += 1
    print(f"suspicion verdict after {ops} writes' missed acks (phi-accrual "
          f"from traffic alone) — everything now routes around node "
          f"{victim} at full speed; {store.hints.pending(victim)} hints "
          f"pending, {store.sloppy_writes} sloppy handoffs so far")

    # the process comes back; probes notice, the verdict clears, the
    # hint hands the write back, the stray holder copy is pruned
    store.shards[victim].recover()
    ops = 0
    while det.suspected(victim) and ops < 400:
        store.get_async(on_victim[ops % len(on_victim)], now=t + 100.0 + ops)
        ops += 1
    assert not det.suspected(victim)
    copies = {store.shards[s].data[key] for s in store.replicas_of(key)}
    assert copies == {b"sloppy-feed-for-u9"}
    assert key not in store.shards[holder].data
    print(f"recovery: {store.probes} probes total, verdict cleared after "
          f"~{ops} ops, hint handed back — replicas byte-identical, "
          f"holder pruned; detector saw {det.timeouts} missed acks, "
          f"{det.suspicions} suspicion, {det.clears} clear; "
          f"set_down calls: 0 in this whole section")

    # -- partition -> sibling writes -> heal -> converge ------------------
    # A fresh two-node ring with TWO coordinator front-ends sharing it.
    # A seeded fault schedule puts each coordinator alone with one
    # storage node for 0.4 virtual seconds; both write the same key
    # inside the window.
    dkv = ShardedDKVStore(n_shards=2, replication=2, write_mode="all",
                          failure_detection=True, sloppy_quorum=True)
    c0, c1 = dkv, dkv.attach_coordinator()
    dkv.enable_chaos(ChaosEngine(ChaosSchedule(seed=0, horizon=1.0, faults=[
        Fault.partition(0.1, 0.5, (c0.coord_name, 0), (c1.coord_name, 1)),
    ])))
    k = ("users", "u0", "bio")
    c0.put(k, b"written-on-the-c0-side", 0.2)   # lands node 0, hints node 1
    c1.put(k, b"written-on-the-c1-side", 0.3)   # lands node 1, hints node 0
    va, vb = dkv.shards[0].versions[k], dkv.shards[1].versions[k]
    print(f"\npartition [0.1, 0.5): both sides accepted the write — "
          f"node0 holds dot {va.dot}, node1 holds dot {vb.dot} "
          f"(concurrent siblings; an int counter would call these equal)")
    # gossip cannot cross the cut: each coordinator keeps its own verdicts
    ex = VerdictExchange()
    ex.gossip([c0, c1], 0.35)
    print(f"verdict gossip mid-partition: {ex.blocked} exchange blocked")
    # past the horizon the world heals: reconcile drains the hints both
    # ways, the drains surface the siblings, and the merge keeps the
    # LWW-by-dot winner while folding every dot into the merged clock
    for t in (0.8, 0.9):
        c0.reconcile(t)
        c1.reconcile(t)
    ex.gossip([c0, c1], 1.0)
    copies = {dkv.shards[s].data[k] for s in (0, 1)}
    merged = dkv.shards[0].versions[k]
    assert len(copies) == 1
    assert merged.seen(1, 0) and merged.seen(1, 1)
    print(f"healed: replicas byte-identical ({copies.pop()!r}), "
          f"{sum(c.sibling_merges for c in (c0, c1))} sibling merge(s); "
          f"the survivor's clock still carries BOTH dots "
          f"({merged.clock}) — no acked write was forgotten, and the "
          f"post-heal gossip round ran {ex.rounds - 1} -> {ex.rounds} "
          f"with 0 new blocks")


if __name__ == "__main__":
    main()
