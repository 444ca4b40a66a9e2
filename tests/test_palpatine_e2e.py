"""End-to-end behaviour of the PALPATINE client (paper §4.1 work flow)."""

import numpy as np
import pytest

from repro.core import (
    BaselineClient,
    HeuristicConfig,
    MiningParams,
    PalpatineClient,
    PalpatineConfig,
    SimulatedDKVStore,
)

pytestmark = pytest.mark.tier1


def build_store(n_items=500, value_size=100):
    store = SimulatedDKVStore()
    store.load((("t", f"r{i}", "c"), bytes(value_size)) for i in range(n_items))
    return store


def make_planted(seed=42, n_seqs=20, item_range=400):
    """Many distinct frequent sequences, so the hot set exceeds the cache
    (as in SEQB's 80..10240 frequent-sequence bias)."""
    rng = np.random.default_rng(seed)
    return tuple(
        tuple(rng.choice(item_range, size=int(rng.integers(4, 7)), replace=False))
        for _ in range(n_seqs)
    )


PLANTED = make_planted()


def workload(rng, n_sessions=300, planted=PLANTED):
    """Sessions over container keys with planted frequent sequences."""
    for _ in range(n_sessions):
        if rng.random() < 0.7 and planted:
            base = list(planted[int(rng.integers(0, len(planted)))])
        else:
            base = list(rng.integers(0, 400, size=5))
        yield [("t", f"r{i}", "c") for i in base]


def run_two_stage(heuristic, cache_bytes=8 * 1024, prefetch=True):
    # cache (8 KB = 80 items) deliberately much smaller than the store
    # (500 items) so misses occur and prefetching has work to do
    store = build_store()
    cfg = PalpatineConfig(
        heuristic=HeuristicConfig(heuristic),
        cache_bytes=cache_bytes,
        mining=MiningParams(minsup=0.02, min_len=3, max_len=10, maxgap=1),
        prefetch_enabled=prefetch,
    )
    client = PalpatineClient(store, cfg)
    rng = np.random.default_rng(0)
    # stage 1: observe (no patterns yet)
    for sess in workload(rng, 200):
        for key in sess:
            client.read(key)
        client.logger.flush_session()
    client.mine_now()
    assert len(client.metastore) > 0
    # stage 2: steady state
    s0 = client.stats.accesses
    for sess in workload(np.random.default_rng(1), 200):
        for key in sess:
            v, lat = client.read(key)
            assert v is not None
        client.logger.flush_session()
    return client, s0


@pytest.mark.parametrize("heuristic", ["fetch_all", "fetch_top_n", "fetch_progressive"])
def test_prefetching_lifts_hit_rate(heuristic):
    client, _ = run_two_stage(heuristic)
    st = client.stats
    assert st.prefetches > 0
    assert st.prefetch_hits > 0
    assert st.hit_rate > 0.3  # planted 70% bias -> plenty of hits
    assert st.precision > 0.2


def test_prefetch_disabled_means_no_prefetches():
    client, _ = run_two_stage("fetch_all", prefetch=False)
    assert client.stats.prefetches == 0


def test_palpatine_faster_than_baseline():
    store_b = build_store()
    base = BaselineClient(store_b)
    rng = np.random.default_rng(1)
    for sess in workload(rng, 200):
        for key in sess:
            base.read(key)
    client, _ = run_two_stage("fetch_progressive")
    # mean virtual latency: palpatine steady state must beat baseline
    base_mean = base.clock.now / max(1, store_b.gets)
    pal_ops = client.stats.accesses
    pal_mean = client.clock.now / pal_ops
    assert pal_mean < base_mean


def test_write_then_read_returns_new_value_from_cache():
    store = build_store()
    client = PalpatineClient(store, PalpatineConfig(prefetch_enabled=False))
    key = ("t", "r1", "c")
    client.read(key)
    client.write(key, b"fresh")
    v, lat = client.read(key)
    assert v == b"fresh"
    assert store.data[key] == b"fresh"  # write-through reached the store


def test_external_write_invalidates_cache():
    store = build_store()
    client = PalpatineClient(store, PalpatineConfig(prefetch_enabled=False))
    key = ("t", "r2", "c")
    client.read(key)
    # another client writes directly to the store -> monitor notifies
    store.put(key, b"external", now=0.0)
    v, _ = client.read(key)
    assert v == b"external"


def test_online_mining_adapts_to_new_patterns():
    """Fig 17 mechanism: fresh patterns get mined as the workload shifts."""
    store = build_store()
    cfg = PalpatineConfig(
        heuristic=HeuristicConfig("fetch_all"),
        cache_bytes=64 * 1024,
        mining=MiningParams(minsup=0.05, min_len=3, max_len=10, maxgap=1),
        online_mine_every=600,
        min_patterns=4,
    )
    client = PalpatineClient(store, cfg)
    planted_a = ((20, 21, 22, 23),)
    planted_b = ((40, 41, 42, 43),)
    rng = np.random.default_rng(2)
    for sess in workload(rng, 150, planted=planted_a):
        for key in sess:
            client.read(key)
        client.logger.flush_session()
    runs_after_a = client.mining_runs
    assert runs_after_a >= 1  # online mining fired
    for sess in workload(rng, 150, planted=planted_b):
        for key in sess:
            client.read(key)
        client.logger.flush_session()
    assert client.mining_runs > runs_after_a
    # the new pattern's items are now tree roots or members
    db = client.logger.db
    ids = {db.item_id(("t", f"r{i}", "c")) for i in (40, 41, 42)}
    in_trees = set()
    for tree in client.engine.index.trees.values():
        for node in tree.root.level_order():
            in_trees.add(node.item)
    assert ids & in_trees


def test_mining_wall_time_covers_the_whole_round(monkeypatch):
    """A round's host time runs from ``mine_now``'s entry, column round
    included, to the new trees' install; ``rebuild_wall_time`` is the
    install of both metastores.  A fake clock that only mining and tree
    building move makes both exact."""
    from repro.core import PTreeIndex, obs
    from repro.core import palpatine as pal

    now = [0.0]
    monkeypatch.setattr(obs, "host_clock", lambda: now[0])
    mined, built = [], []

    def timed(fn, cost, log):
        def wrapped(*a, **kw):
            now[0] += cost
            log.append(cost)
            return fn(*a, **kw)
        return wrapped

    client = PalpatineClient(build_store(), PalpatineConfig(
        mining=MiningParams(minsup=0.02, min_len=3, max_len=10, maxgap=1),
        column_mining=True))
    for sess in workload(np.random.default_rng(3), 120):
        for key in sess:
            client.read(key)
        client.end_session()
    monkeypatch.setattr(pal, "mine_dynamic_minsup",
                        timed(pal.mine_dynamic_minsup, 10.0, mined))
    monkeypatch.setattr(PTreeIndex, "build",
                        staticmethod(timed(PTreeIndex.build, 1.0, built)))
    for _ in range(2):
        client.mine_now()
    assert client.col_metastore is not None and len(client.metastore) > 0
    assert len(mined) == 4 and len(built) == 4    # column + main, twice
    assert client.mining_runs == 2
    assert client.mining_wall_time == sum(mined) + sum(built) == 44.0
    assert client.rebuild_wall_time == sum(built) == 4.0
    assert client.rebuild_wall_time <= client.mining_wall_time
