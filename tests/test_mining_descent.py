"""The dynamic-minsup descent's one frontier pass: every rung at or above
the pass is read from it exactly as a fresh ``mine`` at that rung
answers, the descent's result does not depend on the rung it starts its
pass at, and an online client's rounds equal those of a client that
keeps no previous rung."""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    MiningParams,
    PalpatineClient,
    PalpatineConfig,
    SequenceDatabase,
    SimulatedDKVStore,
    VerticalBitmaps,
    mine,
    mine_dynamic_minsup,
)
from repro.core import obs
from repro.core.backstore import LatencyModel
from repro.core.mining import (
    _dfs_mine,
    _emit,
    _frontier_levels,
    _ladder,
    _read_rung,
    dynamic_floor_count,
)

pytestmark = pytest.mark.tier1

MIN_LEN, MAX_LEN = 3, 6
START, FLOOR, DECAY = 0.6, 0.02, 0.5
LADDER = _ladder(START, FLOOR, DECAY)


def random_db(seed: int, n_sessions: int = 90, n_items: int = 14):
    """Random sessions over few items, some shorter than ``MIN_LEN``, with
    planted sequences of ``MAX_LEN`` and of ``MAX_LEN + 2`` items (the
    longer ones give frequent sequences that stop at ``MAX_LEN``)."""
    rng = np.random.default_rng(seed)
    planted = [tuple(int(x) for x in rng.choice(n_items, size=n,
                                                 replace=False))
               for n in (MAX_LEN, MAX_LEN + 2, MIN_LEN)]
    sessions = []
    for _ in range(n_sessions):
        s = [int(x) for x in rng.integers(
            0, n_items, size=int(rng.integers(1, MAX_LEN + 5)))]
        if rng.random() < 0.6:
            p = planted[int(rng.integers(len(planted)))]
            at = int(rng.integers(0, len(s) + 1))
            s[at:at] = p
        sessions.append(s)
    return SequenceDatabase.from_sessions(sessions)


def params(maxgap=1, **kw) -> MiningParams:
    return MiningParams(min_len=MIN_LEN, max_len=MAX_LEN, maxgap=maxgap, **kw)


def fresh_descent(db, p, algo, min_patterns):
    """The descent as a plain loop of fresh ``mine`` calls, one a rung."""
    minsup = START
    while True:
        pats = mine(db, dataclasses.replace(p, minsup=minsup), algo)
        if len(pats) >= min_patterns or minsup <= FLOOR:
            return pats, minsup
        minsup = max(FLOOR, minsup * DECAY)


@pytest.mark.parametrize("algo", ["vmsp", "spam", "gsp"])
@pytest.mark.parametrize("maxgap", [1, 2, None])
@pytest.mark.parametrize("seed", range(4))
def test_one_pass_answers_every_rung_above_it(seed, maxgap, algo):
    db = random_db(seed)
    p = params(maxgap)
    vb = VerticalBitmaps(db, dynamic_floor_count(p, len(db), START, FLOOR))
    # with no gap limit, the maximal filter at a support of a few sessions
    # takes tens of seconds: those rungs stop at a tenth
    ladder = [m for m in LADDER if maxgap is not None or m >= 0.1]
    for lo, m0 in enumerate(ladder):
        at = dataclasses.replace(p, minsup=m0)
        levels = _frontier_levels(vb, at, at.minsup_count(len(db)))
        for m in ladder[:lo + 1]:
            rung = dataclasses.replace(p, minsup=m)
            assert _read_rung(levels, rung, len(db), algo) == mine(
                db, rung, algo), (m0, m)
            # the walk's emission before any filter is the DFS walker's,
            # level by level and by item ids within a level
            msc = rung.minsup_count(len(db))
            maximal = algo == "vmsp"
            assert _emit(levels, rung, msc, maximal) == sorted(
                _dfs_mine(db, rung, maximal),
                key=lambda q: (len(q), q.items)), (m0, m)


def _settled(db, p, min_patterns):
    return fresh_descent(db, p, "vmsp", min_patterns)[1]


#: where the previous rung lies against the one the data needs: a rung
#: of the ladder (above, equal, below) or a minsup between rungs, below
#: the floor, or above the start
LAST = {
    "above": lambda need: LADDER[max(LADDER.index(need) - 2, 0)],
    "equal": lambda need: need,
    "below": lambda need: LADDER[min(LADDER.index(need) + 2,
                                     len(LADDER) - 1)],
    "between": lambda need: need * 0.75,
    "under_floor": lambda need: FLOOR / 4,
    "over_start": lambda need: 1.0,
}


@pytest.mark.parametrize("where", sorted(LAST))
@pytest.mark.parametrize("min_patterns", [1, 6, 10_000])
@pytest.mark.parametrize("seed", range(3))
def test_the_descent_does_not_depend_on_its_last_rung(seed, min_patterns,
                                                      where):
    db = random_db(seed)
    p = params()
    need = _settled(db, p, min_patterns)
    want = fresh_descent(db, p, "vmsp", min_patterns)
    for last in (None, LAST[where](need)):
        got = mine_dynamic_minsup(db, p, start=START, floor=FLOOR,
                                  decay=DECAY, min_patterns=min_patterns,
                                  last=last)
        assert got == want, last


def _passes(db, p, **kw) -> int:
    prof = obs.HostProfile()
    old = obs.set_host_profile(prof)
    try:
        mine_dynamic_minsup(db, p, start=START, floor=FLOOR, decay=DECAY,
                            **kw)
    finally:
        obs.set_host_profile(old)
    return prof.counters.get(obs.METRIC_MINE_PASSES, 0)


@pytest.mark.parametrize("min_patterns", [1, 6, 10_000])
def test_a_descent_makes_one_pass_from_the_rung_it_settled_on(min_patterns):
    db = random_db(7)
    p = params()
    need = _settled(db, p, min_patterns)
    visited = LADDER.index(need) + 1
    assert _passes(db, p, min_patterns=min_patterns) == visited
    assert _passes(db, p, min_patterns=min_patterns, last=need) == 1
    # a pass below the rung needed still reads it: one pass
    assert _passes(db, p, min_patterns=min_patterns, last=FLOOR) == 1
    # a pass above it mines on below, one pass a rung
    above = LADDER[0]
    assert _passes(db, p, min_patterns=min_patterns, last=above) == visited


@pytest.mark.parametrize("last", [None, FLOOR, LADDER[1]])
def test_a_pass_that_spills_to_the_dfs_walker_gives_the_same_descent(last):
    """A byte cap below one prefix's join sends every pass to the DFS
    walker, whose emission order the frontier's levels cannot give: the
    descent then mines each rung in turn."""
    db = random_db(5)
    p = params()
    capped = dataclasses.replace(p, frontier_budget=1)
    got = mine_dynamic_minsup(db, capped, start=START, floor=FLOOR,
                              decay=DECAY, min_patterns=6, last=last)
    fresh = fresh_descent(db, capped, "vmsp", 6)
    assert got == fresh
    assert {(q.items, q.support) for q in got[0]} == {
        (q.items, q.support) for q in fresh_descent(db, p, "vmsp", 6)[0]}


# ---------------------------------------------------------------------------
# An online client against one that keeps no previous rung
# ---------------------------------------------------------------------------


class _Forgetful(dict):
    """A rung store that keeps nothing: every round starts from scratch."""

    def __setitem__(self, key, value):
        pass


def _online_client(forget: bool) -> PalpatineClient:
    store = SimulatedDKVStore(LatencyModel(jitter_sigma=0.0, stall_frac=0.0))
    store.load((i, bytes([i % 251]) * 8) for i in range(400))
    client = PalpatineClient(store, PalpatineConfig(
        mining=MiningParams(min_len=MIN_LEN, max_len=MAX_LEN, maxgap=1),
        column_mining=False, online_mine_every=120,
        online_tail_sessions=60, metastore_capacity=200, min_patterns=8,
        dynamic_minsup_start=0.5, dynamic_minsup_floor=0.02))
    if forget:
        client._settled = _Forgetful()
    return client


def _drifting_sessions(rng, n):
    bases = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10], [20, 21, 22, 23, 24, 25, 26]]
    out = []
    for i in range(n):
        shift = 40 * (i // 60)               # the hot set moves
        if rng.random() < 0.7:
            out.append([x + shift for x in bases[int(rng.integers(3))]])
        else:
            out.append([int(x) for x in rng.integers(
                30, 400, size=int(rng.integers(1, 8)))])
    return out


def test_an_online_clients_rounds_match_a_client_without_a_last_rung():
    sessions = _drifting_sessions(np.random.default_rng(11), 240)
    rounds, passes = {}, {}
    for forget in (False, True):
        client = _online_client(forget)
        prof = obs.HostProfile()
        old = obs.set_host_profile(prof)
        seen = []
        mine_now = client.mine_now

        def recorded(mine_now=mine_now, client=client, seen=seen):
            n = mine_now()
            seen.append([(p.items, p.support) for p in client.metastore])
            return n
        client.mine_now = recorded
        try:
            for s in sessions:
                for key in s:
                    client.read(int(key))
                client.end_session()
        finally:
            obs.set_host_profile(old)
        rounds[forget] = seen
        passes[forget] = prof.counters[obs.METRIC_MINE_PASSES]
    assert len(rounds[False]) >= 3 and rounds[False][-1]
    assert rounds[False] == rounds[True]
    # the client that keeps its rung makes fewer passes, and so reads
    # some rungs from one pass: the same rounds all the same
    assert passes[False] < passes[True]
