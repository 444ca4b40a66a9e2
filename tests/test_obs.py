"""Palpascope observability layer (repro.core.obs): percentile/histogram
regression pins, the NULL_TRACER no-op contract, span lifecycle + trace
causality invariants over the real cluster stack (every span closes,
child intervals nest, chaos-dropped RPC spans are marked and have no
service child, same-seed sampling selects identical traces), the
metrics registry's one-name-one-type rule, prefetch-attribution
conservation (the acceptance pin: per-pattern hits sum exactly to the
cache's prefetch-hit counter), and the tools/palpascope CLI renderers.
"""

import json

import pytest

from repro.core import (
    ChaosEngine,
    ChaosSchedule,
    ClusterClient,
    ClusterConfig,
    Fault,
    LatencyModel,
    MiningParams,
    PalpatineClient,
    PalpatineConfig,
    ShardedDKVStore,
    SimulatedDKVStore,
)
from repro.core import obs
from repro.core.obs import (
    EVENT_RETRY,
    METRIC_OPS,
    METRIC_READ_LATENCY,
    METRIC_STALE_READS,
    METRIC_WALK_D2H_BYTES,
    METRIC_WALK_D2H_COPIES,
    METRIC_WALK_H2D_BYTES,
    METRIC_WALK_H2D_COPIES,
    NULL_HOST_PROFILE,
    NULL_SPAN,
    NULL_TRACER,
    REGISTERED_NAMES,
    SPAN_HOST_DECIDE,
    SPAN_HOST_WALK,
    SPAN_HOST_WALK_DISPATCH,
    SPAN_HOST_WALK_READBACK,
    SPAN_HOST_WALK_UNPACK,
    SPAN_HOST_WALK_UPLOAD,
    SPAN_HOST_WALK_WAIT,
    SPAN_OP,
    SPAN_ROUTE,
    SPAN_RPC,
    SPAN_SERVICE,
    HostProfile,
    Histogram,
    MetricsRegistry,
    Tracer,
    critical_path,
    latency_percentiles,
    percentile,
    set_host_profile,
    span_kind_breakdown,
)

pytestmark = pytest.mark.tier1

V = b"v" * 64


def flat_latency(i: int) -> LatencyModel:
    return LatencyModel(jitter_sigma=0.0, stall_frac=0.0, seed=i)


def mk_cluster(n=4, replication=2, **kw):
    kw.setdefault("failure_detection", True)
    return ShardedDKVStore(
        n_shards=n, latencies=[flat_latency(i) for i in range(n)],
        replication=replication, **kw)


# ---------------------------------------------------------------------------
# Percentiles + histograms (the centralized definition every bench shares)
# ---------------------------------------------------------------------------


class TestPercentiles:
    def test_nearest_rank_pins_on_known_sample(self):
        """The regression pin: one canonical nearest-rank definition
        (bench_cluster and bench_overhead used to disagree)."""
        sample = [0.010, 0.012, 0.015, 0.020, 0.050,
                  0.100, 0.500, 1.000, 2.000, 10.000]
        assert latency_percentiles(sample) == {
            "p50": 0.050, "p99": 10.000, "p999": 10.000}
        ramp = [float(i) for i in range(1, 101)]
        assert percentile(ramp, 50.0) == 50.0
        assert percentile(ramp, 99.0) == 99.0
        assert percentile(ramp, 99.9) == 100.0
        assert percentile(ramp, 0.0) == 1.0
        assert percentile(ramp, 100.0) == 100.0

    def test_edge_cases(self):
        assert percentile([], 50.0) == 0.0
        assert latency_percentiles([]) == {"p50": 0.0, "p99": 0.0,
                                           "p999": 0.0}
        with pytest.raises(ValueError):
            percentile([1.0], 101.0)
        with pytest.raises(ValueError):
            percentile([1.0], -0.1)

    def test_histogram_bucketed_percentiles_bound_exact(self):
        """Bucketed percentiles return the containing bucket's upper
        bound: >= the exact nearest-rank value and within one bucket
        ratio (1.2x) of it — deterministic and mergeable, never an
        interpolated value two runs could disagree on."""
        h = Histogram(METRIC_READ_LATENCY)
        sample = [i * 1e-4 for i in range(1, 1001)]   # 0.1 ms .. 100 ms
        h.record_many(sample)
        exact = latency_percentiles(sample)
        for q, key in ((50.0, "p50"), (99.0, "p99"), (99.9, "p999")):
            bucketed = h.percentile(q)
            assert exact[key] <= bucketed <= exact[key] * 1.2 + 1e-12
        snap = h.snapshot()
        assert snap["count"] == 1000
        assert snap["max"] == pytest.approx(0.1)
        assert snap["mean"] == pytest.approx(sum(sample) / len(sample))
        assert h.percentile(50.0) == snap["p50"]

    def test_histogram_overflow_and_empty(self):
        h = Histogram(METRIC_READ_LATENCY, bounds=[1.0, 2.0])
        assert h.percentile(99.0) == 0.0
        h.record(50.0)                      # overflow bucket
        assert h.percentile(99.0) == 50.0   # reports the observed max
        with pytest.raises(ValueError):
            Histogram(METRIC_READ_LATENCY, bounds=[2.0, 1.0])


class TestMetricsRegistry:
    def test_typed_get_or_create(self):
        m = MetricsRegistry()
        c = m.counter(METRIC_OPS)
        c.inc()
        c.inc(2)
        assert m.counter(METRIC_OPS) is c and c.value == 3
        g = m.gauge(METRIC_STALE_READS)
        g.set(4.5)
        h = m.histogram(METRIC_READ_LATENCY)
        h.record(1e-3)
        snap = m.snapshot()
        assert snap[METRIC_OPS] == 3
        assert snap[METRIC_STALE_READS] == 4.5
        assert snap[METRIC_READ_LATENCY]["count"] == 1

    def test_one_name_one_type(self):
        m = MetricsRegistry()
        m.counter(METRIC_OPS)
        with pytest.raises(TypeError):
            m.gauge(METRIC_OPS)
        with pytest.raises(TypeError):
            m.histogram(METRIC_OPS)
        m.reset()
        assert m.gauge(METRIC_OPS).value == 0.0


# ---------------------------------------------------------------------------
# Tracer mechanics
# ---------------------------------------------------------------------------


class TestTracer:
    def test_null_tracer_is_a_complete_noop(self):
        sp = NULL_TRACER.start(SPAN_OP, 0.0)
        assert sp is NULL_SPAN and not sp.live and not NULL_TRACER.active
        assert sp.set(key="k").mark("error").finish(1.0) is sp
        NULL_TRACER.event(EVENT_RETRY, 0.0, node=1)
        NULL_TRACER.end(sp)         # never raises, never accumulates
        assert NULL_TRACER.span(SPAN_RPC, 0.0) is NULL_SPAN

    def test_end_clamps_parent_over_children(self):
        tr = Tracer()
        root = tr.start(SPAN_OP, 0.0)
        child = tr.span(SPAN_RPC, 0.1)
        child.finish(0.5)
        tr.end(child)
        tr.end(root, 0.3)           # background child outlives the t arg
        assert root.end == 0.5 and tr.open_spans == 0
        assert len(tr.traces) == 1

    def test_end_defaults_to_latest_child_end(self):
        tr = Tracer()
        root = tr.start(SPAN_OP, 0.0)
        child = tr.span(SPAN_RPC, 0.1)
        child.finish(0.7)
        tr.end(child)
        tr.end(root)                # exception path: no explicit end time
        assert root.end == 0.7

    def test_same_seed_selects_identical_traces(self):
        def run(seed: int) -> list:
            tr = Tracer(sample=1.0 / 4, seed=seed)
            for i in range(200):
                sp = tr.start(SPAN_OP, float(i))
                if sp.live:
                    sp.set(n=i)
                tr.end(sp, i + 0.5)
            assert tr.roots_seen == 200
            return [t.fields["n"] for t in tr.traces]

        # sampling is a pure function of (seed, root ordinal): reruns
        # of a failing chaos seed capture the traces the breach did
        a, b, c = run(7), run(7), run(8)
        assert a == b and 0 < len(a) < 200
        assert c != a               # a new seed picks a new subset

    def test_capacity_bounds_retained_traces(self):
        tr = Tracer(capacity=8)
        for i in range(50):
            sp = tr.start(SPAN_OP, float(i))
            tr.end(sp, i + 0.5)
        assert len(tr.traces) == 8 and tr.roots_kept == 50
        assert [t.start for t in tr.traces] == [float(i) for i in
                                                range(42, 50)]

    def test_export_roundtrips_through_json(self, tmp_path):
        tr = Tracer()
        sp = tr.start(SPAN_OP, 0.0)
        child = tr.span(SPAN_ROUTE, 0.1)
        tr.event(EVENT_RETRY, 0.2, node=3)
        child.finish(0.4)
        tr.end(child)
        tr.end(sp, 0.5)
        path = tmp_path / "trace.json"
        tr.dump(str(path))
        export = json.loads(path.read_text())
        assert export["roots_kept"] == 1
        trace = export["traces"][0]
        assert trace["kind"] == SPAN_OP and trace["end"] == 0.5
        kinds = [c["kind"] for c in trace["children"]]
        assert kinds == [SPAN_ROUTE]
        assert trace["children"][0]["children"][0]["status"] == "event"
        # the analysis helpers accept exported dicts and live spans alike
        bd = span_kind_breakdown(export["traces"])
        assert bd[SPAN_OP]["count"] == 1      # events excluded
        assert [h["kind"] for h in critical_path(trace)] == [
            SPAN_OP, SPAN_ROUTE]


# ---------------------------------------------------------------------------
# Host profile (host clock, off by default)
# ---------------------------------------------------------------------------


def _fake_clock(step: float = 1.0):
    """A clock that moves ``step`` seconds on every read."""
    now = [0.0]

    def clock() -> float:
        now[0] += step
        return now[0]
    return clock


class TestHostProfile:
    def test_null_profile_records_nothing_and_reads_no_clock(
            self, monkeypatch):
        client = _client_with_mined_chains()     # mining reads the clock

        def no_clock() -> float:
            raise AssertionError("the null profile read the host clock")
        monkeypatch.setattr(obs, "host_clock", no_clock)
        assert obs.host_profile is NULL_HOST_PROFILE
        assert not NULL_HOST_PROFILE.active
        ctx = NULL_HOST_PROFILE.span(SPAN_HOST_DECIDE)
        assert NULL_HOST_PROFILE.span(SPAN_HOST_WALK) is ctx   # prebuilt
        with ctx:
            pass
        assert NULL_HOST_PROFILE.count(METRIC_WALK_H2D_BYTES, 7) is None
        # served reads open palp.decide around every engine call
        for s in ([f"k{j}" for j in range(i, i + 5)]
                  for i in range(0, 50, 5)):
            for k in s:
                client.read(k)
            client.end_session()
        assert client.cache.stats.prefetch_hits > 0
        assert not vars(NULL_HOST_PROFILE)

    def test_obs_imports_without_jax(self):
        import os
        import subprocess
        import sys
        from pathlib import Path

        src = Path(__file__).resolve().parents[1] / "src"
        code = ("import sys; sys.modules['jax'] = None\n"
                "from repro.core import obs\n"
                "assert obs.host_profile is obs.NULL_HOST_PROFILE\n"
                "with obs.NULL_HOST_PROFILE.span(obs.SPAN_HOST_WALK):\n"
                "    pass\n"
                "obs.NULL_HOST_PROFILE.count(obs.METRIC_WALK_D2H_BYTES, 1)\n")
        r = subprocess.run([sys.executable, "-c", code],
                           env=dict(os.environ, PYTHONPATH=str(src)),
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr

    def test_one_switch_returns_the_replaced_profile(self):
        p = HostProfile()
        old = set_host_profile(p)
        try:
            assert old is NULL_HOST_PROFILE and obs.host_profile is p
        finally:
            assert set_host_profile(old) is p
        assert obs.host_profile is NULL_HOST_PROFILE

    def test_names_are_registered_and_prefixed(self):
        names = [SPAN_HOST_DECIDE, SPAN_HOST_WALK, SPAN_HOST_WALK_UPLOAD,
                 SPAN_HOST_WALK_DISPATCH, SPAN_HOST_WALK_WAIT,
                 SPAN_HOST_WALK_READBACK, SPAN_HOST_WALK_UNPACK,
                 METRIC_WALK_H2D_COPIES, METRIC_WALK_H2D_BYTES,
                 METRIC_WALK_D2H_COPIES, METRIC_WALK_D2H_BYTES]
        assert len(set(names)) == len(names)
        assert set(names) <= REGISTERED_NAMES
        assert all(n.startswith("palp.") for n in names)

    def test_active_profile_counts_calls_seconds_and_child_time(self):
        p = HostProfile(clock=_fake_clock())
        assert p.active
        for _ in range(2):
            # clock reads: outer 1 .. 8; upload 2-3, dispatch 4-5,
            # readback 6-7: 7 s outer, 3 x 1 s children
            with p.span(SPAN_HOST_WALK):
                with p.span(SPAN_HOST_WALK_UPLOAD):
                    pass
                with p.span(SPAN_HOST_WALK_DISPATCH):
                    pass
                with p.span(SPAN_HOST_WALK_READBACK):
                    pass
            p.count(METRIC_WALK_D2H_BYTES, 100)
        p.count(METRIC_WALK_D2H_COPIES)
        assert p.calls == {SPAN_HOST_WALK: 2, SPAN_HOST_WALK_UPLOAD: 2,
                           SPAN_HOST_WALK_DISPATCH: 2,
                           SPAN_HOST_WALK_READBACK: 2}
        assert p.seconds[SPAN_HOST_WALK] == 14.0
        assert p.seconds[SPAN_HOST_WALK_UPLOAD] == 2.0
        assert p.child_seconds[SPAN_HOST_WALK] == 6.0
        assert p.child_seconds[SPAN_HOST_WALK_UPLOAD] == 0.0
        assert p.self_seconds(SPAN_HOST_WALK) == 8.0
        assert p.self_seconds(SPAN_HOST_DECIDE) == 0.0      # never opened
        assert p.counters == {METRIC_WALK_D2H_BYTES: 200,
                              METRIC_WALK_D2H_COPIES: 1}

    def test_nesting_books_children_to_their_direct_parent(self):
        p = HostProfile(clock=_fake_clock())
        with p.span(SPAN_HOST_DECIDE):                  # 1 .. 8
            with p.span(SPAN_HOST_WALK):                # 2 .. 7
                with p.span(SPAN_HOST_WALK_WAIT):       # 3 .. 4
                    pass
                with pytest.raises(KeyError):
                    with p.span(SPAN_HOST_WALK_UNPACK):  # 5 .. 6
                        raise KeyError("an exception still closes it")
        assert p.seconds == {SPAN_HOST_DECIDE: 7.0, SPAN_HOST_WALK: 5.0,
                             SPAN_HOST_WALK_WAIT: 1.0,
                             SPAN_HOST_WALK_UNPACK: 1.0}
        assert p.child_seconds[SPAN_HOST_DECIDE] == 5.0  # walk only
        assert p.child_seconds[SPAN_HOST_WALK] == 2.0
        assert p.self_seconds(SPAN_HOST_DECIDE) == 2.0
        assert not p._stack

    def test_served_path_opens_one_decide_span_per_engine_call(self):
        client = _client_with_mined_chains()
        calls = [0]
        on_request = client.engine.on_request

        def counted(iid):
            calls[0] += 1
            return on_request(iid)
        client.engine.on_request = counted
        p = HostProfile(clock=_fake_clock())
        old = set_host_profile(p)
        try:
            for k in ("k0", "k1", "k2"):
                client.read(k)
        finally:
            set_host_profile(old)
        assert calls[0] > 0
        assert p.calls == {SPAN_HOST_DECIDE: calls[0]}


# ---------------------------------------------------------------------------
# Trace causality invariants over the real cluster stack
# ---------------------------------------------------------------------------


def _assert_closed_and_nested(tr: Tracer) -> int:
    """Every span closed; every child interval inside its parent."""
    assert tr.open_spans == 0
    n = 0
    for trace in tr.traces:
        for sp in trace.walk():
            n += 1
            assert sp.end is not None, sp.kind
            assert sp.end >= sp.start, sp.kind
            for c in sp.children or ():
                assert c.start >= sp.start, (sp.kind, c.kind)
                assert c.end is not None and c.end <= sp.end, \
                    (sp.kind, c.kind)
    return n


class TestClusterTracing:
    def test_every_span_closes_and_nests(self):
        store = mk_cluster(n=3)
        store.load([(f"k{i}", V) for i in range(50)])
        tr = Tracer()
        store.enable_tracing(tr)
        t = 0.0
        for i in range(150):
            t += 1e-3
            if i % 3 == 0:
                store.put(f"k{i % 50}", b"w" * 64, t)
            else:
                store.get_async(f"k{i % 50}", t)
        store.reconcile(t + 1.0)
        assert len(tr.traces) >= 100
        assert _assert_closed_and_nested(tr) > 200
        kinds = {sp.kind for trace in tr.traces for sp in trace.walk()}
        assert {SPAN_ROUTE, SPAN_RPC, SPAN_SERVICE} <= kinds

    def test_spans_close_on_unavailability_errors(self):
        """KeyError exits (total outage) still close every span, and the
        route span is marked error."""
        store = mk_cluster(n=2, replication=1)
        store.load([("k", V)])
        tr = Tracer()
        store.enable_tracing(tr)
        eng = ChaosEngine(ChaosSchedule(seed=5, horizon=9.0, faults=[
            Fault.link(0.0, 9.0, ("c0",), (0, 1), drop=1.0)]))
        store.enable_chaos(eng)
        failures = 0
        for i in range(20):
            try:
                store.get_async("k", (i + 1) * 1e-3)
            except KeyError:
                failures += 1
        assert failures > 0
        _assert_closed_and_nested(tr)
        errored = [t for t in tr.traces if t.status == "error"]
        assert errored and all(t.kind == SPAN_ROUTE for t in errored)

    def test_dropped_rpc_marked_with_no_service_child(self):
        """A chaos-dropped demand RPC: status ``dropped``, the eating
        fault named in ``reason``, and conspicuously no service child
        (the node never served it)."""
        store = mk_cluster(n=4)
        store.load([(f"k{i}", V) for i in range(20)])
        tr = Tracer()
        store.enable_tracing(tr)
        eng = ChaosEngine(ChaosSchedule(seed=5, horizon=9.0, faults=[
            Fault.link(0.0, 9.0, ("c0",), (0, 1, 2, 3), drop=1.0)]))
        store.enable_chaos(eng)
        for i in range(20):
            try:
                store.get_async(f"k{i}", (i + 1) * 1e-3)
            except KeyError:
                pass
        dropped = [sp for t in tr.traces for sp in t.walk()
                   if sp.status == "dropped"]
        assert dropped
        for sp in dropped:
            assert sp.kind == SPAN_RPC
            assert sp.fields.get("reason") == "link"
            assert not [c for c in sp.children or ()
                        if c.kind == SPAN_SERVICE]
        _assert_closed_and_nested(tr)
        # healthy traces (pre-chaos load ran untraced; none here) vs
        # delivered RPCs elsewhere carry the service child
        served = [sp for t in tr.traces for sp in t.walk()
                  if sp.kind == SPAN_RPC and sp.status == "ok"]
        for sp in served:
            assert [c for c in sp.children or ()
                    if c.kind == SPAN_SERVICE]


# ---------------------------------------------------------------------------
# Prefetch attribution (the conservation acceptance pin)
# ---------------------------------------------------------------------------


def _client_with_mined_chains() -> PalpatineClient:
    """Ten disjoint 5-key chains, observed then mined: every chain
    becomes a maximal pattern, so replays prefetch-hit deterministically
    out of the tiny (12-entry) cache."""
    store = SimulatedDKVStore(LatencyModel(seed=7))
    store.load([(f"k{i}", V) for i in range(60)])
    client = PalpatineClient(store, PalpatineConfig(
        cache_bytes=64 * 12, preemptive_frac=0.5,
        mining=MiningParams(minsup=0.02, min_len=3, max_len=15, maxgap=1)))
    seqs = [[f"k{j}" for j in range(i, i + 5)] for i in range(0, 50, 5)]
    for _ in range(40):
        for s in seqs:
            for k in s:
                client.read(k)
            client.end_session()
    client.mine_now()
    for _ in range(10):
        for s in seqs:
            for k in s:
                client.read(k)
            client.end_session()
    return client


class TestAttribution:
    def test_per_pattern_hits_sum_to_cache_counter(self):
        client = _client_with_mined_chains()
        stats = client.cache.stats
        attr = client.cache.attr
        assert stats.prefetch_hits > 0
        # the conservation law, exactly: every recorded hit belongs to
        # one pattern row, no hit double-counted or orphaned
        assert attr.total_hits == stats.prefetch_hits
        assert attr.total_prefetched == stats.prefetches
        assert sum(r.hits for r in attr.rows.values()) == \
            stats.prefetch_hits
        # every fetch was engine-attributed (no unattributed row)
        assert all(heur != "unattributed"
                   for (heur, _root, _len) in attr.rows)
        # roots were rewritten to container keys, lengths are depths
        for (_h, root, length), r in attr.rows.items():
            assert isinstance(root, str) and root.startswith("k")
            assert 1 <= length <= 15
            assert r.bytes_hit == r.hits * 64
        deciles = attr.hit_mass_by_length_decile()
        assert sum(deciles) == sum(r.bytes_hit
                                   for r in attr.rows.values())
        top = attr.top_rows(3)
        assert top and top[0]["hits"] >= top[-1]["hits"]
        assert 0.0 <= attr.waste_ratio <= 1.0

    def test_cluster_aggregate_conserves_across_tenants(self):
        store = ShardedDKVStore(
            n_shards=2, latencies=[flat_latency(i) for i in range(2)],
            replication=1)
        store.load([(f"k{i}", V) for i in range(60)])
        cluster = ClusterClient(store, ClusterConfig(
            n_clients=2, palpatine=PalpatineConfig(
                cache_bytes=64 * 12, preemptive_frac=0.5,
                mining=MiningParams(minsup=0.02, min_len=3, max_len=15,
                                    maxgap=1))))
        seqs = [[f"k{j}" for j in range(i, i + 5)]
                for i in range(0, 50, 5)]
        train = [[list(s) for s in seqs] * 20 for _ in range(2)]
        cluster.run(train)
        cluster.mine_all()
        cluster.exchange_patterns()
        cluster.reset_stats()
        cluster.run([[list(s) for s in seqs] * 5 for _ in range(2)])
        agg = cluster.aggregate_stats()
        attr = cluster.aggregate_attribution()
        assert agg.prefetch_hits > 0
        assert attr.total_hits == agg.prefetch_hits
        assert attr.total_prefetched == agg.prefetches
        # reset_stats starts a fresh attribution window too
        cluster.reset_stats()
        assert cluster.aggregate_attribution().total_prefetched == 0


# ---------------------------------------------------------------------------
# tools/palpascope CLI
# ---------------------------------------------------------------------------


class TestPalpascopeCLI:
    def _trace_file(self, tmp_path) -> str:
        tr = Tracer()
        sp = tr.start(SPAN_OP, 0.0)
        child = tr.span(SPAN_ROUTE, 0.05)
        child.finish(0.9)
        tr.end(child)
        tr.end(sp, 1.0)
        sp = tr.start(SPAN_OP, 2.0)
        tr.end(sp, 2.1)
        path = tmp_path / "trace.json"
        tr.dump(str(path))
        return str(path)

    def test_summary_slowest_critical(self, tmp_path, capsys):
        from tools.palpascope import main
        path = self._trace_file(tmp_path)
        assert main(["summary", path]) == 0
        assert main(["slowest", path, "-n", "1"]) == 0
        assert main(["critical", path]) == 0
        out = capsys.readouterr().out
        assert "op" in out and "route" in out
        assert "2 sampled traces" in out
        assert main(["critical", path, "--trace-index", "99"]) == 1

    def test_attr_renders_bench_keys(self, tmp_path, capsys):
        from tools.palpascope import main
        bench = tmp_path / "BENCH.json"
        bench.write_text(json.dumps({
            "attr_hits": 400.0, "attr_waste_ratio": 0.25,
            "attr_top_patterns": [{
                "heuristic": "fetch_progressive", "root": "k0",
                "length": 4, "prefetched": 40, "hits": 38, "unused": 2,
                "bytes_hit": 2432, "mean_confidence": 0.81}],
        }))
        assert main(["attr", str(bench)]) == 0
        out = capsys.readouterr().out
        assert "attr_hits" in out and "fetch_progressive" in out
        empty = tmp_path / "empty.json"
        empty.write_text("{}")
        assert main(["attr", str(empty)]) == 1
