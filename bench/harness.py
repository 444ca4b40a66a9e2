"""One run of one cell: set-up, the measured window, the per-layer
readers, and the comparison with the plain reference.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``configs/<config>.json`` holds the
deployment, ``traffic/<mix>.json`` the mix and the generator module that
reads it (``traffic/<generator>.py``), and ``metrics/<metric>.py`` each
per-layer metric.  The program under test is driven only through
``PalpatineClient`` and the module functions its readers wrap.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import itertools
import json
import math
import resource
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: the path selectors the program may still have, set to their device
#: value where ``PalpatineConfig`` or ``MiningParams`` has the field; a
#: selector the program has replaced by a choice of its own is skipped
DEVICE_SELECTORS = {"decision_backend": "jax", "use_kernel": True}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the events of JAX making a program (jax 0.9's names): tracing a
#: function to a jaxpr, compiling it for the backend, and loading it from
#: the persistent compilation cache.  A program first used in the window
#: records one or more of them there, whether the cache holds it or not.
PROGRAM_EVENTS = ("/jax/core/compile/jaxpr_trace_duration", _COMPILE_EVENT,
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH) -> dict:
    """The cell's entry with its configuration, mix and metric lists, and
    the directory their files are found in."""
    bench = json.loads(bench_file.read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in {bench_file}")
    config = json.loads((bench_dir / "configs" / f"{cell['config']}.json")
                        .read_text())
    mix = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell, "config": config, "mix": mix, "dir": bench_dir,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def generator(spec: dict):
    """The traffic mix's generator module, ``traffic/<generator>.py``."""
    return load_module(spec["dir"] / "traffic"
                       / f"{spec['mix']['generator']}.py")


def reader(spec: dict, metric: str):
    """The per-layer metric's reader, ``metrics/<metric>.py``."""
    return load_module(spec["dir"] / "metrics" / f"{metric}.py")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 100]."""
    vs = sorted(values)
    if not vs:
        raise ValueError("percentile of no values")
    return float(vs[max(0, math.ceil(q / 100.0 * len(vs)) - 1)])


def _build(cls, spec: dict):
    """A dataclass from a dict, nested dataclass fields from nested dicts,
    with the device selectors applied where the class has them."""
    kw = {}
    for f in dataclasses.fields(cls):
        if f.name in spec:
            v = spec[f.name]
            if isinstance(v, dict) and f.default_factory is not dataclasses.MISSING:
                v = _build(type(f.default_factory()), v)
            kw[f.name] = v
        if f.name in DEVICE_SELECTORS:
            kw[f.name] = DEVICE_SELECTORS[f.name]
    for f in dataclasses.fields(cls):
        if (f.name not in kw and f.default_factory is not dataclasses.MISSING
                and dataclasses.is_dataclass(f.default_factory())):
            kw[f.name] = _build(type(f.default_factory()), {})
    return cls(**kw)


class Run:
    """State shared by the harness and the per-layer readers."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.in_window = False
        self.window_calls = 0
        self.compiles = []                # seconds of each backend compile
        self.window_programs = collections.Counter()  # PROGRAM_EVENTS
        self.state: dict = {}             # per-reader scratch
        self.trace_data = None            # reduced profiler trace
        self._patches: list = []
        self.client = None

    def engines(self) -> list:
        return [self.client.engine, self.client.col_engine]

    def patch(self, obj, name: str, make) -> None:
        """Replace ``obj.name`` by ``make(original)`` until ``restore``."""
        orig = getattr(obj, name)
        own = name in getattr(obj, "__dict__", {})
        self._patches.append((obj, name, orig if own else None))
        setattr(obj, name, make(orig))

    def restore(self) -> None:
        while self._patches:
            obj, name, orig = self._patches.pop()
            if orig is None:
                delattr(obj, name)
            else:
                setattr(obj, name, orig)

    def timed(self, key: str):
        """A wrapper factory adding the wall seconds of each call made in
        the window to ``state[key] = [seconds, calls]``."""
        acc = self.state.setdefault(key, [0.0, 0])

        def make(fn):
            def wrapped(*a, **kw):
                if not self.in_window:
                    return fn(*a, **kw)
                t = time.perf_counter()
                out = fn(*a, **kw)
                acc[0] += time.perf_counter() - t
                acc[1] += 1
                return out
            return wrapped
        return make

    def on_event(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.compiles.append(secs)
        if self.in_window and event in PROGRAM_EVENTS:
            self.window_programs[event] += 1


def _annotate(name: str, fn):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, say=print, fault=None) -> dict:
    """Set up, measure, compare; ``spec`` is what :func:`load_cell`
    gives.  Returns the result object.  ``fault``, for tests, is called
    with the client and the run before the backlog is logged, and may
    break the client through ``run.patch``."""
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core import LatencyModel, PalpatineClient, PalpatineConfig
    from repro.core import SimulatedDKVStore

    config, mix = spec["config"], spec["mix"]
    setup: dict = {}
    say(f"compile cache: {enable_compile_cache()}")
    run = Run(trace)
    jax.monitoring.register_event_duration_secs_listener(run.on_event)
    try:
        gen = generator(spec)
        t = time.perf_counter()
        work = gen.build(config, mix, seed)
        setup["data"] = work["data_s"]
        setup["traffic"] = time.perf_counter() - t - work["data_s"]
        data = work["data"]

        t = time.perf_counter()
        store = SimulatedDKVStore(LatencyModel(
            seed=seed, **config["store"]["latency"]),
            demand_width=config["semantics"]["demand_lanes"])
        store.load(data.items())
        setup["load"] = time.perf_counter() - t

        client = PalpatineClient(store, _build(PalpatineConfig,
                                              config["client"]))
        run.client = client
        rec = _Recorder(run, client)
        if fault is not None:
            fault(client, run)

        *set_up, (_, window) = phases(work, mix)
        for phase, steps in set_up:
            t = time.perf_counter()
            rec.drive(steps)
            setup[phase] = time.perf_counter() - t

        readers = {}
        if trace:
            for m in spec["per_layer"]:
                readers[m["name"]] = mod = reader(spec, m["name"])
                mod.install(run)
            for eng in run.engines():
                run.patch(eng, "on_request",
                          lambda f: _annotate("decide", f))
        setup_compiles = len(run.compiles)
        setup_compile_s = sum(run.compiles)
        rss_setup = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

        import devtrace as trace_mod
        tdir = ROOT / ".bench_trace"
        if trace:
            trace_mod.clear(tdir)
            jax.profiler.start_trace(str(tdir))
        setup_s = time.perf_counter() - t_start
        lat, t0, t_end = rec.window(window, seconds)
        if trace:
            jax.profiler.stop_trace()
        run.in_window = False
        window_s = t_end - t0
        dev = jax.devices()
        device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
                  "count": len(dev)}
        stats = dev[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        if trace:
            run.trace_data = trace_mod.reduce(tdir)
            device["busy_s"] = run.trace_data["busy_s"]
            device["window_s"] = run.trace_data["window_s"]
        window_programs = sum(run.window_programs.values())
        run.restore()

        n = len(lat)
        e2e = {
            "ops_per_s": n / window_s,
            "op_p50_us": percentile(lat, 50.0) * 1e6,
            "op_p99_us": percentile(lat, 99.0) * 1e6,
            "setup_s": setup_s,
        }
        units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
        if trace:
            metrics = {}
            for name, mod in readers.items():
                v = mod.read(run)
                if v is not None:
                    metrics[name] = {"value": v, "unit": units[name]}
        else:
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in spec["end_to_end"] if m["name"] in e2e}

        say("set-up s: " + ", ".join(f"{k} {v}" for k, v in setup.items())
            + f"; total {setup_s}")
        say(f"set-up compiles: {setup_compiles} ({setup_compile_s} s); "
            f"programs traced, compiled or loaded inside the window: "
            f"{window_programs} {dict(run.window_programs)}")
        wraps = rec.sessions_in_window // (len(work["window"])
                                           - mix["warm_sessions"])
        say(f"window: {n} calls in {window_s} s, {rec.sessions_in_window} "
            f"sessions; traffic wrapped {wraps} times; slowest call "
            f"{max(lat, default=0.0)} s")
        say(f"host peak RSS after set-up: {rss_setup} KiB")

        program = rec.outputs()
        program["window_programs"] = window_programs
        ops = rec.ops
        del client, store, rec, run.client
        gc.collect()
        t = time.perf_counter()
        checks = compare(program, ops, data, config, seed)
        say(f"reference: {time.perf_counter() - t} s for {len(ops)} steps; "
            f"compared {len(program['reads'])} reads, "
            f"{sum(map(bool, program['targets']))} + "
            f"{sum(map(bool, program['col_targets']))} decisions with "
            f"targets, {len(program['rounds'])} rounds of "
            f"{[len(m) + len(c) for m, c in program['rounds']]} patterns")
        say(f"host peak RSS: "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        return {"correct": correct, "attempted": n,
                "failed": sum(v is None for v, _ in program["reads"]),
                "metrics": metrics, "device": device,
                **({"breakdown": run.trace_data["breakdown"]} if trace else {}),
                "checks": checks}
    finally:
        jax.monitoring.unregister_event_duration_listener(run.on_event)
        run.restore()


class _Recorder:
    """Drives the client and keeps what the comparison needs: the steps
    taken, each read's answer, each decision's targets and each mining
    round's patterns."""

    def __init__(self, run: Run, client):
        self.run = run
        self.client = client
        self.ops: list = []            # ("r", key) / ("w", key, v) / ("e",) / ("m",)
        self.reads: list = []
        self.targets: list = []
        self.col_targets: list = []
        self.rounds: list = []
        self.sessions_in_window = 0

        def record_into(out):
            def make(fn):
                def wrapped(item):
                    t = fn(item)
                    out.append(t)
                    return t
                return wrapped
            return make

        run.patch(client.engine, "on_request", record_into(self.targets))
        run.patch(client.col_engine, "on_request",
                  record_into(self.col_targets))

        def mine_wrap(fn):
            def mine_now(*a, **kw):
                out = fn(*a, **kw)
                self.rounds.append((list(client.metastore),
                                    list(client.col_metastore or [])))
                return out
            return mine_now

        run.patch(client, "mine_now", mine_wrap)

    def drive(self, steps) -> None:
        """Take the set-up's steps, untimed."""
        self.reads += replay(self.client, steps, self.ops)

    def window(self, steps, seconds: float):
        """Closed loop, one client: each call is issued when the previous
        one returns, until ``seconds`` have passed.  Returns per-call wall
        seconds and the window's start and end."""
        c, run = self.client, self.run
        ops, reads = self.ops, self.reads
        read, write, end = c.read, c.write, c.end_session
        if run.trace:
            read, write = _annotate("serve", read), _annotate("serve", write)
        lat: list = []
        clock = time.perf_counter
        run.in_window = True
        t0 = clock()
        deadline = t0 + seconds
        t_end = t0
        for op in steps:
            ops.append(op)
            kind = op[0]
            if kind == "e":
                end()
                self.sessions_in_window += 1
                continue
            t = clock()
            if kind == "r":
                out = read(op[1])
                t_end = clock()
                reads.append(out)
            else:
                write(op[1], op[2])
                t_end = clock()
            lat.append(t_end - t)
            if t_end >= deadline:
                break
        run.in_window = False
        run.window_calls = len(lat)
        return lat, t0, t_end

    def outputs(self) -> dict:
        """The program's answers, item ids turned back into keys."""
        c = self.client
        item, col_item = c.logger.db.item, c.col_logger.db.item
        return {
            "reads": self.reads,
            "targets": [[item(i) for i in t] for t in self.targets],
            "col_targets": [[col_item(i) for i in t] for t in self.col_targets],
            "rounds": [([(tuple(item(i) for i in p.items), p.support)
                         for p in main],
                        [(tuple(col_item(i) for i in p.items), p.support)
                         for p in col])
                       for main, col in self.rounds],
            "stats": dataclasses.asdict(c.cache.stats),
        }


def _session(sess) -> list:
    return [("r", key) if value is None else ("w", key, value)
            for key, value in sess] + [("e",)]


def phases(work: dict, mix: dict) -> list:
    """A run's steps, phase by phase, as ``(phase, steps)``: the backlog
    logged, the first mining round, the warm sessions, and the window,
    whose steps never end: its traffic starts over when it runs out.  A
    step is ``("r", key)``, ``("w", key, value)``, ``("e",)`` (the end of
    a session) or ``("m",)`` (a mining round)."""
    warm = mix["warm_sessions"]
    return [
        ("logging", (op for s in work["backlog"] for op in _session(s))),
        ("first_round", [("m",)]),
        ("warm", (op for s in work["window"][:warm] for op in _session(s))),
        ("window", (op for s in itertools.cycle(work["window"][warm:])
                    for op in _session(s))),
    ]


def first_calls(steps, calls: int) -> list:
    """The steps up to and with the ``calls``-th client call, where a
    window of that many calls ends."""
    out = []
    for op in steps:
        out.append(op)
        if op[0] in ("r", "w"):
            calls -= 1
            if calls == 0:
                return out
    return out


def replay(client, steps, ops: list | None = None) -> list:
    """Drive a client, the program's or the reference's, through the
    steps; returns the reads' answers and appends each step to ``ops``."""
    reads = []
    for op in steps:
        if ops is not None:
            ops.append(op)
        kind = op[0]
        if kind == "r":
            reads.append(client.read(op[1]))
        elif kind == "w":
            client.write(op[1], op[2])
        elif kind == "e":
            client.end_session()
        elif kind == "m":
            client.mine_now()
    return reads


def compare(program: dict, ops: list, data: dict, config: dict,
            seed: int) -> dict:
    """Each number compared, with its limit.  All are counts, so every
    limit is 0: of answers that differ from the reference's, and of the
    programs JAX had to make inside the window (``window_programs``: a
    window may only use what set-up made)."""
    import reference

    ref = reference.Client(data, config, seed)
    reads = replay(ref, ops)
    pr = program["reads"]
    written = set()
    stale = 0
    vals = lats = 0
    ri = 0
    for op in ops:
        if op[0] == "w":
            written.add(op[1])
        elif op[0] == "r":
            if ri < len(pr) and ri < len(reads):
                (pv, pl), (rv, rl) = pr[ri], reads[ri]
                if pv != rv:
                    vals += 1
                    stale += op[1] in written
                if pl != rl:
                    lats += 1
            ri += 1
    missing = abs(len(pr) - len(reads))

    def differ(a: list, b: list) -> int:
        return sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))

    checks = {
        "read_values": vals + missing,
        "written_keys_read_back": stale,
        "read_latencies": lats + missing,
        "prefetch_targets": differ(program["targets"], ref.targets),
        "column_targets": differ(program["col_targets"], ref.col_targets),
        "mined_patterns": differ(program["rounds"], ref.rounds),
        "cache_stats": sum(program["stats"].get(k) != v
                           for k, v in ref.stats.items()),
        "window_programs": program["window_programs"],
    }
    return {k: {"value": v, "limit": 0} for k, v in checks.items()}
