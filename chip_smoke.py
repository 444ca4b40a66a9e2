"""Chip smoke run: Palpatine's main path on one TPU at the paper's SEQB scale.

Builds the paper's SEQB deployment (§5: 2.3M blocks of 1000 bytes,
10,240 frequent sequences of 3-10 blocks, Zipf 1.0) in a
``SimulatedDKVStore`` with a jitter-free latency model and a 32 MB cache,
and drives it through ``PalpatineClient`` the way
``benchmarks.workloads.run_two_stage`` does: a 5,000-session warm stage,
``mine_now()``, then the measured sessions.

The device run mines with the Pallas frontier join
(``MiningParams(use_kernel=True)``) and decides prefetches with the
jitted decision walk (``decision_backend="jax"``).  The same stages then
run again in this process on the numpy paths, the plain reference.  The
mined patterns, every measured read's value and virtual latency, and the
cache stats must be identical.

Usage::

    python chip_smoke.py [--seed N]

Everything runs in this one process.  Without a TPU it exits non-zero
and prints no result.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import resource
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: SEQB at the paper's scale (§5); ``run`` takes ``n_blocks`` so the same
#: code can be rehearsed small
N_BLOCKS = 2_300_000
WARM_SESSIONS = 5_000
MEASURED_SESSIONS = 500
CACHE_BYTES = 32 * 1024 * 1024      # PalpatineConfig default; paper 2-256 MB

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(msg, flush=True)


class Probe:
    """Records what the device run launches: every mining join's shape,
    the decision walks, and their wall seconds (each timed call ends on
    the host, so device work is included), plus the seconds XLA spends
    compiling."""

    def __init__(self):
        self.frontier: list[tuple] = []     # (P, K, S, W) per frontier join
        self.sstep: list[tuple] = []        # (K, S, W) per DFS-spill join
        self.walks = 0
        self.join_s = 0.0
        self.walk_s = 0.0
        self.compile_s = 0.0
        self.compiles = 0

    def on_event(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.compile_s += secs
            self.compiles += 1

    @contextlib.contextmanager
    def watching(self):
        import jax

        from repro.kernels.bitmap_support import ops as bops
        from repro.kernels.decision_walk import ops as dops

        frontier, sstep, walk = (bops.frontier_join_support,
                                 bops.sstep_join_support, dops.decision_walk)

        def timed_join(join, slots, cand, **kw):
            t = time.perf_counter()
            out = jax.block_until_ready(join(slots, cand, **kw))
            self.join_s += time.perf_counter() - t
            return out

        def frontier_join(slots, cand, **kw):
            self.frontier.append((*slots.shape[:1], *cand.shape))
            return timed_join(frontier, slots, cand, **kw)

        def sstep_join(slots, cand, **kw):
            self.sstep.append(tuple(cand.shape))
            return timed_join(sstep, slots, cand, **kw)

        def decision_walk(*args, **kw):
            t = time.perf_counter()
            out = walk(*args, **kw)          # returns host arrays
            self.walk_s += time.perf_counter() - t
            self.walks += 1
            return out

        bops.frontier_join_support = frontier_join
        bops.sstep_join_support = sstep_join
        dops.decision_walk = decision_walk
        jax.monitoring.register_event_duration_secs_listener(self.on_event)
        try:
            yield self
        finally:
            bops.frontier_join_support = frontier
            bops.sstep_join_support = sstep
            dops.decision_walk = walk
            jax.monitoring.unregister_event_duration_listener(self.on_event)


def run_stages(data, warm, measured, *, device: bool) -> dict:
    """One two-stage run on a fresh store; returns what parity compares
    plus per-phase wall seconds."""
    from repro.core import (HeuristicConfig, LatencyModel, MiningParams,
                            PalpatineClient, PalpatineConfig,
                            SimulatedDKVStore)
    from repro.core.cache import CacheStats

    wall = {}
    t = time.perf_counter()
    store = SimulatedDKVStore(LatencyModel(jitter_sigma=0.0, stall_frac=0.0))
    store.load(data)
    wall["load"] = time.perf_counter() - t
    # run_two_stage's mining and heuristic settings, the paper's cache
    cfg = PalpatineConfig(
        heuristic=HeuristicConfig("fetch_progressive", top_n=5),
        cache_bytes=CACHE_BYTES,
        mining=MiningParams(minsup=0.02, min_len=3, max_len=15, maxgap=1,
                            use_kernel=device),
        decision_backend="jax" if device else "numpy",
        min_patterns=400, dynamic_minsup_floor=0.002)
    client = PalpatineClient(store, cfg)
    t = time.perf_counter()
    for sess in warm:
        for key in sess:
            client.read(key)
        client.end_session()
    wall["warm"] = time.perf_counter() - t
    t = time.perf_counter()
    client.mine_now()
    wall["mine"] = time.perf_counter() - t
    client.cache.stats = CacheStats()
    reads = []
    t = time.perf_counter()
    for sess in measured:
        for key in sess:
            reads.append(client.read(key))
        client.end_session()
    wall["measured"] = time.perf_counter() - t
    return {
        "patterns": [(p.items, p.support) for p in client.metastore],
        "reads": reads,
        "stats": dataclasses.asdict(client.cache.stats),
        "wall": wall,
    }


def run(n_blocks: int = N_BLOCKS, warm_sessions: int = WARM_SESSIONS,
        measured_sessions: int = MEASURED_SESSIONS,
        seed: int = 0) -> dict[str, bool]:
    """The device run, then the numpy reference.  Prints what ran and
    returns each check by name; the smoke passes when all hold."""
    import jax
    import numpy as np

    from benchmarks.workloads import SEQB, SEQBConfig
    from repro.kernels.bitmap_support import ops as bops

    t = time.perf_counter()
    seqb = SEQB(SEQBConfig(n_blocks=n_blocks, block_bytes=1000,
                           n_frequent=10_240, min_seq=3, max_seq=10,
                           zipf_exp=1.0, seed=seed))
    data = list(seqb.dataset())
    rng = np.random.default_rng(seed)
    warm = list(seqb.sessions(rng, warm_sessions))
    measured = list(seqb.sessions(rng, measured_sessions))
    say(f"store: {n_blocks} blocks x 1000 B = {n_blocks * 1000} B; "
        f"workload built in {time.perf_counter() - t} s")

    probe = Probe()
    with probe.watching():
        dev = run_stages(data, warm, measured, device=True)
    ref = run_stages(data, warm, measured, device=False)

    warm_reads = sum(map(len, warm))
    for name, r in (("device", dev), ("reference", ref)):
        say(f"{name}: warm {len(warm)} sessions / {warm_reads} reads, "
            f"measured {len(measured)} sessions / {len(r['reads'])} reads, "
            f"{len(r['patterns'])} patterns mined; wall s "
            + ", ".join(f"{k} {v}" for k, v in r["wall"].items()))
    say(f"device compile: {probe.compiles} XLA compiles, {probe.compile_s} s")
    say(f"device joins: {probe.join_s} s in {len(probe.frontier)} frontier "
        f"+ {len(probe.sstep)} DFS-spill calls; decision walks: "
        f"{probe.walk_s} s in {probe.walks} calls")

    checks = {"frontier join with K > 8": any(f[1] > 8 for f in probe.frontier),
              "device decision walks": probe.walks > 0}
    if probe.frontier:
        big = max(probe.frontier, key=lambda f: np.prod(f))
        p, k, s, w = big
        # the Mosaic kernel lowers to a tpu_custom_call; the interpret
        # path would be plain XLA loops instead
        pb, kb, sb, wb = bops.frontier_calls(p, k, s, w)[-1]
        text = bops.frontier_program.lower(
            jax.ShapeDtypeStruct((wb, pb, sb), np.uint32),
            jax.ShapeDtypeStruct((wb, kb, sb), np.uint32),
            interpret=jax.default_backend() != "tpu").compile().as_text()
        checks["compiled Mosaic kernel"] = "tpu_custom_call" in text
        say(f"frontier path: {len(probe.frontier)} joins, "
            f"{sum(f[1] > 8 for f in probe.frontier)} with K > 8; largest "
            f"(P, K, S, W) = {big}; compiled Mosaic kernel: "
            f"{checks['compiled Mosaic kernel']}")
    else:
        say("frontier path: no join ran")
    if probe.sstep:
        say(f"DFS spill path: {len(probe.sstep)} joins, largest (K, S, W) = "
            f"{max(probe.sstep, key=lambda f: np.prod(f))}")
    else:
        say("DFS spill path: not taken")

    parity = {
        "patterns": bool(dev["patterns"]) and dev["patterns"] == ref["patterns"],
        "reads": dev["reads"] == ref["reads"],
        "cache stats": dev["stats"] == ref["stats"],
    }
    for name, good in parity.items():
        say(f"parity {name}: {'identical' if good else 'DIFFERENT'}")
    say(f"measured-stage cache stats: {dev['stats']}")
    say(f"host peak RSS: "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    checks.update(("parity " + k, v) for k, v in parity.items())
    return checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the SEQB data and sessions")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    for path in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(path))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing: {e}",
              file=sys.stderr)
        return 1
    say(f"compile cache: {enable_compile_cache()}")
    failed = [name for name, good in run(seed=args.seed).items() if not good]
    if failed:
        print(f"chip_smoke: failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
