"""Palpatine's chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with a TPU: it exits
non-zero and prints no result without one, with a device kind that
``bench/peaks.json`` does not hold, or with fewer chips than the cell
asks for.  The last line of standard output is the result object;
the numbers compared with the plain reference, each beside its limit,
are the last lines of standard error and the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness

    spec = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if devices[0].device_kind not in peaks:
        print(f"bench: no peaks for device kind {devices[0].device_kind!r} "
              f"in bench/peaks.json", file=sys.stderr)
        return 2
    if len(devices) < spec["cell"]["chips"]:
        print(f"bench: the cell needs {spec['cell']['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    def say(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    result = harness.run_cell(spec, args.seed, args.seconds,
                              bool(args.trace), T_START, say=say)
    for name, c in result["checks"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
