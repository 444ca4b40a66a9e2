"""The control of ``correct``: the plain reference put in the program's
place with one guarantee of the configuration broken (the configuration
file's ``control``), driven through a cell's traffic and compared as a
run is.  It has to come out not correct.

    python3 bench/control.py --workload <cell> --calls <n> --seed <s> [<s> ...]

``--calls`` is the number of client calls a run makes in its window.
Prints each seed's compared numbers and their limits.  It runs the
reference only, on the host, and needs no chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def steps(work: dict, mix: dict, calls: int) -> list:
    """The steps a run takes: its set-up, then a window of ``calls``
    calls, from the same phases the harness drives."""
    import harness

    *set_up, (_, window) = harness.phases(work, mix)
    return ([op for _, phase in set_up for op in phase]
            + harness.first_calls(window, calls))


def control_run(spec: dict, seed: int, calls: int,
                control: str | None) -> dict:
    """The checks of a run whose program is the reference with
    ``control`` broken (``None``: the sound reference), driven through
    the steps of a run whose window makes ``calls`` calls."""
    import harness
    import reference

    config, mix = spec["config"], spec["mix"]
    work = harness.generator(spec).build(config, mix, seed)
    ops = steps(work, mix, calls)
    ref = reference.Client(work["data"], config, seed, control=control)
    reads = harness.replay(ref, ops)
    program = {"reads": reads, "targets": ref.targets,
               "col_targets": ref.col_targets, "rounds": ref.rounds,
               "stats": ref.stats, "window_programs": 0}
    return harness.compare(program, ops, work["data"], config, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness

    spec = harness.load_cell(args.workload)
    control = spec["config"]["control"]
    failed = 0
    for seed in args.seed:
        t = time.perf_counter()
        checks = control_run(spec, seed, args.calls, control)
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        failed += not correct
        print(json.dumps({"workload": args.workload, "control": control,
                          "seed": seed, "correct": correct,
                          "seconds": time.perf_counter() - t,
                          "checks": checks}), flush=True)
    return 0 if failed == len(args.seed) else 1


if __name__ == "__main__":
    sys.exit(main())
