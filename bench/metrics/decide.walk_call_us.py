"""decide.walk_call_us: host microseconds per call of the jitted decision
walk (``kernels/decision_walk/ops.py`` ``decision_walk``): padding, upload,
the step and the read-back of its outputs."""


def install(run):
    from repro.kernels.decision_walk import ops

    run.patch(ops, "decision_walk", run.timed("decide.walk_call_us"))


def read(run):
    seconds, calls = run.state["decide.walk_call_us"]
    return seconds * 1e6 / calls if calls else None
