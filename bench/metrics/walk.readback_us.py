"""walk.readback_us: host microseconds per decision-walk call
(``kernels/decision_walk/ops.py`` ``decision_walk``) in the program's
``palp.walk.readback`` span: copying the step's one packed output back
to the host."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    return hostprofile.per_walk_us(run, "palp.walk.readback")
