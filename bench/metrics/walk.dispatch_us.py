"""walk.dispatch_us: host microseconds per decision-walk call
(``kernels/decision_walk/ops.py`` ``decision_walk``) in the program's
``palp.walk.dispatch`` span: the jitted step's call, until it returns."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    return hostprofile.per_walk_us(run, "palp.walk.dispatch")
