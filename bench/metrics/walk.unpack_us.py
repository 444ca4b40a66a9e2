"""walk.unpack_us: host microseconds per decision-walk call
(``kernels/decision_walk/ops.py`` ``decision_walk``) in the program's
``palp.walk.unpack`` span: the wave's nonzeros, the slices and the
casts."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    return hostprofile.per_walk_us(run, "palp.walk.unpack")
