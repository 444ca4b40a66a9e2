"""walk.wait_us: host microseconds per decision-walk call
(``kernels/decision_walk/ops.py`` ``decision_walk``) in the program's
``palp.walk.wait`` span: waiting for the step's outputs
(``block_until_ready``)."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    return hostprofile.per_walk_us(run, "palp.walk.wait")
