"""walk.upload_us: host microseconds per decision-walk call
(``kernels/decision_walk/ops.py`` ``decision_walk``) in the program's
``palp.walk.upload`` span: padding the live contexts and copying them to
the device."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    return hostprofile.per_walk_us(run, "palp.walk.upload")
