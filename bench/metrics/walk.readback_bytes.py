"""walk.readback_bytes: bytes copied from the device to the host per
decision-walk call, from the program's ``palp.walk.d2h_bytes`` counter
(the ``nbytes`` of the step's six outputs)."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    prof, n = hostprofile.walks(run)
    return prof.counters.get("palp.walk.d2h_bytes", 0) / n if n else None
