"""walk.upload_bytes: bytes copied from the host to the device per
decision-walk call, from the program's ``palp.walk.h2d_bytes`` counter
(the ``nbytes`` of the padded context arrays and the alive mask)."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    prof, n = hostprofile.walks(run)
    return prof.counters.get("palp.walk.h2d_bytes", 0) / n if n else None
