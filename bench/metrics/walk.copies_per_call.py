"""walk.copies_per_call: host-to-device plus device-to-host copies per
decision-walk call, from the program's ``palp.walk.h2d_copies`` and
``palp.walk.d2h_copies`` counters."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    prof, n = hostprofile.walks(run)
    if not n:
        return None
    c = prof.counters
    return (c.get("palp.walk.h2d_copies", 0)
            + c.get("palp.walk.d2h_copies", 0)) / n
