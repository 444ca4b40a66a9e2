"""mine.passes_per_round: frontier passes per mining round inside the
window: the program's ``palp.mine.passes`` counter (one per pass of a
round's dynamic-minsup descent, ``core/mining.py``
``mine_dynamic_minsup``) over its ``palp.mine`` rounds.  A program that
does not register the counter reads nothing."""

import mineprofile

#: the program's counter, spelled here so that a program without it
#: still loads this reader
PASSES = "palp.mine.passes"


def install(run):
    mineprofile.install(run)


def read(run):
    from repro.core import obs

    prof, n = mineprofile.rounds(run)
    if not n or PASSES not in getattr(obs, "REGISTERED_NAMES", ()):
        return None
    return prof.counters.get(PASSES, 0) / n
