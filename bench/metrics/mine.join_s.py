"""mine.join_s: host seconds per window mining round spent in frontier
support joins, each until its answer is on the host (the program's
``palp.mine.join`` spans)."""

import mineprofile


def install(run):
    mineprofile.install(run)


def read(run):
    return mineprofile.per_round_s(run, mineprofile.JOIN)
