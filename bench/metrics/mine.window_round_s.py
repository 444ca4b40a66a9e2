"""mine.window_round_s: host seconds per mining round inside the
window, from the program's ``palp.mine`` span (``mine_now``, from its
entry to the new trees' install): the stall one round puts into the
user's read that triggers it."""

import mineprofile


def install(run):
    mineprofile.install(run)


def read(run):
    return mineprofile.per_round_s(run, mineprofile.MINE)
