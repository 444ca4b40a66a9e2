"""mine.bitmaps_s: host seconds per window mining round spent building
the packed vertical bitmaps of the tail (the program's
``palp.mine.bitmaps`` spans)."""

import mineprofile


def install(run):
    mineprofile.install(run)


def read(run):
    return mineprofile.per_round_s(run, mineprofile.BITMAPS)
