"""The mining round's spans and counters in the program's host profile
(``repro.core.obs``), shared by the readers of the ``mine.*`` metrics of
a cell that mines inside its window.

The profile is the one ``hostprofile.py`` installs, after set-up, so
every ``palp.mine`` span it holds is a round of the window.  A program
without these spans books none, and the readers read nothing.  The
names are the program's, spelled out here so that a program without
them still loads every reader.
"""

from __future__ import annotations

import hostprofile

MINE = "palp.mine"                    # one round: mine_now
BITMAPS = "palp.mine.bitmaps"         # a VerticalBitmaps build
JOIN = "palp.mine.join"               # a frontier join, answered
REBUILD = "palp.mine.rebuild"         # metastore, trees, forest upload
WARM = "palp.mine.warm"               # the round's programs made ahead
JOIN_H2D_BYTES = "palp.mine.join_h2d_bytes"
COLD_PROGRAMS = "palp.mine.cold_programs"


def install(run) -> None:
    hostprofile.install(run)


def rounds(run) -> tuple:
    """(the run's profile, its mining rounds in the window), or
    (None, 0)."""
    prof = run.state.get(hostprofile.KEY)
    return prof, (prof.calls.get(MINE, 0) if prof else 0)


def per_round_s(run, span: str):
    """Host seconds in ``span`` per mining round of the window."""
    prof, n = rounds(run)
    return prof.seconds.get(span, 0.0) / n if n else None
