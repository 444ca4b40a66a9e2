"""The program's host profile (``repro.core.obs``), shared by the
per-layer readers of its spans and counters.

The first of those readers to be installed makes one active profile
current for the rest of the run, through ``run.patch``, so
``run.restore()`` turns it off again.  A program whose ``obs`` has no
host profile gets none, and its readers read nothing.  Span and
counter names are the program's, spelled out here so that a program
without them still loads every reader.
"""

from __future__ import annotations

KEY = "host_profile"
WALK = "palp.walk"


def install(run) -> None:
    from repro.core import obs

    if KEY in run.state or not hasattr(obs, "HostProfile"):
        return
    run.state[KEY] = prof = obs.HostProfile()
    run.patch(obs, "host_profile", lambda _: prof)


def walks(run) -> tuple:
    """(the run's profile, its decision-walk calls), or (None, 0)."""
    prof = run.state.get(KEY)
    return prof, (prof.calls.get(WALK, 0) if prof else 0)


def per_walk_us(run, span: str):
    """Host microseconds in ``span`` per decision-walk call."""
    prof, n = walks(run)
    return prof.seconds.get(span, 0.0) * 1e6 / n if n else None
