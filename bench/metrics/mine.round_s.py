"""mine.round_s: host seconds per mining round, from the client's own
timer (``PalpatineClient.mining_wall_time`` over ``mining_runs``): the
mean over every round of the run, set-up's and the window's alike."""


def install(run):
    pass


def read(run):
    c = run.client
    runs = getattr(c, "mining_runs", 0)
    return c.mining_wall_time / runs if runs else None
