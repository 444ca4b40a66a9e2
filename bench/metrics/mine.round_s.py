"""mine.round_s: host seconds per mining round, from the client's own
timer (``PalpatineClient.mining_wall_time`` over ``mining_runs``).  No
cell mines in its window, so this is the set-up's first round."""


def install(run):
    pass


def read(run):
    c = run.client
    runs = getattr(c, "mining_runs", 0)
    return c.mining_wall_time / runs if runs else None
