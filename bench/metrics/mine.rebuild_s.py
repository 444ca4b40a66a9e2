"""mine.rebuild_s: host seconds per mining round spent installing what
it mined (metastore ``populate``, ``PTreeIndex.build`` and the engines'
``replace_index``, main and column), from the client's own timer
(``PalpatineClient.rebuild_wall_time`` over ``mining_runs``)."""


def install(run):
    pass


def read(run):
    c = run.client
    runs = getattr(c, "mining_runs", 0)
    rebuild = getattr(c, "rebuild_wall_time", None)
    return rebuild / runs if runs and rebuild is not None else None
