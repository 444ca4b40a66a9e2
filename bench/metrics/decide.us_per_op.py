"""decide.us_per_op: host microseconds inside the decision engines'
``on_request`` (main and column engines, ``core/decision.py``) per client
call of the window."""


def install(run):
    for eng in run.engines():
        run.patch(eng, "on_request", run.timed("decide.us_per_op"))


def read(run):
    seconds, calls = run.state["decide.us_per_op"]
    if not calls or not run.window_calls:
        return None
    return seconds * 1e6 / run.window_calls
