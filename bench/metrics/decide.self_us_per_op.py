"""decide.self_us_per_op: host microseconds inside the decision engines'
calls (the program's ``palp.decide`` span, ``core/palpatine.py``) less
the decision-walk calls opened in them (``palp.walk``), per client call
of the window: the engines' own numpy work."""

import hostprofile


def install(run):
    hostprofile.install(run)


def read(run):
    prof = run.state.get(hostprofile.KEY)
    if prof is None or not prof.calls.get("palp.decide") \
            or not run.window_calls:
        return None
    return prof.self_seconds("palp.decide") * 1e6 / run.window_calls
