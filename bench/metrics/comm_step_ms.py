"""Device time of the comm step's branch per comm step (one a round)."""


def read(run):
    if run.trace is None or run.rounds == 0 or run.trace.cat_s["comm"] <= 0:
        return None
    return run.trace.cat_s["comm"] / run.rounds * 1e3
