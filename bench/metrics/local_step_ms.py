"""Device time of the local-step scan bodies per local step."""


def read(run):
    if run.trace is None or run.local_steps == 0:
        return None
    return run.trace.cat_s["local"] / run.local_steps * 1e3
