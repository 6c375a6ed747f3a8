"""Device time of the collective ops per training step, from the trace,
mean over the chips, in s."""


def read(run):
    s = run.summary
    steps = run.facts.get("steps")
    if s is None or not steps or s.collective_s <= 0:
        return None
    return s.collective_s / steps
