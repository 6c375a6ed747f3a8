"""Share of the collective ops' device time during which no other op runs
on that device, from the trace, in %."""


def read(run):
    s = run.summary
    if s is None or s.collective_s <= 0:
        return None
    return 100 * s.collective_exposed_s / s.collective_s
