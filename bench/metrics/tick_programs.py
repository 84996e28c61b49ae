"""Model step: device programs (module executions) that start inside
each ``engine.tick`` span that lies in the traced window, mean per
tick: the tick program and the small programs dispatched around it."""
from bench import trace


def read(run):
    ticks = [s for s in run.trace.spans if s.name == trace.TICK] \
        if run.trace else []
    if not ticks:
        return None
    return sum(s.programs for s in ticks) / len(ticks)
