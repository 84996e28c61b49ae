"""Engine: the device's idle time inside each ``engine.tick`` span that
lies in the traced window, mean per tick, in ms: the host's part of a
decode tick (uploads, dispatch, sampling's copy, bookkeeping)."""
from bench import trace


def read(run):
    ticks = [s for s in run.trace.spans if s.name == trace.TICK] \
        if run.trace else []
    if not ticks:
        return None
    return 1e3 * sum(s.dur_s - s.busy_s for s in ticks) / len(ticks)
