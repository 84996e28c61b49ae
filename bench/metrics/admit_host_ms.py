"""Engine: the device's idle time inside each ``engine.admit`` span that
lies in the traced window and ran an ``admit.chunk``, mean per
admission, in ms: the host's part of an admission (reservation, each
chunk's uploads and dispatch, the admission token's draw). An
admission that ran no chunk admitted nothing and is not counted."""
from bench import trace


def read(run):
    admits = trace.admissions(run.trace.spans) if run.trace else []
    if not admits:
        return None
    return 1e3 * sum(s.dur_s - s.busy_s for s in admits) / len(admits)
