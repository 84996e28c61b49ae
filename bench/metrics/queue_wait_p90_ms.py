"""Front end: 90th percentile of the wait from a request's due time
(closed loop: its send) to the start of its admission, over the
requests due in the window; one not admitted by the window's end
counts with the wait it has had so far. Benchmark timestamps."""
from bench.stats import percentile


def read(run):
    w0, w1 = run.window
    start = {}
    for a in run.admissions:
        start.setdefault(a.rid, a.start)
    waits = [min(start.get(s.rid, w1), w1) - s.due for s in run.in_window()
             if not s.failed]
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
