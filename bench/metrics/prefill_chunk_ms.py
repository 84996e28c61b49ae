"""Model step: device time of the prefill-chunk program
(``decode_paged`` at one sequence of ``prefill_chunk`` tokens), mean
per chunk over the traced admissions."""


def read(run):
    t = run.trace
    if t is None or not t.chunk_count:
        return None
    return 1e3 * t.chunk_device_s / t.chunk_count
