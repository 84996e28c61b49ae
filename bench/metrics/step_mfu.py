"""Device: the decode-tick and prefill-chunk programs' share of the
chip's peak bf16 rate: the operations the traced ticks and chunks need
(the architecture module's ``decode_tick`` and ``prefill_chunk``:
active sequences and real prompt tokens only) over those programs'
device time times the peak, in percent."""


def read(run):
    t = run.trace
    if t is None or not t.step_device_s or run.peaks is None:
        return None
    return 100.0 * t.step_flops / (t.step_device_s
                                   * run.peaks["bf16_flop_s"])
