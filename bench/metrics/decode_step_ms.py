"""Model step: device time of the decode-tick program (``decode_paged``
at one token per slot), mean per tick over the traced ticks."""


def read(run):
    t = run.trace
    if t is None or not t.tick_device_s:
        return None
    return 1e3 * sum(t.tick_device_s) / len(t.tick_device_s)
