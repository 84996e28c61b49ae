"""Device: the share of the traced window in which no operation ran on
the device (1 minus the union of the device's busy intervals over the
window), in percent."""


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
