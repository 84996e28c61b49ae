"""Kernels: the paged attention kernel's share of its roofline over the
traced engine calls: the least time the chip needs for the kernel
calls' work (the architecture module's ``kernel_calls``: every live
block of keys and values, queries in and outputs out, against the peaks
table), over the kernel's device time, in percent. The trace summary
says which bound (memory or compute) sets it."""


def read(run):
    t = run.trace
    if t is None or not t.kernel_device_s or not t.kernel_min_s:
        return None
    return 100.0 * t.kernel_min_s / t.kernel_device_s
