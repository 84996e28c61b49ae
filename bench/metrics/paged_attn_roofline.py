"""Kernels: the paged attention kernel's share of its roofline over the
traced engine calls: the least time the chip needs for the kernel
calls' work (the architecture module's ``kernel_calls`` entries named
``paged_attention``: every live block of keys and values, queries in
and outputs out, against the peaks table), over the device time of the
ops named for the kernel (``%paged_attention.<n>``), in percent. The
trace summary says which bound (memory or compute) sets it."""
from bench import trace


def read(run):
    return trace.roofline(run, "paged_attention")
