"""Roofline share of the ragged paged decode kernel
(``kernels/paged_attention.py``), in %: the work of one call at the true
context lengths and the pool's itemsize, over its mean device time.  The
kernel's name (``trace.KERNELS``) is for full-attention layers: each call
is counted at the whole context.  A kernel for sliding-window layers takes
a ``name=`` and a metric file of its own."""
from bench.metrics_common import kernel_roofline


def read(record):
    return kernel_roofline(record, "decode")
