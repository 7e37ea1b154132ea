"""Roofline share of the ragged paged prefill kernel
(``kernels/paged_prefill.py``), in %: the work of one call at the true
prefix and chunk lengths and the pool's itemsize, over its mean device
time.  The kernel's name (``trace.KERNELS``) is for full-attention layers:
each call is counted over the whole prefix.  A kernel for sliding-window
layers takes a ``name=`` and a metric file of its own."""
from bench.metrics_common import kernel_roofline


def read(record):
    return kernel_roofline(record, "prefill")
