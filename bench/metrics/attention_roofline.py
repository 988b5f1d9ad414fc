"""Kernels: the attention scope's share of the chip's HBM roofline, per traced
decode step: the bytes it must move (``harness/hbm.py``) over its device
time (``attention_ms_per_step``) times the peak bandwidth
(``harness/peaks.py``)."""
from harness import readers


def read(ctx):
    return readers.roofline_share(ctx, "attention")
