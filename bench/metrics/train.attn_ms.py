"""Device milliseconds per training step in the attention layer
(``models/attention.py``: projections, rotary and the flash-attention
kernel, in the forward pass, its remat recompute and the backward pass),
from the profiler trace joined to the compiled step's scopes
(``bench/layers.py``), mean over the devices."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "attn")
