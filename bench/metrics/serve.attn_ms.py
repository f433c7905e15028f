"""Device milliseconds per decode step in the attention layer
(``models/attention.py``: projections, rotary, the KV-cache write and the
attention core), from the profiler trace joined to the compiled step's
scopes (``bench/layers.py``)."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "attn")
