"""Device milliseconds per training step in the output head
(``models/transformer.py``: the loss's vocabulary product, forward and
backward), from the profiler trace joined to the compiled step's scopes
(``bench/layers.py``), mean over the devices."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "head")
