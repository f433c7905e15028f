"""Device milliseconds per decode step in the output head
(``models/transformer.py``: the vocabulary product and its soft cap), from
the profiler trace joined to the compiled step's scopes
(``bench/layers.py``)."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "head")
