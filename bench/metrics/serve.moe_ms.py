"""Device milliseconds per decode step in the MoE layer (``models/moe.py``:
router, dispatch, all-to-all, expert products and combine), from the
profiler trace joined to the compiled step's scopes (``bench/layers.py``)."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "moe")
