"""Device milliseconds per decode step under no layer's scope: the step's
device time less every op of ``attn``, ``moe`` and ``head`` (pre-norms,
embedding, residual adds, the layer scan's weight slicing and copies),
from the profiler trace joined to the compiled step's scopes
(``bench/layers.py``)."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "other")
