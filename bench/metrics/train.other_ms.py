"""Device milliseconds per training step under no layer's scope: the
step's device time less every op of ``attn``, ``moe`` and ``head``
(norms, embedding, residual adds, the layer scan's slicing, the gradient
all-reduces and the AdamW update), from the profiler trace joined to the
compiled step's scopes (``bench/layers.py``), mean over the devices."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "other")
