"""Device milliseconds per training step of the ops under the MoE's
``moe/a2a`` scope (``models/moe.py`` ``_a2a`` to
``core/rails_all_to_all.py``: the rails' collective-permute chain, its
partial-sum buffers and adds, in every pass), the union of their
intervals, from the profiler trace joined to the compiled step's scopes
(``bench/layers.py``), mean over the devices. Unlike ``train.a2a_ms`` it
counts the ops themselves, not the time a collective is in flight."""

from bench.layers import read_ms


def read(r):
    return read_ms(r, "moe/a2a")
