"""Traffic generation: every input a cell feeds the program, from the seed.

The benchmark makes its own inputs, so no change to the program's data
pipeline can change what a cell measures. ``SyntheticTokens`` is a copy of
``repro.data.pipeline.SyntheticTokens`` (Zipf documents packed into rows),
kept here unchanged in its arithmetic.
"""

from __future__ import annotations

import numpy as np

__all__ = ["SyntheticTokens", "prompts", "seed_key"]


def seed_key(seed: int):
    """A JAX PRNG key from any whole seed (all 64 bits of it count)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _pack(docs: list[np.ndarray], row_len: int) -> np.ndarray:
    flat = np.concatenate(docs)
    if flat.size < row_len:
        flat = np.pad(flat, (0, row_len - flat.size))
    return flat[:row_len]


class SyntheticTokens:
    """Zipf(1.2) token documents of ``mean_doc_len // 2`` to ``2 * mean_doc_len``
    tokens, each opened by BOS (id 1), packed into rows of ``seq_len + 1``.

    Row ``r`` of step ``s`` is a function of ``(seed, s, r)`` alone, so every
    step's rows differ and any of them can be made again for the check.
    """

    def __init__(self, vocab_size: int, seq_len: int, batch: int, seed: int,
                 mean_doc_len: int = 512):
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.batch_size = batch
        self.seed = seed
        self.mean_doc_len = mean_doc_len

    def _row(self, step: int, row: int) -> np.ndarray:
        seed = np.uint64(self.seed) * np.uint64(1_000_003)
        seed += np.uint64(step) * np.uint64(8_191) + np.uint64(row)
        rng = np.random.default_rng(int(seed))
        docs, total = [], 0
        while total < self.seq_len + 1:
            n = int(rng.integers(self.mean_doc_len // 2, self.mean_doc_len * 2))
            doc = rng.zipf(1.2, size=n) % (self.vocab_size - 2) + 2
            docs.append(np.concatenate([[1], doc]))
            total += n + 1
        return _pack(docs, self.seq_len + 1)

    def batch(self, step: int) -> dict:
        rows = np.stack([self._row(step, r) for r in range(self.batch_size)])
        return {
            "tokens": rows[:, :-1].astype(np.int32),
            "labels": rows[:, 1:].astype(np.int32),
        }


def prompts(vocab_size: int, batch: int, prompt_len: int, seed: int, index: int) -> np.ndarray:
    """Prompt ids of batch ``index``: uniform over ``[2, vocab)``, as
    ``repro.launch.serve`` draws them, one generator per batch."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(2, vocab_size, size=(batch, prompt_len)).astype(np.int32)
