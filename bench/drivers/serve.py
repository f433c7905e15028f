"""Serving driver: closed-loop batches through the program's jitted decode
step, driven as ``repro.launch.serve.main`` drives it.

Each batch of requests gets a fresh cache from ``init_cache``, is primed
token by token through the decode step, and then decodes greedily: every
step takes the argmax of the last logits as its token and fetches it to
the host before the next step is issued. Batches follow one another with
no gap until the window's time is up; the batch under way then finishes,
so every request in the window is whole.

``tpot_ms_p95`` is the 95th percentile, over every decode step of every
request in the window, of the host-clock time from one generated token's
arrival to the next one's (one step yields one token per request).

The step is built with the program's counters on
(``make_decode_step(..., return_counts=True)``): each step also gives the
(layer, expert) weight sets it read, which stay on the device until the
window has closed and are then fetched at once; the bytes each step had to
read are counted from them.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.configs.base import ModelConfig
from repro.launch.steps import make_decode_step
from repro.launch.train import init_sharded_params
from repro.models import init_cache
from repro.parallel.mesh_view import build_mesh_context

from bench import flops
from bench.check import checked, serve_numbers
from bench.gen import prompts, seed_key

__all__ = ["Job"]


class Job:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, devices
        t = cell.traffic
        self.batch, self.prompt_len, self.gen_len = t["batch"], t["prompt_len"], t["gen_len"]
        self.cfg = cfg = ModelConfig(**cell.model)
        mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
        self.ctx = ctx = build_mesh_context(mesh, cfg)
        self.key = seed_key(seed)
        max_len = self.prompt_len + self.gen_len
        with jax.set_mesh(ctx.mesh):
            self.params, _ = init_sharded_params(cfg, ctx, self.key)
            self.decode = jax.jit(make_decode_step(cfg, ctx, return_counts=True),
                                  donate_argnums=(1,))
            self.new_cache = jax.jit(lambda: init_cache(cfg, self.batch, max_len))
        self.check_setup_s = 0.0
        self.served: list[tuple] = []  # (prompts, tokens, all logits finite)
        self.reads: list = []  # each step's expert weight sets read, on the device
        self.batch_index = 0
        # Warm-up: every program the window drives, at the window's shapes.
        self._run_batch(prime=2, gen=2, keep=False)

    def _step(self, cache, tokens, pos):
        with jax.set_mesh(self.ctx.mesh), jax.profiler.TraceAnnotation("bench.step"):
            logits, cache, _, read = self.decode(self.params, cache, {"tokens": tokens},
                                                 jnp.int32(pos))
        self.reads.append(read)
        return logits, cache

    @staticmethod
    def _argmax(logits):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]

    def _run_batch(self, prime: int, gen: int, keep: bool = True):
        """One batch: ``prime`` prompt tokens, then ``gen`` decode steps.
        Returns the host times at which each generated token arrived."""
        p = prompts(self.cfg.vocab_size, self.batch, self.prompt_len, self.seed, self.batch_index)
        self.batch_index += 1
        with jax.profiler.TraceAnnotation("bench.cache"):
            cache = self.new_cache()
        finite = jnp.bool_(True)  # every logit of every step, reduced on device
        for pos in range(prime):
            with jax.profiler.TraceAnnotation("bench.input"):
                tokens = jnp.asarray(p[:, pos:pos + 1], jnp.int32)
            logits, cache = self._step(cache, tokens, pos)
            finite = finite & jnp.isfinite(logits).all()
        tok = self._argmax(logits)
        generated, times = [], []
        for i in range(gen):
            with jax.profiler.TraceAnnotation("bench.fetch"):
                generated.append(np.asarray(tok))
            times.append(time.perf_counter())
            logits, cache = self._step(cache, tok, prime + i)
            finite = finite & jnp.isfinite(logits).all()
            tok = self._argmax(logits)
        ok = bool(finite)
        if keep:
            self.served.append((p, np.concatenate(generated, axis=1), ok))
        return times

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        gaps, batches, positions = [], 0, []
        self.reads = []
        while time.perf_counter() < deadline:
            times = self._run_batch(self.prompt_len, self.gen_len)
            batches += 1
            positions += list(range(self.prompt_len + self.gen_len))
            gaps += np.diff(times).tolist()
        elapsed = time.perf_counter() - t0
        reads = [int(r) for r in jax.device_get(self.reads)]
        requests = batches * self.batch
        tokens = requests * self.gen_len
        vocab = self.cfg.vocab_size
        failed = sum(
            self.batch if not ok else int(((g < 0) | (g >= vocab)).any(axis=1).sum())
            for _, g, ok in self.served
        )
        tpot = statistics.quantiles(gaps, n=20)[-1] * 1e3 if len(gaps) >= 2 else None
        m = self.cell.model
        return {
            "attempted": requests,
            "failed": failed,
            "metrics": {"serve_tokens_per_s": tokens / elapsed, "tpot_ms_p95": tpot},
            "steps": len(positions),
            "step_module": f"jit_{self.decode.__name__}",
            "step_flops": [flops.decode_step_flops(m, self.batch, p) for p in positions],
            "step_bytes": [flops.decode_step_bytes(m, self.batch, p, r)
                           for p, r in zip(positions, reads)],
        }

    def free(self) -> None:
        del self.params, self.decode

    def sample(self) -> list[int]:
        """Requests the check compares: ``check_requests`` of those served,
        drawn from the seed (every request is as long as the longest)."""
        n = len(self.served) * self.batch
        k = min(self.cell.traffic["check_requests"], n)
        rng = np.random.default_rng([self.seed, 7])
        return sorted(rng.choice(n, size=k, replace=False).tolist())

    def _sequences(self):
        rows, toks = [], []
        for r in self.sample():
            p, g, _ = self.served[r // self.batch]
            rows.append(np.concatenate([p[r % self.batch], g[r % self.batch, :-1]]))
            toks.append(g[r % self.batch])
        return np.stack(rows).astype(np.int32), np.stack(toks)

    def reference_logits(self, precision: str = "f32"):
        """Reference logits at every position that chose a served token:
        ``(requests * gen_len, V)``, and those tokens."""
        seqs, served = self._sequences()
        ref = self.cell.reference
        arch = ref.Arch.from_model({**self.cell.model, **self.cell.config["assumed"]}, self.ctx.ep)
        logits = ref.forward_logits(arch, self.key, jnp.asarray(seqs), precision)
        logits = np.asarray(logits[:, self.prompt_len - 1:], np.float32)
        return logits.reshape(-1, logits.shape[-1]), served.reshape(-1)

    def check(self) -> list[dict]:
        logits, served = self.reference_logits()
        return checked(serve_numbers(logits, served), self.cell.limits)
