#!/usr/bin/env python3
"""Smoke run of the system's accelerator paths on a TPU, through the
entry points a user calls, at Mixtral-8x7B's published widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with four chips

One chip runs three phases:

* ``kernels`` — the Pallas flash-attention and RMSNorm kernels, forward
  and custom-VJP backward, against the ``ref.py`` oracles at Mixtral's
  head widths;
* ``serve`` — ``repro.launch.serve.main`` on ``mixtral-8x7b`` cut in depth
  only (``--layers``), decoding a few batched requests; every token id
  must lie in ``[0, vocab)`` and every logit must be finite;
* ``simulator`` — one RailS all-to-all on the paper's 512-NIC fabric
  (64 domains x 8 rails) under seeded Mixtral-gated traffic through
  ``run_collective(backend="device")``, and one batched policy suite,
  each against ``backend="vector"`` under the device backend's tolerance
  contract.

``--four-chips`` runs only expert-parallel Mixtral training through
``repro.launch.train.main`` (ep = 4, two experts per chip), once with the
``rails`` all-to-all and once with ``dense``; both move the same blocks,
so their losses must agree at every step.

The script refuses any device that is not a TPU, catches no phase's
failure, and prints as its last line one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = "mixtral-8x7b"
SEED = 0

# Depth cuts, sized from the compiled programs' memory_analysis for v5e
# (16 GB of HBM per chip). Serving: 3 layers hold 9.2 GB of bf16 weights
# and their initializer peaks at 13.0 GB (4 layers would peak at 15.9).
# Training, two experts per chip: 2 layers at batch 8 x 1024 tokens take
# 12.5 GB per chip.
SERVE_LAYERS = 3
TRAIN_LAYERS = 2
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 3

# Device-simulator buckets (padded chunk counts, powers of two). The
# scan's 5-key f64 sort sets the cold TPU compile time, which grows about
# fourfold per doubling past 2^12 chunks, and fourfold again under vmap.
# The single collective takes the largest bucket that compiles within a
# few minutes; the policy suite (five members in one vmap-ed call) runs
# on an 8x8 fabric at a bucket that compiles in well under a minute.
SIM_BUCKET = 2**14
SUITE_FABRIC = (8, 8)
SUITE_BUCKET = 2**10
SUITE_POLICIES = ("ecmp", "plb", "minrtt", "reps", "rails")

# Tolerance contract of the device simulator against the vector backend
# (netsim/devicesim.py, tests/test_devicesim.py): makespans agree to
# 1e-12. Under ``rails`` the CCT tail (p95 and up) agrees to 1e-9; the
# mid-distribution statistics, and every statistic of the spine
# policies, agree to 2e-2, because equal-size chunk waves tie (every
# sender's chunks for one expert converge on one NIC) and the device
# scan may serve a tie in another order, which moves a flow's finish by
# a service quantum but no link's schedule.
MAKESPAN_RTOL = 1e-12
RAILS_TAIL_RTOL = 1e-9
RAILS_TAIL = ("p95", "p99", "p99.9", "max")
TIE_CCT_RTOL = 2e-2

BF16_RTOL = 2**-7  # two bf16 ulps


class PhaseFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailure(msg)


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's own events."""

    def __init__(self):
        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), secs))

    def since(self, mark: int) -> list[tuple[str, float]]:
        return self.events[mark:]


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def phase_kernels() -> None:
    from repro.configs import get_config
    from repro.kernels import kernel_backend
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ref import flash_attention_ref, rmsnorm_ref
    from repro.kernels.rmsnorm import rmsnorm_pallas

    check(kernel_backend() == "pallas", f"kernel backend {kernel_backend()!r}")
    cfg = get_config(ARCH)
    b, t = 1, 512
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(SEED), 6)
    q = jax.random.normal(keys[0], (b, t, h, hd), jnp.float32)
    k = jax.random.normal(keys[1], (b, t, hkv, hd), jnp.float32)
    v = jax.random.normal(keys[2], (b, t, hkv, hd), jnp.float32)
    probe = jax.random.normal(keys[3], (b, t, h, hd), jnp.float32)
    window = cfg.sliding_window

    def attn_loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * probe), argnums=(0, 1, 2)
        ))

    got = attn_loss(lambda q, k, v: flash_attention_pallas(q, k, v, window=window))(q, k, v)
    want = attn_loss(lambda q, k, v: flash_attention_ref(q, k, v, window=window))(q, k, v)
    errs = [rel_err(g, w) for g, w in zip([got[0], *got[1]], [want[0], *want[1]])]
    print(f"kernels: flash attention B{b} T{t} H{h}/{hkv} hd{hd} rel err "
          f"loss {errs[0]:.2e} dq {errs[1]:.2e} dk {errs[2]:.2e} dv {errs[3]:.2e}")
    check(all(e < 2e-2 for e in errs), f"flash attention vs ref: {errs}")

    x = jax.random.normal(keys[4], (2048, cfg.d_model), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(keys[5], (cfg.d_model,), jnp.float32)
    probe = jax.random.normal(keys[3], x.shape, jnp.float32)

    def norm_loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, w: jnp.sum(fn(x, w, cfg.rms_eps) * probe), argnums=(0, 1)
        ))

    got = norm_loss(rmsnorm_pallas)(x, w)
    want = norm_loss(rmsnorm_ref)(x, w)
    errs = [rel_err(g, r) for g, r in zip([got[0], *got[1]], [want[0], *want[1]])]
    print(f"kernels: rmsnorm {x.shape[0]}x{x.shape[1]} rel err loss {errs[0]:.2e} "
          f"dx {errs[1]:.2e} dw {errs[2]:.2e}")
    check(all(e < 1e-3 for e in errs), f"rmsnorm vs ref: {errs}")


def phase_serve() -> None:
    from repro.configs import get_config
    from repro.launch import serve

    cfg = get_config(ARCH)
    print(f"serve: {ARCH} d_model {cfg.d_model} heads {cfg.num_heads}/"
          f"{cfg.num_kv_heads} head_dim {cfg.head_dim} experts "
          f"{cfg.num_experts} top-{cfg.experts_per_token} moe_d_ff "
          f"{cfg.moe_d_ff} vocab {cfg.vocab_size}")
    batch, gen = 4, 16
    out = serve.main([
        "--arch", ARCH, "--layers", str(SERVE_LAYERS), "--batch", str(batch),
        "--prompt-len", "32", "--gen", str(gen), "--seed", str(SEED),
    ])
    tokens = out["tokens"]
    check(tokens.shape == (batch, gen), f"tokens shape {tokens.shape}")
    check(bool(((tokens >= 0) & (tokens < cfg.vocab_size)).all()),
          "generated token id outside [0, vocab)")
    check(out["logits_finite"], "non-finite logits")
    print(f"serve: {batch}x{gen} tokens in [0, {cfg.vocab_size}), all logits finite")


def _planned_chunks(tm, chunk_bytes) -> int:
    from repro.netsim import build_job_arrays

    return int(build_job_arrays(tm, chunk_bytes).size.size)


def _fit_chunk_bytes(tm, bucket: int) -> tuple[float, int]:
    """Chunk size whose chunk count fills most of ``bucket`` without
    spilling into the next one."""
    from repro.netsim.devicesim import bucket_size

    target = int(bucket * 0.9)
    while True:
        chunk_bytes = tm.total_bytes() / target
        chunks = _planned_chunks(tm, chunk_bytes)
        if bucket_size(chunks) == bucket:
            return chunk_bytes, chunks
        check(target > bucket // 4, f"no chunk size fits bucket {bucket}")
        target = int(target * 0.9)


def _compare(name, policy, dev, vec) -> None:
    ms = abs(dev.makespan / vec.makespan - 1.0)
    cct = {k: abs(dev.cct[k] / v - 1.0) for k, v in vec.cct.items()}
    limit = {
        k: RAILS_TAIL_RTOL if policy == "rails" and k in RAILS_TAIL else TIE_CCT_RTOL
        for k in cct
    }
    print(f"simulator: {name} makespan device {dev.makespan!r} vector "
          f"{vec.makespan!r} rel {ms:.3e} (limit {MAKESPAN_RTOL:g})")
    print(f"simulator: {name} CCT p99 device {dev.cct['p99']!r} vector "
          f"{vec.cct['p99']!r}; rel per stat (limit) "
          + " ".join(f"{k} {e:.2e} ({limit[k]:g})" for k, e in cct.items()))
    check(ms <= MAKESPAN_RTOL, f"{name}: makespan rel {ms:.3e}")
    check(all(cct[k] <= limit[k] for k in cct), f"{name}: CCT rel {cct}")


def phase_simulator(compiles: CompileLog) -> None:
    from repro.core.traffic import mixtral_trace_workload
    from repro.netsim import run_collective, run_policy_suite

    # The paper's sparse Mixtral setup: each expert's payload lands on one
    # GPU of its domain, on 64 domains x 8 rails = 512 NICs.
    tm = mixtral_trace_workload(64, 8, mode="sparse", seed=SEED)

    chunk_bytes, chunks = _fit_chunk_bytes(tm, SIM_BUCKET)
    print(f"simulator: 512 NICs (64x8), {chunks} chunks of "
          f"{chunk_bytes:.0f} B, bucket {SIM_BUCKET}")
    vec = run_collective(tm, "rails", chunk_bytes=chunk_bytes, backend="vector")
    mark = len(compiles.events)
    t0 = time.perf_counter()
    dev = run_collective(tm, "rails", chunk_bytes=chunk_bytes, backend="device")
    cold = time.perf_counter() - t0
    for fun, secs in compiles.since(mark):
        print(f"simulator: compile bucket {SIM_BUCKET} {fun}: {secs:.1f} s")
    print(f"simulator: first device call (compile + run) {cold:.1f} s")
    _compare("rails", "rails", dev, vec)

    tm = mixtral_trace_workload(*SUITE_FABRIC, mode="sparse", seed=SEED)
    chunk_bytes, chunks = _fit_chunk_bytes(tm, SUITE_BUCKET)
    mark = len(compiles.events)
    dev = run_policy_suite(tm, SUITE_POLICIES, chunk_bytes=chunk_bytes,
                           seed=SEED, backend="device")
    for fun, secs in compiles.since(mark):
        print(f"simulator: compile bucket {SUITE_BUCKET} {fun}: {secs:.1f} s")
    vec = run_policy_suite(tm, SUITE_POLICIES, chunk_bytes=chunk_bytes,
                           seed=SEED, backend="vector")
    print(f"simulator: policy suite on {SUITE_FABRIC[0]}x{SUITE_FABRIC[1]}, "
          f"{len(SUITE_POLICIES)} x {chunks} chunks, one batched device call")
    for p in SUITE_POLICIES:
        _compare(f"suite/{p}", p, dev[p], vec[p])


def phase_train_four_chips() -> None:
    from repro.launch import train

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, need 4")
    losses = {}
    for mode in ("rails", "dense"):
        out = train.main([
            "--arch", ARCH, "--layers", str(TRAIN_LAYERS),
            "--dispatch-mode", mode, "--steps", str(TRAIN_STEPS),
            "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
            "--microbatches", "1", "--log-every", "1", "--seed", str(SEED),
        ])
        check(out["ep"] == 4, f"expert parallelism {out['ep']}, need 4")
        losses[mode] = np.array([loss for _, loss in out["losses"]])
        check(losses[mode].size == TRAIN_STEPS, f"{mode}: losses {losses[mode]}")
        check(bool(np.isfinite(losses[mode]).all()), f"{mode}: non-finite loss")
    rel = np.abs(losses["rails"] - losses["dense"]) / np.abs(losses["dense"])
    print(f"train: ep 4, losses rails {losses['rails'].tolist()} dense "
          f"{losses['dense'].tolist()} max rel diff {rel.max():.3e} "
          f"(limit {BF16_RTOL:g})")
    check(bool((rel <= BF16_RTOL).all()), "rails and dense losses disagree")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only expert-parallel training on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog()
    if args.four_chips:
        phases = [("train", phase_train_four_chips)]
    else:
        phases = [
            ("kernels", phase_kernels),
            ("serve", phase_serve),
            ("simulator", lambda: phase_simulator(compiles)),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        print(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
