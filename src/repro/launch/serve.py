"""Serving driver: batched prefill + autoregressive decode.

Small-scale runnable (CPU, reduced config) and production-mesh lowering
share the same step functions. Requests are batched; decode is a jit'd
single-token step donated in place. For MoE archs it prints the share of
the (layer, expert) weight sets the decode steps read (the decode MoE
kernel reads only the experts some token of the step routes to).

``--sim-fabric`` closes the loop with the RailS simulator: the decode
loop's *real* per-step expert routing counts (MoE archs; uniform synthetic
counts for dense ones) and measured step timestamps are replayed as
release-timed all-to-all rounds through
:func:`repro.sched.serving.simulate_decode_trace`, reporting the p50/p99/
p99.9 per-token fabric latency those decode batches would pay on the
chosen policy — optionally under a degraded fabric (``--sim-fault``).

``--gateway`` runs the overload-control plane instead of the model: a
synthetic request stream through :func:`repro.serve.gateway.run_gateway`
on the simulated fabric (``--slo-ms``, ``--admission-rps``,
``--brownout``, ``--gw-dead-rail``), reporting shed rate, SLO attainment
and goodput. No model or accelerator is touched in this mode.

Example:
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --reduced \
        --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
        --batch 2 --prompt-len 8 --gen 8 --sim-fabric --sim-fault degraded
    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --gateway \
        --gw-requests 2000 --admission-rps 500 --brownout --gw-dead-rail
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.steps import make_decode_step
from repro.launch.train import apply_cli_cuts, init_sharded_params, make_local_mesh
from repro.models import init_cache
from repro.parallel.mesh_view import build_mesh_context


def _sim_fault_spec(kind: str, num_rails: int):
    """The --sim-fault presets: the PR-4 fault grid's serving-path cells."""
    if kind == "none":
        return None
    from repro.netsim import FaultSpec, LossConfig, step_profile

    if kind == "loss":
        return FaultSpec(
            loss=LossConfig(rate=0.01, rto=5e-4, bad_rate=0.3,
                            p_enter_bad=0.02, p_leave_bad=0.3),
            seed=11,
        )
    if kind == "degraded":
        return FaultSpec(
            rail_profiles={num_rails - 1: step_profile(0.0, 0.25)},
            loss=LossConfig(rate=0.005, rto=5e-4, bad_rate=0.15,
                            p_enter_bad=0.02, p_leave_bad=0.3),
            seed=11,
        )
    raise ValueError(f"unknown --sim-fault {kind!r}")


def _run_sim_fabric(args, cfg, counts_per_step, releases) -> dict:
    """Replay the recorded decode trace onto the simulated rail fabric."""
    from repro.sched.serving import simulate_decode_trace

    res = simulate_decode_trace(
        counts_per_step,
        releases,
        num_domains=args.sim_domains,
        num_rails=args.sim_rails,
        bytes_per_token=float(cfg.d_model * 2),  # bf16 activations
        policy=args.sim_policy,
        fault_spec=_sim_fault_spec(args.sim_fault, args.sim_rails),
        feedback=args.sim_policy == "rails-online",
    )
    s = res.summary()
    print(
        f"sim-fabric [{args.sim_policy}, fault={args.sim_fault}, "
        f"{args.sim_domains}x{args.sim_rails}]: per-token fabric latency "
        f"p50 {s['p50'] * 1e6:.1f}us p99 {s['p99'] * 1e6:.1f}us "
        f"p99.9 {s['p99.9'] * 1e6:.1f}us"
    )
    return {"summary": s, "token_latency": res.token_latency}


def _run_gateway_mode(args) -> dict:
    """--gateway: the control plane on a synthetic stream, no model."""
    from repro.core.traffic import serve_workload
    from repro.sched.control import AdmissionConfig, BrownoutConfig, ControlConfig
    from repro.serve.gateway import run_gateway

    wl = serve_workload(
        args.sim_domains,
        args.sim_rails,
        num_requests=args.gw_requests,
        mean_gap=args.gw_mean_gap,
        seed=args.seed,
    )
    control = ControlConfig(
        slo_s=args.slo_ms * 1e-3,
        admission=(
            AdmissionConfig(rate_rps=args.admission_rps)
            if args.admission_rps > 0
            else AdmissionConfig()
        ),
        brownout=BrownoutConfig() if args.brownout else None,
    )
    fabric_schedule = None
    if args.gw_dead_rail:
        speeds = np.ones(args.sim_rails)
        speeds[-1] = 0.02  # crawling rail: the vector loop's fail-stop proxy
        fabric_schedule = [(0.0, speeds)]
    res = run_gateway(
        wl,
        args.sim_policy,
        control=control,
        fabric_schedule=fabric_schedule,
        backend="vector",
    )
    s = res.slo
    print(
        f"gateway [{args.sim_policy}, slo={args.slo_ms:.1f}ms, "
        f"dead_rail={args.gw_dead_rail}]: offered {s['offered']} "
        f"shed {s['shed']} ({s['shed_rate']:.1%}) "
        f"slo_attainment {s['slo_attainment']:.1%} "
        f"goodput {s['goodput_rps']:.1f} req/s "
        f"brownout_windows {res.brownout_windows}"
    )
    return {"gateway": res.row(), "windows": len(res.windows)}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="replace num_layers (depth only; widths kept)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--sim-fabric",
        action="store_true",
        help="replay the decode loop's routing counts + step timing onto "
        "the simulated rail fabric and report per-token p99/p99.9 latency",
    )
    ap.add_argument("--sim-domains", type=int, default=8,
                    help="fabric domains (M) for --sim-fabric")
    ap.add_argument("--sim-rails", type=int, default=8,
                    help="rails per domain (N) for --sim-fabric")
    ap.add_argument("--sim-policy", type=str, default="rails-online",
                    help="load-balancing policy for --sim-fabric")
    ap.add_argument("--sim-fault", choices=("none", "loss", "degraded"),
                    default="none",
                    help="degraded-fabric preset for --sim-fabric")
    ap.add_argument("--gateway", action="store_true",
                    help="run the serving control plane on a synthetic "
                    "request stream (no model); see --slo-ms/--admission-rps")
    ap.add_argument("--gw-requests", type=int, default=1000,
                    help="synthetic request count for --gateway")
    ap.add_argument("--gw-mean-gap", type=float, default=2e-3,
                    help="mean inter-arrival gap (s) for --gateway")
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="TTFT SLO in milliseconds for --gateway")
    ap.add_argument("--admission-rps", type=float, default=0.0,
                    help="token-bucket admission rate (req/s) for "
                    "--gateway; 0 = queue/p99 shedding only")
    ap.add_argument("--brownout", action="store_true",
                    help="enable graceful degradation for --gateway")
    ap.add_argument("--gw-dead-rail", action="store_true",
                    help="degrade the last rail to 2%% speed for --gateway")
    args = ap.parse_args(argv)

    if args.gateway:
        return _run_gateway_mode(args)

    enable_compile_cache()
    cfg = apply_cli_cuts(get_config(args.arch), args)
    mesh = make_local_mesh(cfg)
    ctx = build_mesh_context(mesh, cfg)
    max_len = args.prompt_len + args.gen

    # Real gating counts and expert reads exist only for MoE archs;
    # --sim-fabric on dense models falls back to uniform synthetic counts
    # (batch tokens spread evenly over 8 pseudo-experts) so the timing
    # replay still works.
    trace_counts = bool(cfg.num_experts)

    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(ctx.mesh):
        params, _ = init_sharded_params(cfg, ctx, key)
        decode = jax.jit(
            make_decode_step(cfg, ctx, return_counts=trace_counts),
            donate_argnums=(1,),
        )

        rng = np.random.default_rng(args.seed)
        prompts = rng.integers(2, cfg.vocab_size, size=(args.batch, args.prompt_len))
        cache = init_cache(cfg, args.batch, max_len)

        def step(logits_cache_args):
            """One decode call, normalizing the optional counts outputs."""
            out = decode(*logits_cache_args)
            if trace_counts:
                return out
            logits, new_cache = out
            return logits, new_cache, None, None

        # Prefill via repeated decode steps (token-at-a-time priming keeps
        # one compiled program; a fused prefill path exists for the dry-run).
        # Each phase's time ends when its last step's result is ready.
        logits = None
        finite = jnp.bool_(True)  # every logit of every step, reduced on device
        with obs.span("serve.prefill") as t_prefill:
            for pos in range(args.prompt_len):
                batch = {"tokens": jnp.asarray(prompts[:, pos : pos + 1], jnp.int32)}
                logits, cache, _, _ = step((params, cache, batch, jnp.int32(pos)))
                finite = finite & jnp.isfinite(logits).all()
            jax.block_until_ready(logits)

        generated = []
        experts_read = jnp.int32(0)  # (layer, expert) weight sets, summed on device
        step_counts: list[np.ndarray] = []
        step_times: list[float] = []
        with obs.span("serve.decode") as t_gen:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            for i in range(args.gen):
                generated.append(np.asarray(tok))
                step_times.append(time.time())
                logits, cache, counts, read = step(
                    (params, cache, {"tokens": tok}, jnp.int32(args.prompt_len + i))
                )
                finite = finite & jnp.isfinite(logits).all()
                if read is not None:
                    experts_read = experts_read + read
                if counts is not None and args.sim_fabric:
                    step_counts.append(np.asarray(counts))
                if args.temperature > 0:
                    key, sub = jax.random.split(key)
                    tok = jax.random.categorical(sub, logits / args.temperature)[:, None]
                else:
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            jax.block_until_ready(logits)
        t_prefill, t_gen = t_prefill.seconds, t_gen.seconds

    out_tokens = np.concatenate(generated, axis=1)
    tput = args.batch * args.gen / t_gen if t_gen > 0 else 0.0
    print(f"prefill {args.prompt_len} tok x{args.batch}: {t_prefill:.2f}s")
    print(f"decode {args.gen} tok x{args.batch}: {t_gen:.2f}s  ({tput:.1f} tok/s)")
    print("sample:", out_tokens[0][:12])
    result = {
        "tokens": out_tokens,
        "tput": tput,
        "logits_finite": bool(finite),
    }
    if trace_counts and args.gen > 0:
        held = args.gen * cfg.num_layers * cfg.num_experts
        share = int(experts_read) / held
        print(f"experts read over decode: {share:.3f} of {cfg.num_layers} layers x "
              f"{cfg.num_experts} experts per step "
              f"({share * cfg.num_experts:.2f} of {cfg.num_experts} per layer)")
        result["experts_read_share"] = share
    if args.sim_fabric and args.gen > 0:
        if not step_counts:
            # Dense arch: uniform synthetic routing (the step's batch
            # tokens spread evenly over enough pseudo-experts to cover
            # every fabric domain), real cadence.
            k = max(8, args.sim_domains)
            step_counts = [np.full(k, args.batch / k) for _ in step_times]
        result["sim_fabric"] = _run_sim_fabric(
            args, cfg, step_counts, np.asarray(step_times)
        )
    return result


if __name__ == "__main__":
    main()
