"""End-to-end training driver.

Runs the real thing at any scale: on a laptop/CI (``--reduced``, 1 CPU
device) or on the production mesh (``--production``). Wires together data
pipeline, mesh view, sharded train step, async checkpointing and restart.

Example (CPU, ~100M-param class run):
    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``--layers N`` cuts depth only (published widths kept), so one layer stack
of a full-width config fits a chip. On a local mesh, MoE configs lay every
device on the ``model`` axis, so the mesh view gives them expert
parallelism (e.g. four chips hold two Mixtral experts each).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.checkpoint import Checkpointer, latest_step, restore
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import ModelConfig, ShapeSpec
from repro.data import DataConfig, SyntheticTokens
from repro.launch.mesh import make_production_mesh
from repro.compat import make_mesh as compat_make_mesh
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import AdamWConfig, adamw_init, warmup_cosine
from repro.parallel.mesh_view import build_mesh_context
from repro.parallel.sharding import (
    batch_pspecs,
    opt_state_pspecs,
    param_shardings,
    to_shardings,
)


def make_local_mesh(cfg: ModelConfig):
    """All local devices on ``data`` — or on ``model`` for MoE configs, so
    the mesh view can split them into expert-parallel shards."""
    n = len(jax.devices())
    shape = (1, n) if cfg.is_moe else (n, 1)
    return compat_make_mesh(shape, ("data", "model"))


def apply_cli_cuts(cfg: ModelConfig, args) -> ModelConfig:
    """``--reduced`` (smoke widths) and ``--layers`` (depth only)."""
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        print(f"reduced: num_layers {cfg.num_layers}->{args.layers}")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def init_sharded_params(cfg: ModelConfig, ctx, key):
    """Parameters made on the devices in their final shardings (one jitted
    call, so no full-size f32 initializer temporaries and no host copy)."""
    abstract = jax.eval_shape(lambda: init_params(cfg, key))
    shardings = param_shardings(cfg, ctx, abstract)
    init = jax.jit(lambda k: init_params(cfg, k), out_shardings=shardings)
    return init(key), shardings


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="replace num_layers (depth only; widths kept)")
    ap.add_argument("--dispatch-mode", choices=("dense", "ring", "rails", "spray"),
                    default=None,
                    help="override the config's MoE all-to-all dispatch")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--sched-replay",
        action="store_true",
        help="feed per-iteration MoE gating counts to the repro.sched "
        "routing-replay planner and log its all-to-all forecast",
    )
    ap.add_argument("--sched-domains", type=int, default=8,
                    help="fabric domains (M) for the --sched-replay planner")
    ap.add_argument("--sched-rails", type=int, default=8,
                    help="rails per domain (N) for the --sched-replay planner")
    ap.add_argument(
        "--placement",
        choices=["static", "greedy", "lp", "online"],
        default="static",
        help="expert layout for the --sched-replay planner: static "
        "round-robin, a one-shot greedy/LP re-layout planned after "
        "--placement-warmup steps, or the online drift-triggered "
        "migration controller (repro.placement)",
    )
    ap.add_argument(
        "--placement-warmup", type=int, default=10,
        help="gating-count steps accumulated before a one-shot "
        "greedy/lp re-layout is planned",
    )
    ap.add_argument(
        "--fail-at", type=int, default=None,
        help="fail-stop drill: at this step the scheduler is told rail "
        "--fail-rail died (plan cache flushed, next plans over N-1 "
        "rails), and after the loop a full inject→detect→re-spray→"
        "evacuate drill (repro.runtime.failover) reports time-to-detect/"
        "recover and the degraded-CCT ratio",
    )
    ap.add_argument("--fail-rail", type=int, default=1,
                    help="rail index the --fail-at drill kills")
    ap.add_argument(
        "--fail-kind", choices=["rail", "nic", "node"], default="rail",
        help="fail-stop flavor for the --fail-at drill (node drills add "
        "expert evacuation + elastic re-mesh + supervisor rollback legs)",
    )
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = apply_cli_cuts(get_config(args.arch), args)
    if args.dispatch_mode:
        cfg = dataclasses.replace(cfg, dispatch_mode=args.dispatch_mode)
    mesh = (
        make_production_mesh(multi_pod=args.multipod)
        if args.production
        else make_local_mesh(cfg)
    )
    ctx = build_mesh_context(mesh, cfg)
    shape = ShapeSpec("cli", args.seq, args.batch, "train", args.microbatches)

    opt_cfg = AdamWConfig(
        learning_rate=warmup_cosine(args.lr, min(100, args.steps // 10 + 1), args.steps)
    )
    step_fn = make_train_step(cfg, ctx, shape, opt_cfg)

    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(ctx.mesh):
        params, p_sh = init_sharded_params(cfg, ctx, key)
        o_sh = to_shardings(ctx, opt_state_pspecs(cfg, ctx, params))
        opt_state = jax.jit(adamw_init, out_shardings=o_sh)(params)
        b_specs = batch_pspecs(cfg, ctx, shape)
        b_sh = to_shardings(ctx, {k: b_specs[k] for k in ("tokens", "labels")})
        jit_step = jax.jit(
            step_fn,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, NamedSharding(ctx.mesh, P())),
            donate_argnums=(0, 1),
        )
    print(
        f"mesh {dict(ctx.mesh.shape)} ep={ctx.ep} "
        f"dispatch={cfg.dispatch_mode if cfg.is_moe else 'n/a'}"
    )

    data = SyntheticTokens(
        DataConfig(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    )
    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = Checkpointer(args.ckpt_dir)
        if latest_step(args.ckpt_dir) is not None:
            (params, opt_state), start_step = restore(
                args.ckpt_dir, (params, opt_state)
            )
            print(f"restored from step {start_step}")

    # Online-scheduling hook: each iteration's gating counts feed the
    # routing-replay planner, which forecasts and LPT-plans the *next*
    # iteration's expert all-to-all (repro.sched control plane).
    sched_hook = None
    placement_state = None  # (method, warmup_sum) until the one-shot re-layout
    if args.sched_replay and cfg.num_experts:
        from repro.sched import GatingFeedbackHook

        bytes_per_token = float(cfg.d_model * 2)  # bf16 activations
        # One expert's parameter footprint: w1/w2/w3 of the FFN, bf16.
        expert_bytes = float(3 * cfg.d_model * cfg.moe_d_ff * 2)
        controller = None
        if args.placement == "online":
            from repro.placement import OnlinePlacementController, Placement

            controller = OnlinePlacementController(
                Placement.round_robin(
                    cfg.num_experts, args.sched_domains, expert_bytes
                ),
                num_rails=args.sched_rails,
                bytes_per_token=bytes_per_token,
            )
        elif args.placement in ("greedy", "lp"):
            placement_state = (args.placement, expert_bytes, None)
        sched_hook = GatingFeedbackHook(
            num_domains=args.sched_domains,
            num_rails=args.sched_rails,
            bytes_per_token=bytes_per_token,
            controller=controller,
        )

    def compile_s() -> float:
        return sum(t.seconds for t in obs.compiles().values())

    losses = []
    compile0 = compile_s()
    t0 = first = None  # step time is counted from the end of the first step
    with jax.set_mesh(ctx.mesh):
        for step in range(start_step, args.steps):
            batch = {k: jax.numpy.asarray(v) for k, v in data.batch(step).items()}
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if t0 is None:
                jax.block_until_ready(metrics)
                t0, first = time.perf_counter(), step
            if (
                args.fail_at is not None
                and step == args.fail_at
                and sched_hook is not None
                and args.fail_kind != "node"
            ):
                # The control-plane half of the drill, live: the watchdog
                # verdict reaches the planner, which drops cached plans
                # and LPT-plans every later iteration over the survivors.
                sched_hook.on_rail_failure([args.fail_rail])
                print(
                    f"  failover: rail {args.fail_rail} marked dead at step "
                    f"{step} — plan cache flushed, planning over "
                    f"{int(sched_hook.survivor_mask.sum())} rails"
                )
            if sched_hook is not None and "moe_counts" in metrics:
                counts = np.asarray(metrics["moe_counts"], dtype=np.float64)
                if placement_state is not None:
                    # One-shot greedy/LP re-layout: accumulate gating counts
                    # through the warmup, then fix the searched placement.
                    method, expert_bytes, acc = placement_state
                    acc = counts if acc is None else acc + counts
                    placement_state = (method, expert_bytes, acc)
                    if step - start_step + 1 >= args.placement_warmup:
                        from repro.placement import Placement, search_placement

                        cand = search_placement(
                            acc, args.sched_domains, args.sched_rails,
                            sched_hook.bytes_per_token, method=method,
                            weight_bytes=expert_bytes, score=False,
                        ).placement
                        _, mig_bytes = Placement.round_robin(
                            cfg.num_experts, args.sched_domains, expert_bytes
                        ).migration_to(cand)
                        sched_hook.placement = cand
                        placement_state = None
                        print(
                            f"  placement[{method}]: re-layout after "
                            f"{args.placement_warmup} steps, migrating "
                            f"{mig_bytes / 2**20:.1f}MiB of expert weights"
                        )
                plan = sched_hook.on_step(counts)
                if plan["migrated"]:
                    print(
                        f"  placement[online]: migrated "
                        f"{plan['migration_bytes'] / 2**20:.1f}MiB at step {step}"
                    )
                if step % args.log_every == 0:
                    print(
                        f"  a2a plan: chunk {plan['chunk_bytes'] / 2**20:.2f}MiB "
                        f"send_mse {plan['pred_send_mse']:.2e} "
                        f"opt {plan['opt_time_s'] * 1e3:.2f}ms "
                        f"fc_err {plan['forecast_err']:.2f}"
                    )
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                losses.append((step, loss))
                timed = step - first
                pace = (f"{(time.perf_counter() - t0) / timed * 1e3:.1f} ms/step"
                        if timed else "first step")
                print(
                    f"step {step:5d} loss {loss:8.4f} nll {float(metrics['nll']):7.4f} "
                    f"gnorm {float(metrics['grad_norm']):7.3f} "
                    f"({pace}, compile {compile_s() - compile0:.1f}s)"
                )
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save_async(step, (params, opt_state))
        if ckpt:
            ckpt.wait()
            ckpt.save_async(args.steps, (params, opt_state))
            ckpt.wait()
    result = {
        "losses": losses,
        "final_loss": losses[-1][1] if losses else None,
        "ep": ctx.ep,
    }
    if args.fail_at is not None:
        # Data-plane half of the drill on a reference 4x4 fabric (the
        # full sched fabric would take minutes of DES for no extra
        # signal): inject -> silence-detect -> re-spray -> evacuate.
        from repro.runtime.failover import run_failover_drill

        m = min(args.sched_domains, 4)
        n = min(args.sched_rails, 4)
        report = run_failover_drill(
            num_domains=m,
            num_rails=n,
            fail_kind=args.fail_kind,
            fail_rail=args.fail_rail % n if args.fail_kind != "node" else None,
            fail_domain=m - 1 if args.fail_kind in ("nic", "node") else None,
        )
        ttd = report.time_to_detect
        print(
            f"failover drill [{args.fail_kind}]: "
            f"detect {'n/a' if ttd is None else f'{ttd * 1e3:.3f}ms'} "
            f"recover {report.time_to_recover * 1e3:.3f}ms "
            f"degraded-CCT x{report.degraded_ratio:.3f} of bound "
            f"(tracking x{report.bound_tracking_ratio:.3f}) "
            f"exactly_once={report.exactly_once}"
        )
        if report.evacuation_bytes:
            print(
                f"  evacuated {report.evacuated_experts} experts, "
                f"{report.evacuation_bytes / 2**20:.1f}MiB over survivors; "
                f"remesh feasible={report.elastic.feasible}"
            )
        result["failover_drill"] = report
    return result


if __name__ == "__main__":
    main()
