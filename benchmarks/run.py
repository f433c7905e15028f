"""Benchmark harness — one entry per paper table/figure + online scheduling.

Prints ``name,us_per_call,derived`` CSV rows. ``us_per_call`` is the wall
time of one simulated collective (or scheduler call); ``derived`` is the
paper-relevant metric for that figure (normalized BusBw, CCT reduction,
MSE, speedup, ...). The ``bench_online_*`` entries exercise the streaming
control plane (`repro.sched`): bursty micro-batch arrivals, degraded-rail
feedback, routing replay under gating drift, and the windowed re-planning
sweep. ``bench_scale`` drives 64→512-node fabrics at up to 10⁵ chunks —
the perf trajectory for the "fast as the hardware allows" north star.

``--json PATH`` additionally writes every row (plus environment metadata)
as machine-readable JSON — CI uploads ``BENCH_netsim.json`` per PR so the
perf trajectory accumulates across the repo's history.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run --only fig7
    PYTHONPATH=src python -m benchmarks.run --quick    # CI smoke scale
    PYTHONPATH=src python -m benchmarks.run --quick --json BENCH_netsim.json
"""

from __future__ import annotations

import argparse
import datetime
import json
import platform
import subprocess
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.lpt import lpt_schedule, lpt_schedule_reference
from repro.core.lp import closed_form_opt, solve_minmax_lp
from repro.core.theorems import theorem2_optimal_time
from repro.core.traffic import (
    TrafficMatrix,
    rl_phase_counts,
    uniform_workload,
)
from repro.netsim import (
    FaultSpec,
    FecConfig,
    LinkIndex,
    LossConfig,
    MultiPodFabric,
    build_job_arrays,
    make_policy,
    run_collective,
    run_policy_suite,
    run_streaming_collective,
    step_profile,
)
from repro.placement import Placement
from repro.sched import RoutingReplayState, run_pipeline

from . import paper_workloads as W

#: Rows accumulated for --json output: (name, us_per_call, derived).
_ROWS: list[dict] = []


#: bench_scale backend selection (``--backend``): "both" (event+vector),
#: "event", "vector", or "device" (jax backend vs vector reference).
_BACKEND = "both"


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def _emit(
    name: str,
    us: float,
    derived: str,
    *,
    bench: str | None = None,
    backend: str | None = None,
    size: int | None = None,
) -> None:
    """Print a CSV row and record it for ``--json``.

    ``bench``/``backend``/``size`` are structured keys for the perf
    trajectory (``scripts/perf_report.py`` keys rows on them so event and
    vector measurements of one bench never overwrite each other).
    """
    print(f"{name},{us:.1f},{derived}")
    row = {"name": name, "us_per_call": round(us, 1), "derived": derived}
    if bench is not None:
        row["bench"] = bench
    if backend is not None:
        row["backend"] = backend
    if size is not None:
        row["size"] = size
    _ROWS.append(row)


def _write_json(path: str, quick: bool, only: str | None) -> None:
    try:
        git_rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        git_rev = None
    doc = {
        "schema": 1,
        "generated_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "quick": quick,
        "only": only,
        "git_rev": git_rev,
        "python": platform.python_version(),
        "numpy": np.__version__,
        "machine": platform.machine(),
        "rows": _ROWS,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"# wrote {len(_ROWS)} rows to {path}")


def bench_fig7_9_uniform() -> None:
    """Figs 7a/8a/9a: normalized BusBw + CCT under uniform load."""
    tm = W.uniform()
    res, us = _timed(lambda: run_policy_suite(tm, chunk_bytes=W.CHUNK))
    base = res["ecmp"]
    for p, m in res.items():
        _emit(f"fig7a_busbw_{p}", us / len(res), f"{m.bus_bw / base.bus_bw:.3f}x_ecmp")
        _emit(f"fig9a_cct_p99_{p}", us / len(res), f"{m.cct['p99'] / res['rails'].cct['p99']:.3f}x_rails")


def bench_fig7_9_sparse() -> None:
    """Figs 7b-e/8/9: sparsity sweep — RailS advantage grows with sparsity."""
    for sp in (0.6, 0.2) if W.QUICK else (0.6, 0.4, 0.2, 0.0):
        tm = W.sparse(sp)
        res, us = _timed(lambda tm=tm: run_policy_suite(tm, chunk_bytes=W.CHUNK))
        best_other = max(
            res[p].bus_bw for p in ("ecmp", "minrtt", "plb", "reps")
        )
        _emit(
            f"fig7_sparse{sp:g}_rails_busbw_gain",
            us / 5,
            f"{(res['rails'].bus_bw / best_other - 1) * 100:.1f}pct_over_best_baseline",
        )
        _emit(
            f"fig9_sparse{sp:g}_rails_cct_cut_vs_ecmp",
            us / 5,
            f"{(1 - res['rails'].cct['p99'] / res['ecmp'].cct['p99']) * 100:.1f}pct",
        )


def bench_fig10_sender_skew() -> None:
    tm = W.sender_skew()
    res, us = _timed(lambda: run_policy_suite(tm, chunk_bytes=W.CHUNK))
    for p, m in res.items():
        _emit(f"fig10b_send_mse_{p}", us / 5, f"{m.send_mse:.4f}")
    _emit(
        "fig10a_rails_busbw_vs_ecmp", us / 5,
        f"{res['rails'].bus_bw / res['ecmp'].bus_bw:.2f}x",
    )
    _emit(
        "fig10d_rails_cct_cut", us / 5,
        f"{(1 - res['rails'].cct['p99'] / res['ecmp'].cct['p99']) * 100:.1f}pct",
    )


def bench_fig11_receiver_skew() -> None:
    tm = W.receiver_skew()
    res, us = _timed(lambda: run_policy_suite(tm, chunk_bytes=W.CHUNK))
    for p, m in res.items():
        _emit(f"fig11c_recv_mse_{p}", us / 5, f"{m.recv_mse:.4f}")
    _emit(
        "fig11a_rails_busbw_vs_ecmp", us / 5,
        f"{res['rails'].bus_bw / res['ecmp'].bus_bw:.2f}x",
    )
    _emit(
        "fig11d_rails_cct_cut", us / 5,
        f"{(1 - res['rails'].cct['p99'] / res['ecmp'].cct['p99']) * 100:.1f}pct",
    )


def bench_fig12_13_mixtral() -> None:
    """Figs 12/13: Mixtral trace, dense + sparse setups, 4 phases."""
    for mode in ("dense", "sparse"):
        for phase in ("start", "early", "mid", "stable"):
            # Iteration time == the all-to-all barrier == makespan (the
            # paper's Figs 12b/13b metric); mean over 3 trace seeds.
            cuts_best, cuts_worst, us_tot = [], [], 0.0
            for seed in (2,) if W.QUICK else (2, 3, 4):
                tm = W.mixtral(phase, mode, seed=seed)
                res, us = _timed(lambda tm=tm: run_policy_suite(tm, chunk_bytes=W.CHUNK))
                us_tot += us
                others = [res[p].makespan for p in ("ecmp", "minrtt", "plb", "reps")]
                cuts_best.append((1 - res["rails"].makespan / min(others)) * 100)
                cuts_worst.append((1 - res["rails"].makespan / max(others)) * 100)
            _emit(
                f"fig{12 if mode == 'dense' else 13}_{phase}_rails_iter_cut",
                us_tot / 15,
                f"{np.mean(cuts_best):.1f}to{np.mean(cuts_worst):.1f}pct",
            )


def _time_sched(fn, w, n, reps):
    fn(w, n)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        res = fn(w, n)
    return res, (time.perf_counter() - t0) / reps * 1e6


def bench_lpt_scheduler() -> None:
    """Algorithm-2 microbenchmark: fast path vs the naive O(F·N) loop.

    ``lpt_sched_F*_N*`` rows use equal-size chunks (the common case —
    ``split_message`` cuts messages into equal atomic chunks): the fast
    path is closed-form round-robin there. ``lpt_sched_mixed_*`` rows use
    heterogeneous (exponential) weights, exercising the heap path.
    """
    rng = np.random.default_rng(0)
    cases = ((1000, 8), (10_000, 64)) if W.QUICK else (
        (1000, 8), (10_000, 64), (100_000, 512)
    )
    for f, n in cases:
        reps = max(1, 20_000 // f)
        w_eq = np.full(f, 4.0 * 2**20)
        res, us = _time_sched(lpt_schedule, w_eq, n, reps)
        _, us_ref = _time_sched(lpt_schedule_reference, w_eq, n, reps)
        _emit(
            f"lpt_sched_F{f}_N{n}", us,
            f"speedup={us_ref / us:.1f}x_vs_reference_mse={res.mse:.3e}",
        )
        w_mix = rng.exponential(1.0, f)
        res, us = _time_sched(lpt_schedule, w_mix, n, reps)
        _, us_ref = _time_sched(lpt_schedule_reference, w_mix, n, reps)
        _emit(
            f"lpt_sched_mixed_F{f}_N{n}", us,
            f"speedup={us_ref / us:.1f}x_vs_reference_mse={res.mse:.3e}",
        )


def bench_lp_solver() -> None:
    """Eq.-24 simplex vs Theorem-3 closed form (validation + timing)."""
    rng = np.random.default_rng(1)
    d2 = rng.uniform(0, 10, (4, 4))
    np.fill_diagonal(d2, 0)
    (p, t_lp, sol), us = _timed(lambda: solve_minmax_lp(d2, 4))
    _, t_cf = closed_form_opt(d2, 4)
    _emit("lp_eq24_simplex_M4N4", us, f"gap_vs_closed_form={abs(t_lp - t_cf):.2e}")


def bench_theorem_bounds() -> None:
    """Theorem-4 bound tightness across skew levels."""
    rng = np.random.default_rng(2)
    for alpha in (0.5, 1.0, 2.0):
        w = rng.zipf(1.0 + alpha, 2000).astype(float)
        res, us = _timed(lambda w=w: lpt_schedule(w, 8))
        _emit(
            f"thm4_mse_over_bound_zipf{alpha:g}", us,
            f"{res.mse / (w.max() ** 2):.2e}",
        )


def bench_online_microbatch() -> None:
    """Streaming micro-batches with bursty releases: the online regime's
    headline — proactive rails-online vs the reactive baselines."""
    rounds = 3 if W.QUICK else 6
    tms = W.micro_stream(num_microbatches=rounds, seed=1)
    # Gaps at half each round's optimal drain time: rounds overlap.
    mean_gap = 0.5 * theorem2_optimal_time(tms[0].d2, W.N, 50e9)
    releases = W.bursty_releases(rounds, mean_gap, seed=2)
    stream = list(zip(releases, tms))
    results, times = {}, {}
    for pol in ("rails-online", "minrtt", "reps"):
        res, us = _timed(
            lambda pol=pol: run_streaming_collective(stream, pol, chunk_bytes=W.CHUNK)
        )
        results[pol], times[pol] = res, us
    rails = results["rails-online"].metrics
    for pol in ("minrtt", "reps"):
        m = results[pol].metrics
        _emit(
            f"online_microbatch_rails_cct_vs_{pol}",
            times[pol],
            f"{rails.makespan / m.makespan:.3f}x_{pol}",
        )
    _emit(
        "online_microbatch_rails_recv_mse",
        times["rails-online"],
        f"{rails.recv_mse:.4f}",
    )
    # Flowlet-coalescing error bound (ROADMAP): measured CCT drift of the
    # coalesced event engine vs the exact vector-backend result on the
    # same release-driven stream.
    exact, us_x = _timed(
        lambda: run_streaming_collective(
            stream, "rails-online", chunk_bytes=W.CHUNK, backend="vector"
        )
    )
    coal, us_c = _timed(
        lambda: run_streaming_collective(
            stream, "rails-online", chunk_bytes=W.CHUNK, coalesce=True
        )
    )
    _emit(
        "online_microbatch_coalesce_drift", us_c,
        f"makespan_drift="
        f"{abs(coal.metrics.makespan / exact.metrics.makespan - 1) * 100:.2f}pct"
        f"_p99_drift="
        f"{abs(coal.metrics.cct['p99'] / exact.metrics.cct['p99'] - 1) * 100:.2f}pct"
        f"_speedup={us_x / us_c:.1f}x_vs_vector_exact",
        bench="online_coalesce_drift", backend="event",
    )


def bench_online_degraded() -> None:
    """Degraded rail: EWMA health feedback pre-charges the online LPT."""
    rounds = 3 if W.QUICK else 6
    tms = W.micro_stream(num_microbatches=rounds, seed=3)
    mean_gap = 0.5 * theorem2_optimal_time(tms[0].d2, W.N, 50e9)
    releases = W.bursty_releases(rounds, mean_gap, seed=4)
    stream = list(zip(releases, tms))
    speeds = [1.0] * (W.N - 1) + [0.4]
    blind, us_b = _timed(
        lambda: run_streaming_collective(
            stream, "rails-online", chunk_bytes=W.CHUNK, rail_speeds=speeds
        )
    )
    fb, us_f = _timed(
        lambda: run_streaming_collective(
            stream, "rails-online", chunk_bytes=W.CHUNK, rail_speeds=speeds,
            feedback=True,
        )
    )
    _emit(
        "online_degraded_feedback_cct_cut",
        us_b + us_f,
        f"{(1 - fb.metrics.makespan / blind.metrics.makespan) * 100:.1f}pct",
    )
    slow_share_fb = fb.metrics.nic_tx[:, -1].sum() / fb.metrics.nic_tx.sum()
    _emit("online_degraded_slow_rail_share", us_f, f"{slow_share_fb:.3f}_of_tx")


def bench_online_replay() -> None:
    """Gating drift: routing replay + overlap pipeline vs no replay."""
    rounds = 3 if W.QUICK else 6
    tms = W.drift_stream(num_rounds=rounds, seed=5)
    speeds = [1.0] * (W.N - 1) + [0.5]
    kwargs = dict(
        gap_fraction=0.5, chunk_bytes=W.CHUNK, rail_speeds=speeds, feedback=True
    )
    off, us_o = _timed(lambda: run_pipeline(tms, use_replay=False, **kwargs))
    rep, us_r = _timed(lambda: run_pipeline(tms, use_replay=True, **kwargs))
    _emit(
        "online_replay_cct_vs_noreplay",
        us_o + us_r,
        f"{rep.makespan / off.makespan:.3f}x_noreplay",
    )
    piped, us_p = _timed(
        lambda: run_pipeline(tms, use_replay=True, compare_sequential=True, **kwargs)
    )
    _emit(
        "online_replay_overlap_speedup",
        us_p,
        f"{piped.overlap_speedup:.2f}x_sequential",
    )


def bench_scale() -> None:
    """ROADMAP fabric scaling: 64→512 nodes, chunk counts up to 10⁶.

    Times one RailS one-shot collective per fabric size on both simulation
    backends (``--backend`` restricts to one), reporting simulated-chunk
    throughput — the raw "fast as the hardware allows" trajectory metric.
    The event engine is only timed up to ``EVENT_CHUNK_CAP`` chunks; above
    that the speedup row compares against the largest event rate measured
    on the same fabric. Flowlet coalescing (an event-engine approximation)
    reports its measured CCT drift against the exact vector result — the
    ROADMAP's "error bound on the CCT drift".
    """
    grid = W.SCALE_GRID_QUICK if W.QUICK else W.SCALE_GRID
    event_rate: dict[int, float] = {}  # nodes -> chunks/s at the largest capped size
    for m, n, target_chunks in grid:
        tm, chunk_bytes = W.scale_fabric(m, n, target_chunks)
        nodes = m * n
        chunks = int(round(tm.total_bytes() / chunk_bytes))
        tag = f"scale_nodes{nodes}_chunks{chunks}"
        res_v = res_e = None
        if _BACKEND in ("both", "vector", "device"):
            res_v, us_v = _timed(
                lambda: run_collective(
                    tm, "rails", chunk_bytes=chunk_bytes, backend="vector"
                )
            )
            _emit(
                f"{tag}_vector", us_v,
                f"{chunks / (us_v / 1e6) / 1e3:.0f}kchunks_per_s_opt_ratio="
                f"{res_v.opt_ratio:.2f}",
                bench="scale", backend="vector", size=chunks,
            )
        if _BACKEND in ("both", "event") and chunks <= W.EVENT_CHUNK_CAP:
            res_e, us_e = _timed(
                lambda: run_collective(
                    tm, "rails", chunk_bytes=chunk_bytes, backend="event"
                )
            )
            event_rate[nodes] = chunks / (us_e / 1e6)
            _emit(
                f"{tag}_event", us_e,
                f"{chunks / (us_e / 1e6) / 1e3:.0f}kchunks_per_s_opt_ratio="
                f"{res_e.opt_ratio:.2f}",
                bench="scale", backend="event", size=chunks,
            )
        if res_v is not None:
            rate_v = chunks / (us_v / 1e6)
            if res_e is not None:
                _emit(
                    f"{tag}_vector_speedup", us_v,
                    f"{us_e / us_v:.1f}x_event_makespan_drift="
                    f"{abs(res_v.makespan / res_e.makespan - 1) * 100:.2e}pct",
                    bench="scale_speedup", backend="vector", size=chunks,
                )
            elif event_rate.get(nodes):
                _emit(
                    f"{tag}_vector_speedup", us_v,
                    f"{rate_v / event_rate[nodes]:.1f}x_event_rate_at_cap",
                    bench="scale_speedup", backend="vector", size=chunks,
                )
        if _BACKEND == "device":
            # Device backend: cold call pays the jit trace (amortized by
            # the power-of-two padding buckets — same-bucket sizes reuse
            # it); the warm rate is the trajectory metric. The suite row
            # is the batching headline: all five policies planned
            # host-side, scanned in one vmap-ed dispatch, vs the serial
            # vector loop over the same grid.
            _, us_cold = _timed(
                lambda: run_collective(
                    tm, "rails", chunk_bytes=chunk_bytes, backend="device"
                )
            )
            res_d, us_d = _timed(
                lambda: run_collective(
                    tm, "rails", chunk_bytes=chunk_bytes, backend="device"
                )
            )
            _emit(
                f"{tag}_device", us_d,
                f"{chunks / (us_d / 1e6) / 1e3:.0f}kchunks_per_s_opt_ratio="
                f"{res_d.opt_ratio:.2f}_jit_cold={us_cold / 1e6:.2f}s",
                bench="scale", backend="device", size=chunks,
            )
            if res_v is not None:
                _emit(
                    f"{tag}_device_speedup", us_d,
                    f"{us_v / us_d:.1f}x_vector_makespan_drift="
                    f"{abs(res_d.makespan / res_v.makespan - 1) * 100:.2e}pct",
                    bench="scale_speedup", backend="device", size=chunks,
                )
            suite_v, us_sv = _timed(
                lambda: run_policy_suite(
                    tm, chunk_bytes=chunk_bytes, backend="vector"
                )
            )
            run_policy_suite(tm, chunk_bytes=chunk_bytes, backend="device")
            suite_d, us_sd = _timed(
                lambda: run_policy_suite(
                    tm, chunk_bytes=chunk_bytes, backend="device"
                )
            )
            npol = len(suite_d)
            _emit(
                f"{tag}_device_suite", us_sd,
                f"{npol}policies_1dispatch_{us_sv / us_sd:.1f}x_vector_loop",
                bench="scale_suite", backend="device", size=chunks,
            )
        if _BACKEND == "both":
            # Coalescing drift vs the exact (vector-backend) result.
            exact = res_v if res_v is not None else res_e
            res_c, us_c = _timed(
                lambda: run_collective(
                    tm, "rails", chunk_bytes=chunk_bytes, coalesce=True
                )
            )
            _emit(
                f"{tag}_coalesced", us_c,
                f"makespan_drift={abs(res_c.makespan / exact.makespan - 1) * 100:.2f}pct"
                f"_p99_drift={abs(res_c.cct['p99'] / exact.cct['p99'] - 1) * 100:.2f}pct"
                "_vs_vector_exact",
                bench="scale_coalesce_drift", backend="event", size=chunks,
            )
    if _BACKEND == "device":
        _bench_scale_microbatch()


def _bench_scale_microbatch() -> None:
    """Batched-sweep regime: many small sims in one device dispatch.

    Times a batch of B independently-planned small collectives through
    ``simulate_many_device`` (one shared padding bucket, one vmap-ed
    call) against the serial vector loop over the same planned arrays —
    planning cost is identical (host-side) in both arms and excluded.
    This is the dispatch-amortization regime the device backend targets:
    the batch dimension is embarrassingly parallel, so on an accelerator
    (or a multi-core host where XLA's thread pool covers the vmap dim)
    one dispatch replaces B python/numpy round trips. On a single-core
    CPU jax install there is nothing to parallelize over and the row
    records the honest ratio vs numpy's serial scans (<1x) — the
    trajectory metric to watch when the toolchain gains a real device.
    """
    from repro.netsim.devicesim import PlannedJobs, simulate_many_device
    from repro.netsim.fastsim import LinkIndex, simulate_chunk_arrays
    from repro.netsim.simulate import _plan_collective
    from repro.netsim.topology import RailTopology

    B = 32 if W.QUICK else 256
    target_chunks = 200
    topo = RailTopology(4, 4)
    index = LinkIndex(topo)
    planned = []
    for i in range(B):
        tm, chunk_bytes = W.scale_fabric(4, 4, target_chunks, seed=100 + i)
        ja, link_by_level, entry_rank = _plan_collective(
            topo, index, tm, "rails", chunk_bytes, seed=i, probe_every=64
        )
        planned.append(
            PlannedJobs(
                link_by_level=link_by_level,
                size=ja.size,
                release=ja.release,
                entry_rank=entry_rank,
                flow_id=ja.flow_id,
                round_id=ja.round_id,
            )
        )
    chunks = sum(p.num_chunks for p in planned)

    def vector_loop():
        return [
            simulate_chunk_arrays(
                index, p.link_by_level, p.size, p.release, p.entry_rank,
                flow_id=p.flow_id, round_id=p.round_id,
            )
            for p in planned
        ]

    res_v, us_v = _timed(vector_loop)
    simulate_many_device(index, planned)  # jit warmup (shared bucket)
    res_d, us_d = _timed(lambda: simulate_many_device(index, planned))
    drift = max(
        abs(d.makespan / v.makespan - 1) for d, v in zip(res_d, res_v)
    )
    _emit(
        f"scale_microbatch_{B}x{target_chunks}chunks_device", us_d,
        f"{us_v / us_d:.2f}x_vector_loop_1dispatch_makespan_drift="
        f"{drift * 100:.2e}pct",
        bench="scale_microbatch", backend="device", size=chunks,
    )


def bench_fault_sweep() -> None:
    """Fabric-dynamics grid: loss rate × degradation depth × policy.

    Each cell runs the same seeded streaming workload under a FaultSpec
    combining Gilbert–Elliott chunk loss (go-back-N recovery) with one
    rail stepping down mid-run, for proactive ``rails-online``+feedback vs
    the reactive ``plb``/``reps`` baselines. Per-policy rows carry raw CCT
    and retransmit counts; the per-cell ``ordering`` row (structured key
    ``bench=fault_l<loss>_d<depth>``) tracks the reactive-over-rails CCT
    ratios — the §VI-E margin — across the repo's perf trajectory.
    """
    rounds = 3 if W.QUICK else 6
    tms = W.micro_stream(num_microbatches=rounds, seed=8)
    mean_gap = 0.5 * theorem2_optimal_time(tms[0].d2, W.N, 50e9)
    releases = W.bursty_releases(rounds, mean_gap, seed=9)
    stream = list(zip(releases, tms))
    t_mid = releases[rounds // 2]
    losses = (0.0, 0.01) if W.QUICK else (0.0, 0.005, 0.02)
    depths = (1.0, 0.5) if W.QUICK else (1.0, 0.5, 0.25)
    for loss in losses:
        for depth in depths:
            def make_spec(loss=loss, depth=depth):
                profiles = (
                    {} if depth == 1.0 else {W.N - 1: step_profile(t_mid, depth)}
                )
                lcfg = (
                    None
                    if loss == 0.0
                    else LossConfig(
                        rate=loss, rto=5e-4, bad_rate=min(0.3, 30 * loss),
                        p_enter_bad=0.02, p_leave_bad=0.3,
                    )
                )
                return FaultSpec(rail_profiles=profiles, loss=lcfg, seed=11)

            cell = f"fault_l{loss:g}_d{depth:g}"
            cct, us_tot = {}, 0.0
            for pol, fb in (("rails-online", True), ("plb", False), ("reps", False)):
                res, us = _timed(
                    lambda pol=pol, fb=fb: run_streaming_collective(
                        stream, pol, chunk_bytes=W.CHUNK,
                        fault_spec=make_spec(), feedback=fb,
                    )
                )
                cct[pol] = res.metrics.makespan
                us_tot += us
                dyn = res.sim.dynamics or {}
                _emit(
                    f"{cell}_{pol}", us,
                    f"cct={res.metrics.makespan:.4e}s"
                    f"_retr={dyn.get('retransmits', 0)}",
                )
            rails = cct["rails-online"]
            _emit(
                f"{cell}_ordering", us_tot,
                f"plb={cct['plb'] / rails:.3f}x"
                f"_reps={cct['reps'] / rails:.3f}x_rails",
                bench=cell, backend="event",
            )


def bench_serving() -> None:
    """Serving-path tail-latency grid: arrival rate × fault × policy.

    Each cell runs one seeded request stream (prefill + decode rounds per
    request, Poisson arrivals) through ``repro.serve.run_serving`` and
    reports release-relative TTFT percentiles (p50/p99/p99.9 — the SLO
    metrics of the serving regime) plus the per-cell ordering row:
    reactive-over-rails p99-TTFT ratios under the PR-4 degraded fabrics.
    Structured bench key ``serve_g<gap>_<fault>`` feeds
    ``perf_report.py --serving``.
    """
    from repro.sched.serving import run_serving

    gaps = (5e-4, 1.25e-4)  # moderate load / near-saturation
    faults = {
        "clean": lambda: None,
        "degraded": lambda: FaultSpec(
            rail_profiles={W.N - 1: step_profile(0.0, 0.25)},
            loss=LossConfig(rate=0.01, rto=1e-4, bad_rate=0.3,
                            p_enter_bad=0.02, p_leave_bad=0.3),
            seed=11,
        ),
    }
    if not W.QUICK:
        faults["loss"] = lambda: FaultSpec(
            loss=LossConfig(rate=0.02, rto=1e-4, bad_rate=0.3,
                            p_enter_bad=0.02, p_leave_bad=0.3),
            seed=11,
        )
    for gap in gaps:
        wl = W.serve_requests(mean_gap=gap)
        for fname, make_spec in faults.items():
            cell = f"serve_g{gap:g}_{fname}"
            p99_ttft, us_tot = {}, 0.0
            for pol, fb in (("rails-online", True), ("plb", False), ("reps", False)):
                res, us = _timed(
                    lambda pol=pol, fb=fb: run_serving(
                        wl, pol, chunk_bytes=256 * 2**10,
                        fault_spec=make_spec(), feedback=fb,
                    )
                )
                row = res.row()
                p99_ttft[pol] = row["ttft_p99_s"]
                us_tot += us
                # No structured bench key: the full row name (unique per
                # policy, still `serve_`-prefixed) keys the trajectory, so
                # these never collide with the cell's ordering row.
                _emit(
                    f"{cell}_{pol}", us,
                    f"ttft_p50={row['ttft_p50_s']:.3e}s"
                    f"_p99={row['ttft_p99_s']:.3e}s"
                    f"_p99.9={row['ttft_p99.9_s']:.3e}s"
                    f"_retr={row['retransmits']}",
                )
            rails = p99_ttft["rails-online"]
            _emit(
                f"{cell}_ordering", us_tot,
                f"plb={p99_ttft['plb'] / rails:.3f}x"
                f"_reps={p99_ttft['reps'] / rails:.3f}x_rails_p99_ttft",
                bench=cell, backend="event",
            )


def bench_serving_slo() -> None:
    """SLO-attainment grid for the serving control plane.

    Offered load (``mean_gap``) × fabric ({clean, one-dead-rail}) ×
    control arm ({no-control, admission, admission+brownout}), every cell
    one seeded request stream through the epoch-windowed
    :func:`repro.serve.gateway.run_gateway` array loop (full mode sweeps
    10⁴ requests per cell — the feedback-at-scale regime the windowed
    loop exists for). Scored shed-aware: goodput = served requests whose
    TTFT met the SLO, per second of trace. The per-cell ``ordering`` row
    (structured key ``bench=slo_g<gap>_<fabric>``, keyed by backend)
    tracks the controlled-over-uncontrolled goodput ratio — the
    overload-robustness headline — via ``perf_report.py --slo``. The
    fabric is a fixed 4×4 (the control loop, not fabric scale, is under
    test); the dead rail is a 2 %-speed crawl, the array loops' fail-stop
    proxy. ``--backend device`` runs each window's scan on the jax
    backend instead and raises full mode to 10⁵ requests per cell — the
    p99.99-tail regime, and the scale where an accelerator-backed jax
    install would amortize per-window dispatch (on single-core CPU jax
    the vector loop stays faster; the rows record what this host
    measures).
    """
    from repro.core.traffic import serve_workload
    from repro.sched.control import (
        AdmissionConfig,
        BrownoutConfig,
        ControlConfig,
    )
    from repro.serve.gateway import run_gateway

    m, n = 4, 4
    slo = 0.002
    gw_backend = "device" if _BACKEND == "device" else "vector"
    if W.QUICK:
        num_req = 300
    else:
        num_req = 100_000 if gw_backend == "device" else 10_000
    gaps = (2e-4, 5e-5) if W.QUICK else (2e-4, 1e-4, 5e-5)
    dead = np.ones(n)
    dead[-1] = 0.02
    fabrics = {"clean": None, "dead1": dead}
    arms = {
        "nocontrol": lambda: None,
        "admission": lambda: ControlConfig(
            slo_s=slo, admission=AdmissionConfig(rate_rps=4000.0)
        ),
        "admission_brownout": lambda: ControlConfig(
            slo_s=slo,
            admission=AdmissionConfig(rate_rps=4000.0),
            brownout=BrownoutConfig(),
        ),
    }
    for gap in gaps:
        wl = serve_workload(m, n, num_requests=num_req, mean_gap=gap, seed=12)
        for fab, speeds in fabrics.items():
            cell = f"slo_g{gap:g}_{fab}"
            goodput, us_tot = {}, 0.0
            for arm, make_control in arms.items():
                res, us = _timed(
                    lambda arm=arm, make_control=make_control: run_gateway(
                        wl, "rails-online", control=make_control(),
                        rail_speeds=speeds, backend=gw_backend, slo_s=slo,
                    )
                )
                s = res.slo
                goodput[arm] = s["goodput_rps"]
                us_tot += us
                _emit(
                    f"{cell}_{arm}", us,
                    f"goodput={s['goodput_rps']:.1f}rps"
                    f"_shed={s['shed_rate']:.3f}"
                    f"_att={s['slo_attainment']:.3f}"
                    f"_brownout_w={res.brownout_windows}",
                    bench=f"{cell}_{arm}", backend=gw_backend, size=num_req,
                )
            base = max(goodput["nocontrol"], 1e-9)
            _emit(
                f"{cell}_ordering", us_tot,
                f"admission={goodput['admission'] / base:.2f}x"
                f"_brownout={goodput['admission_brownout'] / base:.2f}"
                "x_nocontrol_goodput",
                bench=cell, backend=gw_backend, size=num_req,
            )


def bench_placement() -> None:
    """Placement × spraying grid: drift rate × placement mode (ISSUE 6).

    Each cell replays one seeded Mixtral-shaped drifting gating trace
    (``W.placement_drift_counts``) end to end through
    ``repro.placement.run_relayout_trace`` under every placement mode —
    spraying-only ``static`` round-robin, one-shot ``greedy``/``lp``
    re-layouts, and the ``online`` drift-triggered migration controller.
    Per-mode rows carry raw CCT plus migration bytes (the re-layout cost
    rides the simulated fabric); the per-cell ``ordering`` row (structured
    key ``bench=plc_d<drift>``) tracks the static-over-mode CCT ratios —
    the placement+spraying vs spraying-only RailS headline — across the
    repo's perf trajectory via ``perf_report.py --placement``.
    """
    from repro.placement import RelayoutConfig, run_relayout_trace

    drifts = (0.05, 0.4) if W.QUICK else (0.05, 0.2, 0.4)
    modes = ("static", "greedy", "lp", "online")
    # Faster EWMA + shorter cooldown than the library default: the bench
    # traces are short (6 rounds), so the controller must react within a
    # round or two of a collision appearing to amortize before trace end.
    cfg = RelayoutConfig(alpha=0.7, cooldown=1, hysteresis=0.05)
    for drift in drifts:
        counts, bpt, expert_bytes = W.placement_drift_counts(drift)
        cell = f"plc_d{drift:g}"
        cct, mig, us_tot = {}, {}, 0.0
        for mode in modes:
            res, us = _timed(
                lambda mode=mode: run_relayout_trace(
                    counts, W.M, W.N, bpt, mode=mode,
                    weight_bytes=expert_bytes, chunk_bytes=W.CHUNK,
                    config=cfg,
                )
            )
            cct[mode], mig[mode] = res.makespan, res.migration_bytes
            us_tot += us
            _emit(
                f"{cell}_{mode}", us,
                f"cct={res.makespan:.4e}s"
                f"_mig={res.migration_bytes / 2**20:.1f}MiB"
                f"_moves={res.num_migrations}",
            )
        static = cct["static"]
        _emit(
            f"{cell}_ordering", us_tot,
            f"greedy={static / cct['greedy']:.3f}x"
            f"_lp={static / cct['lp']:.3f}x"
            f"_online={static / cct['online']:.3f}x_static_cct",
            bench=cell, backend="event",
        )


def bench_recovery() -> None:
    """Fail-stop recovery grid: failed-rail count × watchdog timeout × policy.

    Each cell runs the seeded ``repro.runtime.failover`` drill — a rail
    (or two) fail-stops mid-collective, stranded chunks retry with
    backoff onto survivors, the silence watchdog flips the planner to the
    N−k survivor mask — and reports time-to-detect, time-to-recover, and
    the steady-state degraded CCT against the Theorem-2 bound recomputed
    on survivors (``track`` = degradation beyond what that bound
    predicts; ~1.0 means failover costs nothing the math doesn't charge).
    Reactive ``reps`` rows have no detection (ttd is planner-side) — they
    recover purely through per-chunk path probing, the baseline the
    proactive path must beat. A serving leg re-runs the PR-5 request
    stream through a mid-trace rail-down and reports the p99-TTFT
    recovery curve (pre/during/post buckets). Structured keys
    ``recov_k<k>_t<mult>`` feed ``perf_report.py --recovery``.
    """
    from repro.netsim import FailStopEvent, RetryConfig
    from repro.runtime.failover import run_failover_drill
    from repro.sched.feedback import DeadRailDetector
    from repro.sched.serving import run_serving, ttft_recovery_curve

    ks = (1, 2)
    mults = (1.0,) if W.QUICK else (1.0, 3.0)
    for k in ks:
        rails = tuple(range(1, 1 + k))
        for mult in mults:
            cell = f"recov_k{k}_t{mult:g}"
            degr, us_tot = {}, 0.0
            for pol in ("rails-online", "reps"):
                rep, us = _timed(
                    lambda pol=pol: run_failover_drill(
                        fail_rail=rails, deadline_gaps=0.6 * mult, policy=pol
                    )
                )
                degr[pol] = rep.degraded_cct_s
                us_tot += us
                ttd = rep.time_to_detect
                _emit(
                    f"{cell}_{pol}", us,
                    f"ttd={'na' if ttd is None else f'{ttd:.3e}s'}"
                    f"_ttr={rep.time_to_recover:.3e}s"
                    f"_track={rep.bound_tracking_ratio:.3f}"
                    f"_eo={int(rep.exactly_once)}"
                    f"_strands={rep.strands}",
                )
            rails_cct = degr["rails-online"]
            _emit(
                f"{cell}_ordering", us_tot,
                f"reps={degr['reps'] / rails_cct:.3f}x_rails_degraded_cct",
                bench=cell, backend="event",
            )
    # Serving leg: mid-trace rail-down + repair through the PR-5 request
    # stream; the recovery curve buckets p99 TTFT by request arrival.
    wl = W.serve_requests(mean_gap=5e-4)
    spec = FaultSpec(
        failures=(FailStopEvent("rail", 2e-3, rail=W.N - 1, t_repair=5e-3),),
        retry=RetryConfig(rto=1e-4),
        seed=11,
    )
    res, us = _timed(
        lambda: run_serving(
            wl, "rails-online", chunk_bytes=256 * 2**10, fault_spec=spec,
            detector=DeadRailDetector(W.N, deadline=5e-4),
        )
    )
    curve = ttft_recovery_curve(res, bucket_s=1e-3)
    pre = [p for t, p in zip(curve["t"], curve["p99"]) if t < 2e-3]
    during = [p for t, p in zip(curve["t"], curve["p99"]) if 2e-3 <= t < 5e-3]
    post = [p for t, p in zip(curve["t"], curve["p99"]) if t >= 5e-3]
    dyn = res.streaming.sim.dynamics or {}
    _emit(
        "recov_serving_raildown", us,
        f"p99_pre={max(pre, default=0.0):.3e}s"
        f"_fail={max(during, default=0.0):.3e}s"
        f"_post={max(post, default=0.0):.3e}s"
        f"_strands={dyn.get('fail_strands', 0)}",
        bench="recov_serving_raildown", backend="event",
        size=len(wl.requests),
    )


def bench_online_window_sweep() -> None:
    """ROADMAP windowed re-planning sweep: CCT vs decision latency as the
    re-planning window goes 1 (greedy on arrival) → ∞ (whole-batch LPT),
    across burstiness levels."""
    rounds = 3 if W.QUICK else 6
    tms = W.micro_stream(num_microbatches=rounds, seed=6)
    mean_gap = 0.5 * theorem2_optimal_time(tms[0].d2, W.N, 50e9)
    bursts = (1.5,) if W.QUICK else (0.5, 1.5, 3.0)
    windows = (1, None) if W.QUICK else (1, 8, 64, None)
    for burst in bursts:
        releases = W.bursty_releases(rounds, mean_gap, seed=7, burstiness=burst)
        stream = list(zip(releases, tms))
        greedy_makespan = None
        for window in windows:
            res, us = _timed(
                lambda window=window: run_streaming_collective(
                    stream, "rails-online", chunk_bytes=W.CHUNK, window=window
                )
            )
            if greedy_makespan is None:
                greedy_makespan = res.metrics.makespan
            label = "inf" if window is None else str(window)
            _emit(
                f"online_window_burst{burst:g}_w{label}", us,
                f"{res.metrics.makespan / greedy_makespan:.4f}x_greedy_cct",
            )


def _xdc_moe_tm(m: int, n: int, bytes_per_pair: float, top_k: int, seed: int) -> TrafficMatrix:
    """MoE-gated sparse all-to-all: each sender GPU routes to ``top_k``
    remote (domain, gpu) experts with lognormal flow sizes.

    Few large flows per sender is exactly where the flat policy's static
    ``rail % wan_lanes`` spray leaves WAN lanes unbalanced — dense uniform
    traffic self-averages over lanes and hides the hierarchy (Theorem 3's
    symmetry, one tier up); ``bench_xdc`` emits both regimes to show it.
    """
    rng = np.random.default_rng(seed)
    d1 = np.zeros((m, n, m, n))
    for d in range(m):
        for g in range(n):
            dsts = rng.choice(
                [x for x in range(m) if x != d], size=top_k, replace=False
            )
            for dd in dsts:
                gg = int(rng.integers(0, n))
                d1[d, g, int(dd), gg] = bytes_per_pair * rng.lognormal(0.0, 0.5)
    return TrafficMatrix(d1=d1, d2=d1.sum(axis=(1, 3)), name=f"xdc-moe-top{top_k}")


def _wan_lane_imbalance(tm: TrafficMatrix, topo, policy_name: str, chunk: float) -> float:
    """Mean over active pod pairs of max-lane-load / mean-lane-load on the
    WAN tier under a policy's static plan (1.0 = perfectly lane-balanced)."""
    ja = build_job_arrays(tm, chunk_bytes=chunk)
    index = LinkIndex(topo)
    pol = make_policy(policy_name, topo, seed=0)
    lbl = pol.plan_arrays(ja, index)
    wan_links = lbl[:, index.level_of_kind["wan"]]
    loads = np.zeros(index.num_links)
    mask = wan_links >= 0
    np.add.at(loads, wan_links[mask], ja.size[mask])
    imbs = []
    p = topo.num_pods
    for ps in range(p):
        for pd in range(p):
            if ps == pd:
                continue
            lane_loads = loads[index.wan[ps, pd]]
            if lane_loads.sum() > 0:
                imbs.append(lane_loads.max() / lane_loads.mean())
    return float(np.mean(imbs)) if imbs else 1.0


def bench_xdc() -> None:
    """Hierarchical multi-pod fabrics: hier-LPT vs flat LPT vs reactive.

    Sweeps oversubscription x WAN RTT on a 4-pod fabric (2 domains/pod)
    carrying MoE-gated sparse traffic, reporting per-policy CCT, the
    hier-vs-flat margin, and the WAN per-lane imbalance that explains it.
    A dense-uniform row quantifies the symmetry break: uniform send keeps
    Theorem 3's balance one tier up and the hierarchy-aware pass is a
    no-op; gated traffic breaks it and two-level LPT wins the difference.
    FEC rows compare XOR parity against go-back-N on the lossy WAN tier.
    """
    pods, dpp, n, lanes = 4, 2, 4, 4
    m = pods * dpp
    chunk = 2 * 2**20
    tm = _xdc_moe_tm(m, n, bytes_per_pair=8 * 2**20, top_k=4, seed=1)
    grid = [(16.0, 10e-3)] if W.QUICK else [
        (4.0, 1e-3), (4.0, 10e-3), (16.0, 1e-3), (16.0, 10e-3)
    ]
    for oversub, rtt in grid:
        topo = MultiPodFabric(
            num_pods=pods, domains_per_pod=dpp, num_rails=n,
            oversub=oversub, wan_rtt=rtt, wan_lanes=lanes,
        )
        tag = f"xdc_o{oversub:g}_rtt{rtt * 1e3:g}ms"
        res, us = {}, {}
        for pol in ("ecmp", "rails", "hier-rails"):
            res[pol], us[pol] = _timed(
                lambda p=pol: run_collective(
                    tm, p, chunk_bytes=chunk, fabric=topo, backend="vector"
                )
            )
            _emit(
                f"{tag}_{pol}", us[pol],
                f"{res[pol].makespan * 1e3:.2f}ms_opt_ratio="
                f"{res[pol].opt_ratio:.2f}",
                bench=f"{tag}_{pol}", backend="vector",
            )
        _emit(
            f"{tag}_hier_vs_flat", us["rails"] + us["hier-rails"],
            f"{(1 - res['hier-rails'].makespan / res['rails'].makespan) * 100:.2f}"
            "pct_cct_cut",
            bench=f"{tag}_hier_vs_flat", backend="vector",
        )
    # The symmetry break, quantified: WAN lane imbalance under each plan,
    # and the hier margin collapsing to ~0 on dense-uniform traffic.
    topo = MultiPodFabric(
        num_pods=pods, domains_per_pod=dpp, num_rails=n,
        oversub=16.0, wan_rtt=10e-3, wan_lanes=lanes,
    )
    for pol in ("rails", "hier-rails"):
        imb, us_i = _timed(lambda p=pol: _wan_lane_imbalance(tm, topo, p, chunk))
        _emit(
            f"xdc_wan_lane_imbalance_{pol}", us_i, f"{imb:.3f}x_mean_lane",
            bench=f"xdc_wan_lane_imbalance_{pol}", backend="vector",
        )
    utm = uniform_workload(m, n, bytes_per_pair=2 * 2**20)
    uflat, us_uf = _timed(
        lambda: run_collective(utm, "rails", chunk_bytes=chunk, fabric=topo,
                               backend="vector")
    )
    uhier, us_uh = _timed(
        lambda: run_collective(utm, "hier-rails", chunk_bytes=chunk, fabric=topo,
                               backend="vector")
    )
    _emit(
        "xdc_uniform_hier_vs_flat", us_uf + us_uh,
        f"{(1 - uhier.makespan / uflat.makespan) * 100:.2f}pct_cct_cut",
        bench="xdc_uniform_hier_vs_flat", backend="vector",
    )
    # FEC vs go-back-N on the lossy WAN: XOR parity absorbs losses without
    # waiting out the 10 ms RTT's RTO (wins under loss), but its r/k
    # redundancy bandwidth is a pure tax at zero loss (loses there).
    fec_chunk = 2**20  # >= k chunks per lane so groups actually fill
    for rate, label in ((0.01, "loss1pct"), (0.0, "loss0")):
        loss = LossConfig(rate=rate, rto=2 * 10e-3, links="wan")
        out = {}
        us_fec = 0.0
        for variant, fec in (("gbn", None), ("fec", FecConfig(k=4, r=1))):
            ftopo = MultiPodFabric(
                num_pods=pods, domains_per_pod=dpp, num_rails=n,
                oversub=16.0, wan_rtt=10e-3, wan_lanes=lanes,
                fault_spec=FaultSpec(loss=loss, fec=fec, seed=7),
            )
            out[variant], us_v = _timed(
                lambda t=ftopo: run_collective(
                    tm, "hier-rails", chunk_bytes=fec_chunk, fabric=t,
                    backend="event",
                )
            )
            us_fec += us_v
        _emit(
            f"xdc_fec_vs_gbn_{label}", us_fec,
            f"{(1 - out['fec'].makespan / out['gbn'].makespan) * 100:.2f}"
            "pct_cct_cut",
            bench=f"xdc_fec_vs_gbn_{label}", backend="event",
        )


def bench_rl_phases() -> None:
    """RL rollout/train lurches: replay forecast quality across phase
    boundaries (PR 8's open question), scored like the gating-drift sweep.

    ``rl_phase_counts`` alternates peaky rollout gating with flat train
    gating; the routing distribution *lurches* at each boundary instead of
    drifting. Pure last-iteration replay (alpha=1) tracks within-phase
    drift best but eats the full lurch at each boundary; EWMA smoothing
    trades steady-state lag for boundary shock absorption. The CCT rows
    re-score run_pipeline's replay warm-start on the lurching stream.
    """
    m, n = W.M, W.N
    rounds = 8 if W.QUICK else 24
    phase_len = 2 if W.QUICK else 6
    tokens = float(m * (m - 1) * 64)
    counts_rounds, shard, phases = rl_phase_counts(
        m, num_experts=4 * m, num_rounds=rounds, tokens_per_round=tokens,
        rollout_len=phase_len, train_len=phase_len, seed=9,
        return_phases=True,
    )
    placement = Placement.round_robin(4 * m, m)
    bpt = float(2**17)  # 128 KiB/token -> ~8 MiB mean off-diagonal entry
    tms = [
        placement.traffic(c, bpt, n, name=f"rl-{phases[i]}-{i}")
        for i, c in enumerate(counts_rounds)
    ]
    forecasters = {"replay": 1.0, "ewma": 0.35}
    for name, alpha in forecasters.items():
        def score(alpha=alpha):
            errs = {"boundary": [], "steady": []}
            rs = RoutingReplayState(m, n, alpha=alpha)
            prev = None
            for tm, phase in zip(tms, phases):
                realized = tm.domain_send_totals()
                if rs.iterations > 0:
                    predicted = rs.expected_totals()
                    err = float(
                        np.abs(predicted - realized).sum()
                        / max(np.abs(realized).sum(), 1e-12)
                    )
                    errs["boundary" if phase != prev else "steady"].append(err)
                rs.update_from_loads(realized)
                prev = phase
            return errs
        errs, us = _timed(score)
        _emit(
            f"rl_forecast_err_{name}", us,
            f"boundary={np.mean(errs['boundary']):.3f}"
            f"_steady={np.mean(errs['steady']):.3f}_rel_l1",
            bench=f"rl_forecast_err_{name}",
        )
    speeds = [1.0] * (n - 1) + [0.5]
    kwargs = dict(
        gap_fraction=0.5, chunk_bytes=W.CHUNK, rail_speeds=speeds, feedback=True
    )
    off, us_o = _timed(lambda: run_pipeline(tms, use_replay=False, **kwargs))
    rep, us_r = _timed(lambda: run_pipeline(tms, use_replay=True, **kwargs))
    _emit(
        "rl_phase_replay_cct_vs_noreplay", us_o + us_r,
        f"{rep.makespan / off.makespan:.3f}x_noreplay",
        bench="rl_phase_replay_cct", backend="event",
    )


def parity_check() -> int:
    """CI gate: the simulation backends must agree on the quick config.

    Two legs, both required (returns 0 only if every check passes):

    * event vs vector — makespan + CCT percentiles; rail-path policies at
      fp tolerance, spine-path baselines at 2e-3 for tie-order degeneracy
      on the synthetic equal-chunk workloads (see tests/test_fastsim.py).
    * vector vs device — the jax backend on CPU jax; makespan at fp
      tolerance for every policy, CCT percentiles at fp tolerance for
      rails and 2e-2 otherwise (degenerate equal-chunk waves can resolve
      ties into a different — equally valid — FIFO order on device; see
      tests/test_devicesim.py).
    """
    W.configure(quick=True)
    workloads = {
        "uniform": W.uniform(),
        "sparse04": W.sparse(0.4),
        "mixtral": W.mixtral("stable", "sparse"),
    }
    failures = []
    for pol in W.POLICIES:
        rtol = 1e-9 if pol in ("rails", "minrtt") else 2e-3
        pol_failures = 0
        for name, tm in workloads.items():
            e = run_collective(tm, pol, chunk_bytes=W.CHUNK, seed=3, backend="event")
            v = run_collective(tm, pol, chunk_bytes=W.CHUNK, seed=3, backend="vector")
            checks = {"makespan": (v.makespan, e.makespan)}
            checks.update({k: (v.cct[k], e.cct[k]) for k in e.cct})
            for key, (got, want) in checks.items():
                if abs(got - want) > rtol * abs(want) + 1e-15:
                    failures.append((pol, name, key, got, want))
                    pol_failures += 1
                    print(f"parity MISMATCH: {pol}/{name}/{key} vector={got!r} event={want!r}")
        verdict = "ok" if pol_failures == 0 else f"FAILED ({pol_failures})"
        print(f"parity {verdict}: {pol} ({len(workloads)} workloads, rtol={rtol:g})")
    for pol in W.POLICIES:
        mk_rtol = 1e-9
        cct_rtol = 1e-9 if pol == "rails" else 2e-2
        pol_failures = 0
        for name, tm in workloads.items():
            v = run_collective(tm, pol, chunk_bytes=W.CHUNK, seed=3, backend="vector")
            d = run_collective(tm, pol, chunk_bytes=W.CHUNK, seed=3, backend="device")
            checks = {"makespan": (d.makespan, v.makespan, mk_rtol)}
            checks.update({k: (d.cct[k], v.cct[k], cct_rtol) for k in v.cct})
            for key, (got, want, rtol) in checks.items():
                if abs(got - want) > rtol * abs(want) + 1e-15:
                    failures.append((pol, name, key, got, want))
                    pol_failures += 1
                    print(f"parity MISMATCH: {pol}/{name}/{key} device={got!r} vector={want!r}")
        verdict = "ok" if pol_failures == 0 else f"FAILED ({pol_failures})"
        print(f"device parity {verdict}: {pol} ({len(workloads)} workloads, "
              f"cct_rtol={cct_rtol:g})")
    if failures:
        print(f"# backend parity FAILED: {len(failures)} mismatches")
        return 1
    print("# backend parity OK: event == vector == device on the quick config")
    return 0


BENCHES = {
    "fig7_9_uniform": bench_fig7_9_uniform,
    "fig7_9_sparse": bench_fig7_9_sparse,
    "fig10": bench_fig10_sender_skew,
    "fig11": bench_fig11_receiver_skew,
    "fig12_13": bench_fig12_13_mixtral,
    "lpt": bench_lpt_scheduler,
    "lp": bench_lp_solver,
    "thm4": bench_theorem_bounds,
    "scale": bench_scale,
    "online_microbatch": bench_online_microbatch,
    "online_degraded": bench_online_degraded,
    "online_replay": bench_online_replay,
    "online_window_sweep": bench_online_window_sweep,
    "fault_sweep": bench_fault_sweep,
    "serving": bench_serving,
    "serving_slo": bench_serving_slo,
    "placement": bench_placement,
    "recovery": bench_recovery,
    "xdc": bench_xdc,
    "rl_phases": bench_rl_phases,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument(
        "--quick",
        action="store_true",
        help="smaller M x N fabric and fewer repeats (CI smoke check)",
    )
    ap.add_argument(
        "--json",
        type=str,
        default=None,
        metavar="PATH",
        help="also write rows + environment metadata as JSON (perf trajectory)",
    )
    ap.add_argument(
        "--backend",
        choices=("both", "event", "vector", "device"),
        default="both",
        help="bench_scale/bench_serving_slo backend selection (default: "
             "time event+vector; 'device' times the jax backend against "
             "the vector reference)",
    )
    ap.add_argument(
        "--parity-check",
        action="store_true",
        help="run the backend agreement gates (event-vs-vector and "
             "vector-vs-device) and exit (CI)",
    )
    args = ap.parse_args()
    enable_compile_cache()
    if args.parity_check:
        raise SystemExit(parity_check())
    global _BACKEND
    _BACKEND = args.backend
    W.configure(quick=args.quick)
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and args.only not in name:
            continue
        fn()
    if args.json:
        _write_json(args.json, quick=args.quick, only=args.only)


if __name__ == "__main__":
    main()
