"""Serving path (`repro.serve`) — release-relative CCT + tail latency.

Anchors:

1. **Release-relative semantics** — streaming flow CCT is sojourn time
   (finish − release) on both backends: t=0 streaming bit-matches the
   one-shot collective, and shifting a round's release shifts its sojourn
   by ~0 (fp tolerance of the shifted arithmetic).
2. **Quantile labels** — p99.9 no longer collides with p99.
3. **Goodput BusBw** — retransmissions inflate wire volume, not achieved
   bandwidth.
4. **Serving metrics** — TTFT / per-token latency on hand-computed micro
   cases; whole-workload time shifts leave every metric bit-identical.
5. **Seeded regression** — `rails-online`+feedback beats PLB/REPS on p99
   TTFT under the PR-4 degraded-fabric grid.
"""

import numpy as np
import pytest

from repro.core.traffic import (
    ServeWorkload,
    request_arrival_times,
    serve_workload,
    uniform_workload,
)
from repro.netsim import (
    FaultSpec,
    LossConfig,
    run_collective,
    run_streaming_collective,
    step_profile,
)
from repro.netsim.events import cct_percentile_dict, quantile_label
from repro.sched import run_pipeline
from repro.sched.serving import (
    RequestMetrics,
    expert_counts_to_matrix,
    run_serving,
    simulate_decode_trace,
)

M, N = 4, 4
B = 8 * 2**20
CHUNK = 1 * 2**20


# -- quantile labels (p99.9 vs p99 collision) --------------------------------


def test_quantile_labels_keep_fractions():
    assert quantile_label(50.0) == "p50"
    assert quantile_label(99.0) == "p99"
    assert quantile_label(99.9) == "p99.9"


def test_percentile_dict_p999_distinct_from_p99():
    # 1000 values 1..1000: p99 and p99.9 are genuinely different numbers.
    vals = np.arange(1.0, 1001.0)
    d = cct_percentile_dict(vals, qs=(99.0, 99.9))
    assert "p99" in d and "p99.9" in d
    assert d["p99.9"] > d["p99"]
    np.testing.assert_allclose(d["p99"], np.percentile(vals, 99.0))
    np.testing.assert_allclose(d["p99.9"], np.percentile(vals, 99.9))


def test_percentile_dict_empty_branch_has_fractional_keys():
    d = cct_percentile_dict([], qs=(99.0, 99.9))
    assert d == {"mean": 0.0, "p99": 0.0, "p99.9": 0.0, "max": 0.0}


def test_default_cct_dict_includes_p999():
    tm = uniform_workload(M, N, bytes_per_pair=B)
    m = run_collective(tm, "rails", chunk_bytes=CHUNK)
    assert "p99.9" in m.cct
    assert m.cct["p99.9"] >= m.cct["p99"]
    assert "cct_p99.9_s" in m.row()


# -- release-relative CCT (sojourn semantics) --------------------------------


@pytest.mark.parametrize("backend", ["event", "vector"])
def test_streaming_t0_flow_cct_matches_oneshot(backend):
    """At t=0 sojourn == absolute finish bit for bit, on both backends."""
    tm = uniform_workload(M, N, bytes_per_pair=B)
    off = run_collective(tm, "rails", chunk_bytes=CHUNK, backend=backend)
    st = run_streaming_collective(tm, "rails", chunk_bytes=CHUNK, backend=backend)
    assert st.metrics.cct == off.cct
    assert st.metrics.makespan == off.makespan


@pytest.mark.parametrize("backend", ["event", "vector"])
def test_shifted_release_leaves_sojourn_unchanged(backend):
    """One round released at Δ: every flow's sojourn equals the t=0 run's
    (the whole simulation translates; fp tolerance covers the Δ-shifted
    arithmetic)."""
    tm = uniform_workload(M, N, bytes_per_pair=B)
    base = run_streaming_collective(tm, "rails", chunk_bytes=CHUNK, backend=backend)
    delta = 0.125
    shifted = run_streaming_collective(
        [(delta, tm)], "rails", chunk_bytes=CHUNK, backend=backend
    )
    f0 = base.sim.flow_cct
    f1 = shifted.sim.flow_cct
    assert set(f0) == set(f1)
    np.testing.assert_allclose(
        [f1[k] for k in sorted(f0)], [f0[k] for k in sorted(f0)], rtol=1e-9
    )
    # absolute completion still reflects the shift...
    assert shifted.metrics.makespan == pytest.approx(base.metrics.makespan + delta)
    # ...but the reported CCT percentiles don't.
    for k, v in base.metrics.cct.items():
        assert f1 and shifted.metrics.cct[k] == pytest.approx(v, rel=1e-9), k


def test_streaming_sojourn_excludes_release_wait():
    """A round released late must not report its wait-before-release as
    CCT: two identical rounds far apart report near-identical sojourns."""
    tm = uniform_workload(M, N, bytes_per_pair=B / 4)
    gap = 1.0  # far beyond each round's drain time
    res = run_streaming_collective(
        [(0.0, tm), (gap, tm)], "rails", chunk_bytes=CHUNK
    )
    soj = res.round_sojourn
    assert soj[1] == pytest.approx(soj[0], rel=1e-9)
    assert soj[1] < gap / 100  # nowhere near the absolute finish (~gap)
    # round_cct stays absolute
    assert res.round_cct[1] > gap


@pytest.mark.parametrize("backend", ["event", "vector"])
def test_round_sojourn_times_match_manual(backend):
    tm = uniform_workload(M, N, bytes_per_pair=B / 4)
    releases = [0.0, 2e-4, 7e-4]
    res = run_streaming_collective(
        [(t, tm) for t in releases], "rails", chunk_bytes=CHUNK, backend=backend
    )
    for rnd, cct in res.round_cct.items():
        assert res.round_sojourn[rnd] == cct - releases[rnd]


def test_pipeline_round_latency_uses_engine_sojourn():
    from repro.core.traffic import microbatch_stream

    tms = microbatch_stream(M, N, 3, bytes_per_pair=B / 3, seed=9)
    res = run_pipeline(tms, gap_fraction=0.5, chunk_bytes=CHUNK)
    for rnd, cct in res.round_cct.items():
        assert res.round_latency[rnd] == cct - res.releases[rnd]
        assert res.round_latency[rnd] > 0


def test_event_vector_sojourn_parity_on_stream():
    tm = uniform_workload(M, N, bytes_per_pair=B / 2)
    stream = [(0.0, tm), (3e-4, tm)]
    e = run_streaming_collective(stream, "rails", chunk_bytes=CHUNK, backend="event")
    v = run_streaming_collective(stream, "rails", chunk_bytes=CHUNK, backend="vector")
    assert e.sim.flow_cct == v.sim.flow_cct
    assert e.round_sojourn == v.round_sojourn


# -- goodput vs wire BusBw ----------------------------------------------------


def test_static_run_goodput_equals_wire():
    tm = uniform_workload(M, N, bytes_per_pair=B)
    m = run_collective(tm, "rails", chunk_bytes=CHUNK)
    assert m.goodput_bytes == m.wire_bytes == pytest.approx(tm.total_bytes())
    assert m.bus_bw == m.wire_bus_bw > 0


def test_lossy_run_reports_goodput_busbw_below_wire():
    tm = uniform_workload(M, N, bytes_per_pair=B)
    spec = FaultSpec(
        loss=LossConfig(rate=0.02, rto=5e-4, bad_rate=0.3,
                        p_enter_bad=0.02, p_leave_bad=0.3),
        seed=7,
    )
    m = run_collective(tm, "rails", chunk_bytes=CHUNK, fault_spec=spec)
    # retransmissions actually fired, inflating the wire volume...
    assert m.wire_bytes > m.goodput_bytes
    # ...goodput is exactly the unique payload bytes,
    assert m.goodput_bytes == pytest.approx(tm.total_bytes())
    # and "achieved" BusBw is goodput-based, below the raw wire rate.
    assert m.bus_bw < m.wire_bus_bw
    assert m.bus_bw == pytest.approx(m.goodput_bytes / m.makespan)
    assert m.wire_bus_bw == pytest.approx(m.wire_bytes / m.makespan)


# -- serving workload generation ---------------------------------------------


@pytest.mark.parametrize("process", ["poisson", "bursty", "diurnal"])
def test_arrival_processes_start_at_zero_and_are_sorted(process):
    t = request_arrival_times(64, 1e-3, process, seed=3)
    assert t.shape == (64,)
    assert t[0] == 0.0
    assert np.all(np.diff(t) >= 0)
    assert np.isfinite(t).all()


def test_arrival_process_rejects_unknown():
    with pytest.raises(ValueError, match="poisson|bursty|diurnal"):
        request_arrival_times(4, 1e-3, "weekly")


def test_serve_workload_structure():
    wl = serve_workload(
        M, N, num_requests=6, mean_gap=1e-3, prefill_tokens=32,
        decode_rounds=3, decode_tokens=4, decode_gap=1e-4, seed=5,
    )
    assert len(wl.requests) == 6
    assert len(wl.rounds) == 6 * (1 + 3)
    # rounds sorted by release (streaming round_id == list index)
    rel = [r.release for r in wl.rounds]
    assert rel == sorted(rel)
    for req in wl.requests:
        mine = [r for r in wl.rounds if r.req_id == req.req_id]
        pre = [r for r in mine if r.kind == "prefill"]
        dec = sorted((r for r in mine if r.kind == "decode"), key=lambda r: r.step)
        assert len(pre) == 1 and pre[0].release == req.arrival
        assert [r.step for r in dec] == [1, 2, 3]
        for r in dec:  # decode cadence off the arrival
            assert r.release == pytest.approx(req.arrival + r.step * 1e-4)
        for r in mine:  # traffic leaves only from the home domain
            sends = r.tm.d2.sum(axis=1)
            assert sends[req.home_domain] == r.tm.d2.sum()
            r.tm.validate()


# -- TTFT / per-token metrics -------------------------------------------------


def test_request_metrics_hand_computed_percentiles():
    ttft = np.arange(1.0, 1001.0)  # 1..1000
    rm = RequestMetrics(ttft=ttft, token_latency=np.array([2.0, 4.0]),
                        sojourn=ttft + 1.0)
    p = rm.ttft_percentiles()
    np.testing.assert_allclose(p["p50"], np.percentile(ttft, 50.0))
    np.testing.assert_allclose(p["p99"], np.percentile(ttft, 99.0))
    np.testing.assert_allclose(p["p99.9"], np.percentile(ttft, 99.9))
    assert p["p99.9"] > p["p99"]
    assert rm.token_percentiles()["max"] == 4.0
    s = rm.summary()
    assert set(s) == {"ttft", "token_latency", "sojourn"}


def test_run_serving_single_request_ttft_matches_round_completion():
    """One request: TTFT is exactly the prefill round's completion (arrival
    is the time origin), per-token latency each decode round's sojourn."""
    wl = serve_workload(
        M, N, num_requests=1, mean_gap=1e-3, prefill_tokens=64,
        decode_rounds=2, decode_tokens=4, decode_gap=1e-3, seed=2,
    )
    res = run_serving(wl, "rails")
    st = res.streaming
    assert res.request.ttft[0] == st.round_cct[0]  # arrival == t0 == 0
    for k in (1, 2):
        assert res.request.token_latency[k - 1] == pytest.approx(
            st.round_cct[k] - wl.rounds[k].release, abs=1e-12
        )
    assert res.request.sojourn[0] == pytest.approx(max(st.round_cct.values()))
    # decode rounds are far apart (1ms gap >> drain) -> TTFT < sojourn
    assert res.request.ttft[0] < res.request.sojourn[0]


@pytest.mark.parametrize("delta", [0.5, 7.25, 123.456])
def test_run_serving_shift_invariance_bit_exact(delta):
    """Shifting every arrival/release by Δ leaves every latency metric
    bit-identical (the driver normalizes to the earliest release on a 1 ns
    grid) — the acceptance property of the release-relative semantics."""
    wl = serve_workload(M, N, num_requests=8, mean_gap=3e-4, seed=4)
    a = run_serving(wl, "rails-online")
    b = run_serving(wl.shifted(delta), "rails-online")
    assert np.array_equal(a.request.ttft, b.request.ttft)
    assert np.array_equal(a.request.token_latency, b.request.token_latency)
    assert np.array_equal(a.request.sojourn, b.request.sojourn)
    assert a.request.summary() == b.request.summary()


def test_serve_workload_shifted_preserves_structure():
    wl = serve_workload(M, N, num_requests=3, mean_gap=1e-3, seed=6)
    sh = wl.shifted(2.0)
    assert isinstance(sh, ServeWorkload)
    assert [r.req_id for r in sh.rounds] == [r.req_id for r in wl.rounds]
    for a, b in zip(wl.rounds, sh.rounds):
        assert b.release == a.release + 2.0
        assert b.tm is a.tm  # traffic shared, not copied


# -- seeded regression: tails under the PR-4 fault grid -----------------------


def test_rails_online_feedback_beats_reactive_p99_ttft_under_faults():
    """The serving-path headline: on a degraded fabric (one rail at 0.25x
    + Gilbert-Elliott loss, the PR-4 grid's serving cell), proactive
    rails-online with EWMA health feedback holds a lower p99 TTFT than the
    reactive PLB/REPS baselines. Seeded end to end."""
    wl = serve_workload(
        M, N, num_requests=32, mean_gap=5e-4, prefill_tokens=1024,
        decode_rounds=2, decode_tokens=8, decode_gap=1e-4,
        bytes_per_token=16 * 2**10, seed=12,
    )
    spec = FaultSpec(
        rail_profiles={N - 1: step_profile(0.0, 0.25)},
        loss=LossConfig(rate=0.01, rto=1e-4, bad_rate=0.3,
                        p_enter_bad=0.02, p_leave_bad=0.3),
        seed=11,
    )

    def p99(pol, fb):
        res = run_serving(
            wl, pol, chunk_bytes=256 * 2**10, fault_spec=spec, feedback=fb
        )
        assert (res.streaming.sim.dynamics or {}).get("drops", 0) > 0
        return res.request.ttft_percentiles()["p99"]

    rails = p99("rails-online", True)
    plb = p99("plb", False)
    reps = p99("reps", False)
    assert rails < plb
    assert rails < reps


# -- decode-trace replay (launch/serve.py --sim-fabric) -----------------------


def test_expert_counts_to_matrix_convention():
    counts = np.array([10.0, 0.0, 6.0, 0.0, 2.0])  # 5 experts, M=4 domains
    c2 = expert_counts_to_matrix(counts, 4)
    assert c2.shape == (4, 4)
    np.testing.assert_allclose(np.diag(c2), 0.0)
    # experts 0 and 4 live on domain 0 (round-robin): 12 tokens ingress,
    # expert 2 puts 6 on domain 2; uniform senders split each column evenly.
    np.testing.assert_allclose(c2[:, 0], [0.0, 4.0, 4.0, 4.0])
    np.testing.assert_allclose(c2[:, 2], [2.0, 2.0, 0.0, 2.0])
    assert c2.sum() == pytest.approx(18.0)


def test_simulate_decode_trace_latencies_and_shift_invariance():
    rng = np.random.default_rng(0)
    counts = [rng.integers(1, 40, 8) for _ in range(12)]
    releases = np.arange(12) * 1.5e-3
    a = simulate_decode_trace(counts, releases, M, N, bytes_per_token=16 * 2**10)
    assert a.token_latency.shape == (12,)
    assert np.all(a.token_latency > 0)
    assert "p99.9" in a.summary()
    # arbitrary time origin (a real wall-clock trace) changes nothing
    b = simulate_decode_trace(counts, releases + 1.7e9, M, N,
                              bytes_per_token=16 * 2**10)
    assert np.array_equal(a.token_latency, b.token_latency)


def test_decode_fn_returns_real_gating_counts():
    """The --sim-fabric source: a reduced MoE arch's decode step surfaces
    per-expert routed-token counts (batch * top_k per layer, summed)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import decode_fn, init_cache, init_params

    cfg = get_config("mixtral-8x7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, 2, 8)
    tok = jnp.ones((2, 1), jnp.int32)
    logits, cache2, counts, _read = jax.jit(
        lambda p, c, t: decode_fn(p, cfg, c, t, 0, return_counts=True)
    )(params, cache, tok)
    assert logits.shape == (2, cfg.vocab_size)
    counts = np.asarray(counts)
    assert counts.shape == (cfg.num_experts,)
    # every token routes to top_k experts in every layer
    assert counts.sum() == 2 * cfg.experts_per_token * cfg.num_layers
    # parity with the counts-free path
    logits2, _ = jax.jit(lambda p, c, t: decode_fn(p, cfg, c, t, 0))(
        params, init_cache(cfg, 2, 8), tok
    )
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2),
                               rtol=1e-5, atol=1e-5)
