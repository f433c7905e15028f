"""Device-backend parity — the jax scan must match the vector backend.

The device backend (`repro.netsim.devicesim`) re-expresses the vector
prefix-scan dynamics as jitted `lax` / Pallas segmented scans over
fixed-shape padded arrays. The contract is *float tolerance* against the
vector backend (reductions reassociate on device; degenerate cross-link
ties may reorder — see the module docstring), plus three structural
invariants pinned here: padding buckets never change results, a vmap
batch of one equals the single-simulation entry point, and dynamic
FaultSpecs are rejected with an error naming the vector/event fallback.
"""

import numpy as np
import pytest

from repro.core.traffic import uniform_workload
from repro.netsim import FaultSpec, LinkIndex, run_collective, step_profile
from repro.netsim.devicesim import (
    PlannedJobs,
    bucket_size,
    check_device_supports,
    pad_job_arrays,
    simulate_chunk_arrays_device,
    simulate_many_device,
)
from repro.netsim.fastsim import paths_from_jobs, simulate_chunk_arrays
from repro.netsim.simulate import run_policy_suite
from repro.netsim.topology import RailTopology
from test_fastsim import CHUNK, M, N, _FixedPathPolicy, _random_jobs, _workloads

ALL_POLICIES = ("ecmp", "plb", "minrtt", "reps", "rails")


def _planned_random(topo, index, seed, num_chunks=200, spine_fraction=0.5):
    """Columns for one randomized fixed-path simulation (non-degenerate:
    random sizes and releases, so tie-order effects cannot hide behind
    equal-chunk waves and parity is tight)."""
    from repro.netsim.events import Engine

    rng = np.random.default_rng(seed)
    jobs, paths = _random_jobs(topo, rng, num_chunks, spine_fraction)
    ordered = _FixedPathPolicy(topo, paths).assign_batch(
        Engine(topo), jobs, now=0.0
    )
    lbl, rank = paths_from_jobs(ordered, index, num_chunks)
    size = np.zeros(num_chunks)
    release = np.zeros(num_chunks)
    for js in jobs.values():
        for j in js:
            size[j.chunk_id] = j.size
            release[j.chunk_id] = j.arrival_time
    return PlannedJobs(
        link_by_level=lbl, size=size, release=release, entry_rank=rank
    )


# -- randomized parity (the tight anchor) -------------------------------------


@pytest.mark.parametrize("spine_fraction", [0.0, 0.5, 1.0])
def test_device_matches_vector_randomized(spine_fraction):
    """Random sizes + releases, rail/spine paths mixed: per-chunk finish
    times match the vector backend at float tolerance."""
    topo = RailTopology(3, 3)
    index = LinkIndex(topo)
    for seed in (21, 22):
        p = _planned_random(topo, index, seed, 200, spine_fraction)
        res_v = simulate_chunk_arrays(
            index, p.link_by_level, p.size, p.release, p.entry_rank
        )
        res_d = simulate_chunk_arrays_device(
            index, p.link_by_level, p.size, p.release, p.entry_rank
        )
        np.testing.assert_allclose(res_d.finish, res_v.finish, rtol=1e-9)
        np.testing.assert_allclose(res_d.start, res_v.start, rtol=1e-9, atol=1e-18)
        assert np.isclose(res_d.makespan, res_v.makespan, rtol=1e-12)
        for link, volume in res_v.link_bytes.items():
            assert np.isclose(res_d.link_bytes[link], volume, rtol=1e-9)


def test_device_link_busy_carry_matches_vector():
    """The per-link busy-until carry (the gateway's window chaining)
    threads through the device scan identically."""
    topo = RailTopology(3, 3)
    index = LinkIndex(topo)
    p1 = _planned_random(topo, index, 31, 150, 0.5)
    p2 = _planned_random(topo, index, 32, 150, 0.5)
    busy = np.zeros(index.num_links)
    rv1 = simulate_chunk_arrays(
        index, p1.link_by_level, p1.size, p1.release, p1.entry_rank,
        link_busy=busy,
    )
    rd1 = simulate_chunk_arrays_device(
        index, p1.link_by_level, p1.size, p1.release, p1.entry_rank,
        link_busy=busy,
    )
    np.testing.assert_allclose(rd1.link_last, rv1.link_last, rtol=1e-9)
    rv2 = simulate_chunk_arrays(
        index, p2.link_by_level, p2.size, p2.release, p2.entry_rank,
        link_busy=rv1.link_last,
    )
    rd2 = simulate_chunk_arrays_device(
        index, p2.link_by_level, p2.size, p2.release, p2.entry_rank,
        link_busy=rd1.link_last,
    )
    np.testing.assert_allclose(rd2.finish, rv2.finish, rtol=1e-9)
    assert np.isclose(rd2.makespan, rv2.makespan, rtol=1e-12)


# -- end-to-end parity on the paper workloads ---------------------------------


@pytest.mark.parametrize("policy", ("rails", "ecmp"))
def test_device_matches_vector_collectives(policy):
    """run_collective(backend="device") matches the vector backend on the
    paper workloads: makespan at fp tolerance everywhere; CCT stats at
    2e-2 for non-rails policies (equal-size chunk waves at t=0 are
    massively degenerate — every flow in a wave ties — and device
    tie-breaking may pick a different, equally valid, FIFO schedule that
    shifts mid-distribution percentiles by a service quantum; the
    randomized tests above are the tight anchor)."""
    for name, tm in _workloads().items():
        v = run_collective(tm, policy, chunk_bytes=CHUNK, seed=3, backend="vector")
        d = run_collective(tm, policy, chunk_bytes=CHUNK, seed=3, backend="device")
        assert np.isclose(d.makespan, v.makespan, rtol=1e-9), (policy, name)
        cct_rtol = 1e-9 if policy == "rails" else 2e-2
        for key, val in v.cct.items():
            assert np.isclose(d.cct[key], val, rtol=cct_rtol, atol=1e-15), (
                policy, name, key,
            )
        np.testing.assert_allclose(d.nic_tx, v.nic_tx, rtol=1e-9)
        np.testing.assert_allclose(d.nic_rx, v.nic_rx, rtol=1e-9)


def test_policy_suite_device_batches_whole_grid():
    """run_policy_suite(backend="device") — one vmap dispatch for every
    policy — matches the per-policy vector loop."""
    tm = _workloads()["sparse04"]
    vec = run_policy_suite(tm, ALL_POLICIES, chunk_bytes=CHUNK, seed=3,
                           backend="vector")
    dev = run_policy_suite(tm, ALL_POLICIES, chunk_bytes=CHUNK, seed=3,
                           backend="device")
    assert set(dev) == set(vec)
    for p in ALL_POLICIES:
        assert np.isclose(dev[p].makespan, vec[p].makespan, rtol=1e-9), p
        cct_rtol = 1e-9 if p == "rails" else 2e-2
        for key, val in vec[p].cct.items():
            assert np.isclose(dev[p].cct[key], val, rtol=cct_rtol), (p, key)



@pytest.mark.parametrize("m,n,chunks", [(8, 4, 4000), (16, 8, 4000)])
def test_device_rails_sparse_ties_reorder_only(m, n, chunks):
    """Sparse Mixtral-gated traffic under ``rails``: every sender's
    equal-size chunks for one expert converge on one downlink, where
    arrivals from different uplinks tie to within a few ulps. The device
    scan's prefix sums and tie keys may order such a tie differently
    from ``vector``; only which flow's chunk goes first changes. So:
    makespan to 1e-12 and the CCT tail (p95 and up) to 1e-9, every
    downlink's sorted completions to 1e-12 (the same FIFO schedule up
    to the order of tied chunks), and the mid-distribution CCT
    statistics to the 2e-2 tie tolerance."""
    from repro.core.traffic import mixtral_trace_workload
    from repro.netsim.simulate import _plan_collective

    tm = mixtral_trace_workload(m, n, mode="sparse", seed=0)
    chunk_bytes = tm.total_bytes() / chunks
    v = run_collective(tm, "rails", chunk_bytes=chunk_bytes, backend="vector")
    d = run_collective(tm, "rails", chunk_bytes=chunk_bytes, backend="device")
    assert np.isclose(d.makespan, v.makespan, rtol=1e-12)
    for key, val in v.cct.items():
        rtol = 1e-9 if key in ("p95", "p99", "p99.9", "max") else 2e-2
        assert np.isclose(d.cct[key], val, rtol=rtol), key

    topo = RailTopology(m, n)
    index = LinkIndex(topo)
    ja, lbl, rank = _plan_collective(topo, index, tm, "rails", chunk_bytes, 0, 64)
    fin_v = simulate_chunk_arrays(index, lbl, ja.size, ja.release, rank).finish
    fin_d = simulate_chunk_arrays_device(index, lbl, ja.size, ja.release, rank).finish
    last = lbl[:, -1]
    assert (last >= 0).all()  # rails paths end on a downlink
    for link in np.unique(last):
        on = last == link
        np.testing.assert_allclose(
            np.sort(fin_d[on]), np.sort(fin_v[on]), rtol=1e-12
        )


# -- structural invariants ----------------------------------------------------


def test_padding_invariance():
    """Results are invariant to the padding bucket: the default bucket and
    a 4x larger one produce bit-identical outputs (padded chunks are
    zero-service tail segments by construction)."""
    topo = RailTopology(3, 3)
    index = LinkIndex(topo)
    p = _planned_random(topo, index, 41, 100, 0.5)
    base = bucket_size(p.num_chunks)
    r1 = simulate_chunk_arrays_device(
        index, p.link_by_level, p.size, p.release, p.entry_rank, bucket=base
    )
    r2 = simulate_chunk_arrays_device(
        index, p.link_by_level, p.size, p.release, p.entry_rank,
        bucket=4 * base,
    )
    np.testing.assert_array_equal(r1.finish, r2.finish)
    np.testing.assert_array_equal(r1.start, r2.start)
    assert r1.makespan == r2.makespan
    assert r1.link_bytes == r2.link_bytes


def test_pad_job_arrays_contract():
    """Padding appends after the valid prefix: sentinel links, zero size,
    past-end ranks; a bucket smaller than the job count is an error."""
    topo = RailTopology(3, 3)
    index = LinkIndex(topo)
    p = _planned_random(topo, index, 42, 50, 0.0)
    lbl, size, release, rank, valid = pad_job_arrays(p)
    b = bucket_size(50)
    assert lbl.shape[0] == size.size == release.size == rank.size == b
    assert valid[:50].all() and not valid[50:].any()
    np.testing.assert_array_equal(lbl[:50], p.link_by_level)
    assert (lbl[50:] == -1).all()
    assert (size[50:] == 0.0).all()
    np.testing.assert_array_equal(rank[50:], np.arange(50, b))
    with pytest.raises(ValueError, match="bucket"):
        pad_job_arrays(p, bucket=32)


def test_batch_of_one_matches_single():
    """simulate_many_device([p]) — the vmap-ed batch path — is bit-identical
    to the single-simulation entry point on the same bucket."""
    topo = RailTopology(3, 3)
    index = LinkIndex(topo)
    p = _planned_random(topo, index, 43, 120, 0.5)
    single = simulate_chunk_arrays_device(
        index, p.link_by_level, p.size, p.release, p.entry_rank
    )
    (batched,) = simulate_many_device(index, [p])
    np.testing.assert_array_equal(batched.finish, single.finish)
    np.testing.assert_array_equal(batched.start, single.start)
    assert batched.makespan == single.makespan


def test_batch_members_match_separate_calls():
    """A heterogeneous batch (different job counts → shared bucket) gives
    each member the same answer as running it alone."""
    topo = RailTopology(3, 3)
    index = LinkIndex(topo)
    ps = [
        _planned_random(topo, index, 51, 60, 0.0),
        _planned_random(topo, index, 52, 140, 0.5),
        _planned_random(topo, index, 53, 90, 1.0),
    ]
    batch = simulate_many_device(index, ps)
    for p, b in zip(ps, batch):
        alone = simulate_chunk_arrays_device(
            index, p.link_by_level, p.size, p.release, p.entry_rank
        )
        np.testing.assert_allclose(b.finish, alone.finish, rtol=1e-12)
        assert np.isclose(b.makespan, alone.makespan, rtol=1e-12)


def test_interpret_kernel_matches_lax():
    """The Pallas lane-scan kernel (interpret mode on CPU) is numerically
    identical to the associative-scan fallback — the parity CI relies on
    this to validate the kernel without an accelerator."""
    topo = RailTopology(3, 3)
    index = LinkIndex(topo)
    p = _planned_random(topo, index, 61, 100, 0.5)
    r_lax = simulate_chunk_arrays_device(
        index, p.link_by_level, p.size, p.release, p.entry_rank, impl="lax"
    )
    r_pal = simulate_chunk_arrays_device(
        index, p.link_by_level, p.size, p.release, p.entry_rank,
        impl="pallas_interpret",
    )
    np.testing.assert_allclose(r_pal.finish, r_lax.finish, rtol=1e-12)
    assert np.isclose(r_pal.makespan, r_lax.makespan, rtol=1e-12)


def test_bucket_sizes_are_bounded_powers_of_two():
    assert bucket_size(1) == 256  # MIN_BUCKET floor
    assert bucket_size(256) == 256
    assert bucket_size(257) == 512
    assert bucket_size(1000) == 1024


# -- unsupported-dynamics rejection -------------------------------------------


def test_device_rejects_dynamic_fault_spec_naming_fallback():
    """Non-constant LinkModels raise NotImplementedError naming the
    vector (static) and event (dynamic) fallbacks; an *unspecified*
    backend still silently falls back to the event engine."""
    spec = FaultSpec(rail_profiles={0: step_profile(1e-3, 0.5)})
    with pytest.raises(NotImplementedError, match="vector"):
        check_device_supports(RailTopology(2, 2, fault_spec=spec))
    tm = uniform_workload(2, 2, bytes_per_pair=CHUNK)
    with pytest.raises(NotImplementedError, match="backend='event'"):
        run_collective(
            tm, "rails", chunk_bytes=CHUNK, backend="device", fault_spec=spec
        )
    # No explicit backend: dynamics resolve to the event engine as before.
    res = run_collective(tm, "rails", chunk_bytes=CHUNK, fault_spec=spec)
    assert res.makespan > 0.0


def test_device_accepts_constant_fault_spec():
    """Constant-profile specs fold into static rates — supported, and in
    parity with the vector backend."""
    spec = FaultSpec(rail_profiles={0: 1.0, 1: 0.5})
    tm = uniform_workload(M, N, bytes_per_pair=8 * 2**20)
    v = run_collective(
        tm, "rails", chunk_bytes=CHUNK, seed=3, backend="vector", fault_spec=spec
    )
    d = run_collective(
        tm, "rails", chunk_bytes=CHUNK, seed=3, backend="device", fault_spec=spec
    )
    assert np.isclose(d.makespan, v.makespan, rtol=1e-9)


# -- downstream consumers -----------------------------------------------------


def test_score_placements_batch_matches_loop():
    """Placement candidate scoring: the one-dispatch batch equals the
    per-candidate device loop exactly, and the vector loop at tolerance."""
    from repro.placement.search import (
        greedy_placement,
        score_placement,
        score_placements_batch,
        static_placement,
    )

    rng = np.random.default_rng(7)
    counts = rng.integers(0, 200, size=(4, 16)).astype(float)
    bpt = 16 * 2**10
    pls = [static_placement(16, 4), greedy_placement(counts, 4)]
    batch = score_placements_batch(counts, pls, 4, bpt)
    for score, pl in zip(batch, pls):
        dev = score_placement(counts, pl, 4, bpt, backend="device")
        vec = score_placement(counts, pl, 4, bpt, backend="vector")
        assert score == dev
        assert np.isclose(score, vec, rtol=1e-9)
