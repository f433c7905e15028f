"""Device-resident exact-FIFO simulation backend (the ``device`` backend).

The vector backend (:mod:`repro.netsim.fastsim`) already removed the
per-event Python dispatch, but each simulation is still a host-side numpy
pipeline: policy-suite grids, serving SLO sweeps and placement candidate
scoring all call it once per cell, serially. This module ports the same
FIFO busy-period dynamics to jax so one jitted (and ``vmap``-batched)
device call evaluates a whole grid of padded simulations at once.

**Same recurrence, scan formulation.** Per link, completions satisfy
``c_i = max(a_i, c_{i-1}) + t_i``. With ``b_i = a_i + t_i`` this is the
max-plus recurrence ``c_i = max(b_i, c_{i-1} + t_i)``, whose segmented
associative form scans ``(flag, t, b)`` triples::

    combine((fx,tx,bx), (fy,ty,by)) =
        (fx|fy, where(fy, ty, tx+ty), where(fy, by, max(bx+ty, by)))

where ``flag`` marks busy-queue (= link-run) heads after one multi-key
``lax.sort`` by ``(link, clamped arrival, original arrival, start-time
tie, rank tie)``. Levels sweep topologically in the fabric's
``level_kinds`` order (``up -> l2s -> s2l -> down`` flat, with a ``wan``
level on multi-pod fabrics) exactly like the vector backend; per-link
``link_busy`` carry is an arrival clamp whose sort keys preserve the
pre-clamp order, mirroring ``fastsim._busy_clamped``.

**Two scan kernels.** The inner segmented scan runs as a pure
``lax.associative_scan`` over the flat sorted arrays (:func:`scan_impl`:
``lax`` on CPU and TPU, the only platforms supported). A Pallas lane
kernel — grid over blocks of per-link job lanes, a sequential
``fori_loop`` over the padded lane depth doing one max/add per position
across the block's links — runs only through the Pallas interpreter
(``impl="pallas_interpret"``), which keeps its numerics tested. The TPU
cannot compile it as it stands: Pallas TPU takes no f64 operands (XLA's
X64 rewrite cannot split a ``tpu_custom_call``), and even in f32 the
column-at-a-time lane stores are not 128-lane aligned, which Mosaic
refuses. It waits for a redesign in f32 with links on lanes.

**Tolerance contract, not bit parity.** The associative scan
re-associates the additions inside a busy period, and simultaneous-finish
tie keys carry ``(service start, previous-level service order)`` instead
of the engine's full opener chain, so results match ``backend="vector"``
to float tolerance (~1e-9 relative on randomized workloads; identical-
size chunk waves can reorder degenerate CCT ties, same class of drift as
the vector backend's spine-path tolerance) rather than bit for bit.
Makespans agree tightly — equal-arrival ties cannot change a link's last
completion.

**Fixed shapes.** :func:`pad_job_arrays` pads planned per-chunk columns
to power-of-two buckets (sentinel link ids, zero sizes) so jit traces
are reused across calls; :func:`simulate_many_device` stacks a list of
planned simulations to one bucket and runs them through a single
``vmap``-ed device call. Everything is f64 under the ``jax.enable_x64``
context — precision matches the numpy backend without flipping the
process-global x64 flag. The TPU has no native f64; XLA emulates it
there, and the tolerance contract above is checked on the chip.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from .fastsim import (
    ArraySimResult,
    LinkIndex,
    _segment_max,
    _segment_min_like,
)

__all__ = [
    "PlannedJobs",
    "check_device_supports",
    "pad_job_arrays",
    "scan_impl",
    "simulate_chunk_arrays_device",
    "simulate_many_device",
]

#: Smallest padding bucket — tiny collectives share one trace instead of
#: compiling per chunk count.
MIN_BUCKET = 256

#: Links per Pallas grid block (second-to-minor tile of the lane layout).
_LANE_BLOCK = 8

#: Minimum Pallas lane depth (minor dimension — keep it register-tile wide).
_MIN_LANE = 128


# --------------------------------------------------------------------------
# Kernel selection


def scan_impl() -> str:
    """The default segmented-scan implementation: ``lax`` (see the module
    docstring for why the TPU runs it too). Other platforms are refused."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"the device simulator supports cpu and tpu, not {platform!r}"
        )
    return "lax"


_IMPLS = ("lax", "pallas_interpret")


# --------------------------------------------------------------------------
# Segmented max-plus scan — the two implementations


def _maxplus_combine(x, y):
    fx, tx, bx = x
    fy, ty, by = y
    return (
        fx | fy,
        jnp.where(fy, ty, tx + ty),
        jnp.where(fy, by, jnp.maximum(bx + ty, by)),
    )


def _segmented_maxplus_lax(head, service, b):
    """Flat segmented scan: c_i = max(b_i, c_{i-1} + t_i), reset at heads."""
    _, _, c = jax.lax.associative_scan(_maxplus_combine, (head, service, b))
    return c


def _lane_scan_kernel(t_ref, b_ref, out_ref):
    """One block of link lanes: sequential max-plus over lane positions.

    ``t_ref``/``b_ref`` are ``(block_links, lane_depth)``; position ``j``
    advances every link's carry with one vectorized max/add pair. Padded
    lane tails hold ``t=0, b=-inf`` so the carry passes through them.
    """
    from jax.experimental import pallas as pl

    bl, depth = t_ref.shape

    def body(j, c):
        t = t_ref[:, pl.ds(j, 1)][:, 0]
        b = b_ref[:, pl.ds(j, 1)][:, 0]
        c = jnp.maximum(b, c + t)
        out_ref[:, pl.ds(j, 1)] = c[:, None]
        return c

    jax.lax.fori_loop(
        0, depth, body, jnp.full((bl,), -jnp.inf, dtype=t_ref.dtype)
    )


def _segmented_maxplus_pallas(head, service, b, num_segments, lane_depth):
    """Dense-lane Pallas path: scatter sorted jobs into (link, position)
    lanes, scan each lane in the kernel, gather completions back.

    ``lane_depth`` (static) must bound the deepest per-link queue — the
    host computes it from the planned assignment and buckets it to a
    power of two so recompiles stay bounded.
    """
    from jax.experimental import pallas as pl

    f = service.shape[0]
    iota = jnp.arange(f, dtype=jnp.int32)
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1
    seg_start = jax.ops.segment_max(
        jnp.where(head, iota, -1), seg, num_segments=num_segments,
        indices_are_sorted=True,
    )
    pos = iota - seg_start[seg]
    padded_segs = -(-num_segments // _LANE_BLOCK) * _LANE_BLOCK
    lane_t = (
        jnp.zeros((padded_segs, lane_depth), service.dtype)
        .at[seg, pos].set(service, mode="drop")
    )
    lane_b = (
        jnp.full((padded_segs, lane_depth), -jnp.inf, b.dtype)
        .at[seg, pos].set(b, mode="drop")
    )
    out = pl.pallas_call(
        _lane_scan_kernel,
        grid=(padded_segs // _LANE_BLOCK,),
        in_specs=[
            pl.BlockSpec((_LANE_BLOCK, lane_depth), lambda i: (i, 0)),
            pl.BlockSpec((_LANE_BLOCK, lane_depth), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_LANE_BLOCK, lane_depth), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_segs, lane_depth), service.dtype),
        interpret=True,
    )(lane_t, lane_b)
    return out[seg, pos]


# --------------------------------------------------------------------------
# Level scan + topological sweep (traced core)


def _level_scan(el, clamped, arrival, tie1, tie2, service, num_links,
                impl, lane_depth):
    """Exact FIFO scan of one topological level, all links at once.

    Sort keys ``(link, clamped arrival, original arrival, start tie, rank
    tie)`` reproduce the vector backend's service order: the two trailing
    keys only matter on exact float ties, and the original arrival keeps
    the pre-clamp order whenever a ``link_busy`` carry collapses arrivals
    onto one busy-until instant. Returns chunk-order ``(completion,
    start, service rank, per-link last completion)``.
    """
    f = el.shape[0]
    iota = jnp.arange(f, dtype=jnp.int32)
    l_s, a_s, _ao, _t1, _t2, perm = jax.lax.sort(
        (el, clamped, arrival, tie1, tie2, iota), num_keys=5
    )
    service_s = service[perm]
    head = jnp.concatenate(
        [jnp.ones((1,), bool), l_s[1:] != l_s[:-1]]
    )
    c_s = _segmented_maxplus_lax(head, service_s, a_s + service_s) \
        if impl == "lax" else _segmented_maxplus_pallas(
            head, service_s, a_s + service_s, num_links + 1, lane_depth,
        )
    # Re-derive the final step from the scan carry: start = max(a, c_prev)
    # exactly (the scan's re-associated sum would otherwise leak into the
    # reported starts and their use as tie keys).
    c_prev = jnp.where(
        head, -jnp.inf,
        jnp.concatenate([jnp.full((1,), -jnp.inf, c_s.dtype), c_s[:-1]]),
    )
    start_s = jnp.maximum(a_s, c_prev)
    c_s = start_s + service_s
    seg_last = jax.ops.segment_max(
        c_s, l_s.astype(jnp.int32), num_segments=num_links,
        indices_are_sorted=True,
    )
    comp = jnp.zeros(f, c_s.dtype).at[perm].set(c_s)
    start = jnp.zeros(f, c_s.dtype).at[perm].set(start_s)
    rank = jnp.zeros(f, jnp.int32).at[perm].set(iota)
    return comp, start, rank, seg_last


def _scan_core(link_by_level, size, release, entry_rank, rate, latency,
               link_busy, valid, hop_latency, *, impl, lane_depth):
    """The full level sweep over one padded simulation (traced).

    ``link_by_level`` is ``(F, num_levels)`` int32 — the level count is a
    static trace dimension taken from the fabric's ``level_kinds`` (4 flat,
    5 multi-pod); −1 = level not on the path (padded chunks are −1
    everywhere); ``valid`` masks real chunks. Sentinel rows sort to the
    tail as their own zero-service segment and are dropped from every
    per-link reduction by the out-of-range scatter rule. ``latency`` is
    the per-link fixed propagation delay charged after each service (zero
    except WAN lanes). Returns ``(finish, start0, link_volume, link_last,
    makespan)``.
    """
    f = size.shape[0]
    num_links = rate.shape[0]
    rate_ext = jnp.concatenate([rate, jnp.ones((1,), rate.dtype)])
    lat_ext = jnp.concatenate([latency, jnp.zeros((1,), latency.dtype)])
    busy_ext = jnp.concatenate([link_busy, jnp.zeros((1,), link_busy.dtype)])
    arrival = release + 0.0
    tie1 = jnp.zeros(f, release.dtype)
    tie2 = entry_rank.astype(jnp.int32)
    finish = jnp.zeros(f, release.dtype)
    start0 = jnp.zeros(f, release.dtype)
    link_last = link_busy
    link_volume = jnp.zeros(num_links, size.dtype)
    for lv in range(link_by_level.shape[1]):
        links = link_by_level[:, lv]
        served = links >= 0
        el = jnp.where(served, links, num_links).astype(jnp.int32)
        service = jnp.where(served, size / rate_ext[el], 0.0)
        # Clamp against the *carried* busy-until (not the running
        # link_last) — the vector backend clamps each level against the
        # input carry too; within-window backlog is already in the scan.
        clamped = jnp.maximum(arrival, busy_ext[el])
        comp, start, rank, seg_last = _level_scan(
            el, clamped, arrival, tie1, tie2, service, num_links,
            impl, lane_depth,
        )
        if lv == 0:
            start0 = jnp.where(served, start, 0.0)
        finish = jnp.where(served, comp, finish)
        arrival = jnp.where(served, comp + hop_latency + lat_ext[el], arrival)
        tie1 = jnp.where(served, start, tie1)
        tie2 = jnp.where(served, rank, tie2)
        link_volume = link_volume + jax.ops.segment_sum(
            jnp.where(served, size, 0.0), el, num_segments=num_links
        )
        link_last = jnp.maximum(link_last, seg_last)
    makespan = jnp.max(jnp.where(valid, finish, -jnp.inf))
    return finish, start0, link_volume, link_last, makespan


@functools.partial(jax.jit, static_argnames=("impl", "lane_depth"))
def _scan_single_jit(link_by_level, size, release, entry_rank, rate, latency,
                     link_busy, valid, hop_latency, *, impl, lane_depth):
    return _scan_core(
        link_by_level, size, release, entry_rank, rate, latency, link_busy,
        valid, hop_latency, impl=impl, lane_depth=lane_depth,
    )


@functools.partial(jax.jit, static_argnames=("impl", "lane_depth"))
def _scan_batch_jit(link_by_level, size, release, entry_rank, rate, latency,
                    link_busy, valid, hop_latency, *, impl, lane_depth):
    core = functools.partial(_scan_core, impl=impl, lane_depth=lane_depth)
    return jax.vmap(core, in_axes=(0, 0, 0, 0, 0, None, 0, 0, None))(
        link_by_level, size, release, entry_rank, rate, latency, link_busy,
        valid, hop_latency,
    )


# --------------------------------------------------------------------------
# Host-side padding, planning containers, result assembly


@dataclasses.dataclass
class PlannedJobs:
    """One planned simulation in column form (policy already applied).

    The device batch API takes a list of these — the planning phase stays
    host-side (policies are Python), only the fabric dynamics batch.
    """

    link_by_level: np.ndarray  # (F, num_levels) int, -1 = level skipped
    size: np.ndarray  # (F,) float64
    release: np.ndarray  # (F,) float64
    entry_rank: np.ndarray  # (F,) int
    flow_id: np.ndarray | None = None
    round_id: np.ndarray | None = None

    @property
    def num_chunks(self) -> int:
        return int(self.size.size)


def bucket_size(num_chunks: int) -> int:
    """Power-of-two padding bucket (>= MIN_BUCKET) for one chunk count.

    Jit traces key on padded shape, so the number of distinct compilations
    is log2-bounded in the largest collective ever simulated.
    """
    if num_chunks <= MIN_BUCKET:
        return MIN_BUCKET
    return 1 << (num_chunks - 1).bit_length()


def pad_job_arrays(planned: PlannedJobs, bucket: int | None = None):
    """Pad one planned simulation's columns to a fixed bucketed length.

    Padding appends chunks *after* the valid prefix — chunk order within
    ``[0, F)`` is untouched, so flow/round ids stay contiguous runs and
    the host-side segment reductions run on a plain slice. Padded chunks
    carry sentinel link ids (−1 at every level), zero size and past-end
    entry ranks; inside the scan they sort to the tail as zero-service
    segments and contribute to nothing.

    Returns ``(link_by_level, size, release, entry_rank, valid)`` numpy
    arrays of length ``bucket`` (default: :func:`bucket_size`).
    """
    f = planned.num_chunks
    if bucket is None:
        bucket = bucket_size(f)
    if bucket < f:
        raise ValueError(f"bucket {bucket} smaller than job count {f}")
    lbl = np.full(
        (bucket, planned.link_by_level.shape[1]), -1, dtype=np.int32
    )
    lbl[:f] = planned.link_by_level
    size = np.zeros(bucket)
    size[:f] = planned.size
    release = np.zeros(bucket)
    release[:f] = planned.release
    rank = np.arange(bucket, dtype=np.int64)
    rank[:f] = planned.entry_rank
    valid = np.zeros(bucket, dtype=bool)
    valid[:f] = True
    return lbl, size, release, rank, valid


def check_device_supports(topo) -> None:
    """Reject fabrics the device backend cannot express.

    Time-varying link dynamics (rate profiles, PFC/ECN/loss) have no
    fixed-shape scan form; static specs can fall back to the numpy
    ``backend='vector'`` path, dynamic fault_specs need the event engine.
    """
    if topo.has_dynamics:
        raise NotImplementedError(
            "backend='device' supports constant-profile link models only; "
            "use backend='vector' for static specs on the host or "
            "backend='event' for dynamic fault_specs"
        )


def _resolve_impl(impl: str | None) -> str:
    if impl is None:
        return scan_impl()
    if impl not in _IMPLS:
        raise ValueError(f"unknown scan impl {impl!r}; choose {_IMPLS}")
    return impl


def _lane_depth_for(link_by_level_list, num_links: int) -> int:
    """Static Pallas lane depth: deepest per-(level, link) queue, padded.

    Only consulted by the interpreted Pallas kernel; the ``lax`` scan
    runs on the flat sorted arrays and ignores it (pass 0 so the jit
    cache key stays constant there).
    """
    deepest = 1
    for lbl in link_by_level_list:
        for lv in range(lbl.shape[1]):
            col = lbl[:, lv]
            col = col[col >= 0]
            if col.size:
                deepest = max(deepest, int(np.bincount(col).max()))
    return max(_MIN_LANE, 1 << (deepest - 1).bit_length())


def _result_from_rows(index, finish, start0, link_volume, link_last,
                      makespan, planned, had_busy):
    """Assemble an :class:`ArraySimResult` from one device row (host side)."""
    f = planned.num_chunks
    finish = finish[:f]
    release = np.asarray(planned.release, dtype=np.float64)
    flow_id = (
        planned.flow_id if planned.flow_id is not None
        else np.arange(f, dtype=np.int64)
    )
    round_id = (
        planned.round_id if planned.round_id is not None
        else np.zeros(f, dtype=np.int64)
    )
    flow_ids, flow_finish = _segment_max(finish, np.asarray(flow_id))
    round_ids, round_finish = _segment_max(finish, np.asarray(round_id))
    return ArraySimResult(
        finish=finish,
        start=start0[:f],
        link_bytes={
            nm: float(v) for nm, v in zip(index.names, link_volume)
        },
        makespan=float(makespan) if f else 0.0,
        flow_ids=flow_ids,
        flow_finish=flow_finish,
        round_ids=round_ids,
        round_finish=round_finish,
        flow_release=_segment_min_like(release, np.asarray(flow_id)),
        round_release=_segment_min_like(release, np.asarray(round_id)),
        link_last=link_last if had_busy else None,
    )


def _check_level0(link_by_level, f) -> None:
    if f and np.any(np.asarray(link_by_level)[:f, 0] < 0):
        raise ValueError("every path must start with an up-link (level 0)")


def simulate_chunk_arrays_device(
    index: LinkIndex,
    link_by_level: np.ndarray,
    size: np.ndarray,
    release: np.ndarray,
    entry_rank: np.ndarray,
    hop_latency: float = 1e-6,
    flow_id: np.ndarray | None = None,
    round_id: np.ndarray | None = None,
    link_busy: np.ndarray | None = None,
    bucket: int | None = None,
    impl: str | None = None,
) -> ArraySimResult:
    """Drop-in device counterpart of ``fastsim.simulate_chunk_arrays``.

    Same signature and result type; the scan runs as one jitted device
    call on padded fixed-shape arrays. ``impl`` forces a scan kernel
    (``lax`` or ``pallas_interpret``) — default is :func:`scan_impl`.
    Parity with the vector backend is float tolerance, not bit-exact (see the module docstring).
    """
    check_device_supports(index.topo)
    impl = _resolve_impl(impl)
    f = size.size
    num_links = index.num_links
    planned = PlannedJobs(
        link_by_level=np.asarray(link_by_level),
        size=np.asarray(size, dtype=np.float64),
        release=np.asarray(release, dtype=np.float64),
        entry_rank=np.asarray(entry_rank, dtype=np.int64),
        flow_id=flow_id,
        round_id=round_id,
    )
    _check_level0(planned.link_by_level, f)
    if link_busy is not None:
        busy = np.asarray(link_busy, dtype=np.float64)
        if busy.shape != (num_links,):
            raise ValueError(
                f"link_busy must be ({num_links},), got {busy.shape}"
            )
    else:
        busy = np.zeros(num_links)
    lbl, psize, prelease, prank, valid = pad_job_arrays(planned, bucket)
    lane_depth = (
        _lane_depth_for([planned.link_by_level], num_links)
        if impl != "lax" else 0
    )
    with jax.enable_x64(True):
        finish, start0, link_volume, link_last, makespan = _scan_single_jit(
            jnp.asarray(lbl), jnp.asarray(psize), jnp.asarray(prelease),
            jnp.asarray(prank), jnp.asarray(index.rate),
            jnp.asarray(index.latency), jnp.asarray(busy),
            jnp.asarray(valid),
            jnp.asarray(hop_latency, dtype=jnp.float64),
            impl=impl, lane_depth=lane_depth,
        )
    return _result_from_rows(
        index,
        np.asarray(finish), np.asarray(start0), np.asarray(link_volume),
        np.asarray(link_last), np.asarray(makespan), planned,
        had_busy=link_busy is not None,
    )


def simulate_many_device(
    index: LinkIndex,
    planned: list[PlannedJobs],
    hop_latency: float = 1e-6,
    link_busy: np.ndarray | None = None,
    bucket: int | None = None,
    impl: str | None = None,
) -> list[ArraySimResult]:
    """Batched sweep execution: many planned simulations, one device call.

    All members pad to one shared bucket (sized for the largest) and run
    through the ``vmap``-ed scan — the policy-suite grid, placement
    candidate scoring and SLO sweeps become a single dispatch instead of
    a Python loop over simulations. ``link_busy`` (optional) is a
    ``(B, num_links)`` per-member carry.
    """
    check_device_supports(index.topo)
    impl = _resolve_impl(impl)
    if not planned:
        return []
    num_links = index.num_links
    b = len(planned)
    if bucket is None:
        bucket = bucket_size(max(p.num_chunks for p in planned))
    for p in planned:
        _check_level0(p.link_by_level, p.num_chunks)
    cols = [pad_job_arrays(p, bucket) for p in planned]
    lbl = np.stack([c[0] for c in cols])
    size = np.stack([c[1] for c in cols])
    release = np.stack([c[2] for c in cols])
    rank = np.stack([c[3] for c in cols])
    valid = np.stack([c[4] for c in cols])
    rate = np.broadcast_to(index.rate, (b, num_links))
    if link_busy is not None:
        busy = np.asarray(link_busy, dtype=np.float64)
        if busy.shape != (b, num_links):
            raise ValueError(
                f"link_busy must be ({b}, {num_links}), got {busy.shape}"
            )
    else:
        busy = np.zeros((b, num_links))
    lane_depth = (
        _lane_depth_for([p.link_by_level for p in planned], num_links)
        if impl != "lax" else 0
    )
    with jax.enable_x64(True):
        finish, start0, link_volume, link_last, makespan = _scan_batch_jit(
            jnp.asarray(lbl), jnp.asarray(size), jnp.asarray(release),
            jnp.asarray(rank), jnp.asarray(rate),
            jnp.asarray(index.latency), jnp.asarray(busy),
            jnp.asarray(valid),
            jnp.asarray(hop_latency, dtype=jnp.float64),
            impl=impl, lane_depth=lane_depth,
        )
    finish = np.asarray(finish)
    start0 = np.asarray(start0)
    link_volume = np.asarray(link_volume)
    link_last = np.asarray(link_last)
    makespan = np.asarray(makespan)
    return [
        _result_from_rows(
            index, finish[i], start0[i], link_volume[i], link_last[i],
            makespan[i], p, had_busy=link_busy is not None,
        )
        for i, p in enumerate(planned)
    ]
