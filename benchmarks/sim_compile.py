#!/usr/bin/env python3
"""Cold compile seconds of the device simulator's scan, per padding bucket.

The bucket a collective can use on a chip is capped by how long its scan
takes to compile (ROADMAP §1.3). This compiles ``devicesim``'s jitted
scan at each given bucket, with the persistent compile cache off, and
prints one line per bucket as soon as it is done, so a run cut by an
outer time limit still reports every bucket that finished:

    python benchmarks/sim_compile.py 16384 32768 65536        # attached chip
    timeout 310 python benchmarks/sim_compile.py 65536         # cap one bucket
    python benchmarks/sim_compile.py --batch 5 1024            # vmap-ed suite
    python benchmarks/sim_compile.py --topology v5e:2x2 4096   # no chip needed

``--topology`` compiles for a described TPU topology instead of the
attached device (the compile-only rehearsal; its seconds are the
host's, not the chip host's). Shapes follow ``chip_smoke.py``: the
single scan on the 512-NIC (64x8) fabric, the batch on the policy
suite's 8x8 fabric.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("buckets", type=int, nargs="+", help="padded chunk counts")
    ap.add_argument("--batch", type=int, default=0,
                    help="compile the vmap-ed batch scan for this many members")
    ap.add_argument("--topology", default=None,
                    help="compile for a described TPU topology, e.g. v5e:2x2")
    args = ap.parse_args(argv)

    from repro.netsim import LinkIndex
    from repro.netsim.devicesim import _scan_batch_jit, _scan_single_jit
    from repro.netsim.topology import RailTopology

    if args.topology:
        from jax.experimental import topologies

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        device = topologies.get_topology_desc(
            platform="tpu", topology_name=args.topology
        ).devices[0]
    else:
        device = jax.devices()[0]
    jax.config.update("jax_enable_compilation_cache", False)
    sharding = SingleDeviceSharding(device)
    domains, rails = (8, 8) if args.batch else (64, 8)
    index = LinkIndex(RailTopology(domains, rails))
    links, levels = index.num_links, index.num_levels
    batch = (args.batch,) if args.batch else ()
    fn = _scan_batch_jit if args.batch else _scan_single_jit
    print(f"device {device.device_kind}, {domains}x{rails} fabric, "
          f"{links} links, {levels} levels, batch {args.batch or 'none'}",
          flush=True)

    def arg(shape, dtype, batched=True):
        return jax.ShapeDtypeStruct((batch if batched else ()) + shape, dtype,
                                    sharding=sharding)

    for f in args.buckets:
        with jax.enable_x64(True):
            t0 = time.perf_counter()
            lowered = fn.lower(
                arg((f, levels), jnp.int32), arg((f,), jnp.float64),
                arg((f,), jnp.float64), arg((f,), jnp.int64),
                arg((links,), jnp.float64), arg((links,), jnp.float64, False),
                arg((links,), jnp.float64), arg((f,), jnp.bool_),
                arg((), jnp.float64, False), impl="lax", lane_depth=0,
            )
            t1 = time.perf_counter()
            lowered.compile()
            t2 = time.perf_counter()
        print(f"bucket {f}: lower {t1 - t0:.1f} s, compile {t2 - t1:.1f} s",
              flush=True)


if __name__ == "__main__":
    main()
