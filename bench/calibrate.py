#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... --control-seeds 1,2,3

For every seed it builds the cell's timed path as a run does and reads the
numbers that a run compares: for training, the first steps of set-up; for
serving, one closed-loop batch at the cell's own load. Then, on the
control seeds, the same numbers of

* the control: the plain reference computed in float8 (e4m3, one scale
  per tensor), put in the program's place;
* the faults a training cell can have, planted where they arise: half of
  each batch left out with the mean taken over the rest (in the reference
  put in the program's place), and the exchange between chips left out
  (in the program: its MoE all-to-all returns each chip's own buckets);
* for serving, one served token altered where it is produced.

A state left unchanged reads 1 on the gradient and the change of the
parameters by their definition and needs no run. Prints one JSON object
per reading and writes them all to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _train(run, cell, seeds, control_seeds, devices, emit):
    import numpy as np

    from bench.check import train_numbers

    ref = cell.reference

    driver = run.load_module(ROOT / "bench" / "drivers" / "train.py")
    refs, models = {}, {}

    def reference(job, precision="f32", batch_fault=False):
        arch = ref.Arch.from_model({**cell.model, **cell.config["assumed"]}, job.ctx.ep)
        kind = (precision, batch_fault)
        if kind not in models:
            opt = driver.reference_optimizer(ref, cell.config["optimizer"])
            models[kind] = ref.TrainReference(arch, opt, np.array(devices), precision, batch_fault)
        batches = [job.data.batch(i) for i in range(len(job.losses))]
        return models[kind].run(job.key, batches)

    for seed in seeds:
        job = driver.Job(cell, seed, devices)
        prog = job.program()
        job.free()
        refs[seed] = reference(job)
        emit({"seed": seed, "kind": "program", **train_numbers(prog, refs[seed]),
              "program": prog, "reference": refs[seed]})
        if seed in control_seeds:
            for kind, out in (("control_fp8", reference(job, "fp8")),
                              ("fault_half_batch", reference(job, "f32", True))):
                emit({"seed": seed, "kind": kind, **train_numbers(out, refs[seed])})
        del job
    import repro.models.moe as moe

    kept = moe._a2a
    moe._a2a = lambda payload, axis, cfg: payload
    try:
        for seed in control_seeds:
            job = driver.Job(cell, seed, devices)
            prog = job.program()
            job.free()
            emit({"seed": seed, "kind": "fault_no_exchange", **train_numbers(prog, refs[seed])})
            del job
    finally:
        moe._a2a = kept


def _serve(run, cell, seeds, control_seeds, devices, emit):
    import numpy as np

    from bench.check import served_gaps, serve_numbers

    driver = run.load_module(ROOT / "bench" / "drivers" / "serve.py")

    def numbers(logits, tokens):
        gaps = served_gaps(logits, tokens)
        return {**serve_numbers(logits, tokens), "widest_gap": float(gaps.max()),
                "p95_gap": float(np.quantile(gaps, 0.95)),
                "share_not_best": float((gaps > 0).mean())}

    for seed in seeds:
        job = driver.Job(cell, seed, devices)
        job._run_batch(job.prompt_len, job.gen_len)
        job.free()
        logits, served = job.reference_logits("f32")
        emit({"seed": seed, "kind": "program", **numbers(logits, served),
              "tokens": int(served.size)})
        if seed in control_seeds:
            low, _ = job.reference_logits("fp8")
            emit({"seed": seed, "kind": "control_fp8", **numbers(logits, low.argmax(1))})
            altered = served.copy()
            at = np.random.default_rng([seed, 11]).integers(altered.size)
            altered[at] = (altered[at] + 1) % logits.shape[1]
            emit({"seed": seed, "kind": "fault_token_altered", **numbers(logits, altered)})
        del job


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-chip", action="store_true", help="allow a CPU (rehearsal only)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run

    cell = run.load_cell(ROOT, args.workload)
    devices = run._devices(cell, not args.no_chip)
    run._setup_jax()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k not in ("program", "reference")}),
              flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(rows, indent=1))

    kind = cell.traffic["driver"]
    {"train": _train, "serve": _serve}[kind](run, cell, seeds, control, devices, emit)


if __name__ == "__main__":
    main()
