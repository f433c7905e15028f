#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration in ``bench/configs/<config>.json`` (which names its
plain reference, a file under ``bench/reference/``), its traffic mix in
``bench/traffic/<traffic>.json`` (which names a driver in
``bench/drivers/<driver>.py``), the limits of its output check in
``bench/workloads/<cell>.json``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``. A new cell, mix, driver or metric is new
files and new entries, with no file here edited.

A run refuses any device but a TPU, and fewer chips than the cell asks
for. It makes weights and inputs from ``--seed``, warms up every program
the window drives (set-up, reported as ``setup_s``), measures for
``--seconds``, then checks what the timed path produced against the plain
reference. With ``--trace 1`` the window runs under the profiler and the
result carries the per-layer metrics instead of the end-to-end ones.

Standard output ends with one JSON object; standard error ends with the
numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from the start of the process

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import faulthandler  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Refused(SystemExit):
    """The run cannot measure this cell here; no result is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path

    @property
    def model(self) -> dict:
        return self.config["model"]

    @functools.cached_property
    def reference(self):
        """The plain reference module that the configuration names."""
        return load_module(self.root / self.config["reference"])


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise Refused(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise Refused(f"missing {path}") from None


def load_cell(root: Path, name: str) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    bench = root / "bench"
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_read_json(bench / "configs" / f"{entry['config']}.json"),
        traffic=_read_json(bench / "traffic" / f"{entry['traffic']}.json"),
        limits=_read_json(bench / "workloads" / f"{name}.json")["limits"],
        end_to_end=e2e,
        per_layer=per_layer,
        root=root,
    )


def load_peaks(root: Path, device_kind: str) -> dict:
    peaks = _read_json(root / "bench" / "peaks.json")
    if device_kind not in peaks:
        raise Refused(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return peaks[device_kind]


class CompileLog:
    """Backend compilations, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), secs))


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader may read."""

    cell: Cell
    trace: object  # bench.trace.Trace
    window: dict  # the cell driver's counters of the traced window
    peaks: dict
    chips: int


def _setup_jax():
    import jax
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # Every program, however quick to compile, is kept: a second run of a
    # cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def _devices(cell: Cell, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise Refused(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise Refused(f"bench: cell {cell.name} needs {cell.chips} chips, JAX found {len(devices)}")
    return devices[: cell.chips]


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        require_chip: bool = True) -> tuple[dict, list]:
    """One run of one cell: (result object, checked numbers).
    ``require_chip=False`` lets the CPU rehearsals and fault tests drive it."""
    import jax

    cell = load_cell(root, workload)
    devices = _devices(cell, require_chip)
    peaks = load_peaks(root, devices[0].device_kind) if require_chip or trace else {}
    _setup_jax()
    compiles = CompileLog()
    driver = load_module(root / "bench" / "drivers" / f"{cell.traffic['driver']}.py")

    job = driver.Job(cell, seed, devices)
    setup_s = time.perf_counter() - T_START - job.check_setup_s
    mark = len(compiles.events)
    tdir = root / ".bench_trace"
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation("bench.window"):
        window = job.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.events[mark:]
    print(f"compiles in window: {len(in_window)} {in_window}", flush=True)
    memory_peak = _memory_peak(devices)
    job.free()
    t_check = time.perf_counter()
    checks = job.check()
    print(f"bench: check {time.perf_counter() - t_check:.1f} s", file=sys.stderr, flush=True)
    correct = window["failed"] == 0 and all(c["value"] <= c["limit"] for c in checks)

    metrics: dict = {}
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"]}
    if trace:
        from bench import trace as tr

        t_reduce = time.perf_counter()
        reduced = tr.load(str(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        used = sorted(reduced.devices)
        busy = [tr.busy_ns(reduced, d) for d in used]
        device["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        device["window_s"] = reduced.window_ns * 1e-9
        reading = Reading(cell, reduced, window, peaks, len(devices))
        for m in cell.per_layer:
            reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr.top_ops(reduced),
                               "idle_gaps": tr.idle_gaps(reduced)}
        print(f"bench: trace reduction {time.perf_counter() - t_reduce:.1f} s", file=sys.stderr,
              flush=True)
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else window["metrics"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    return result, checks


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    faulthandler.enable()  # a crash in the runtime prints the Python stack on stderr
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    result, checks = run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    # Everything is printed and this process started no other. Leave without
    # the interpreter's teardown, in which the TPU runtime's threads and the
    # arrays still cached by JAX are destroyed in no fixed order.
    os._exit(0)


if __name__ == "__main__":
    main()
