"""Helpers for the benchmark's own tests: a copy of the benchmark cut to a
size the CPU holds, the cells of a tree's ``BENCHMARK.json`` with their
drivers, and runs of a copy in a child process with virtual devices.

Run with ``python -m pytest bench/tests`` from the root of the checkout.

Every cell's limits for the small copy are in its own limits file,
``bench/workloads/<cell>.json``, under ``small_limits`` (beside the chip's
``limits``), so a cell added as new files is covered here with no file
edited.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SMALL_MODEL = dict(d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=128,
                   moe_d_ff=128, vocab_size=512, xent_chunk=64)


def cells(root: Path = ROOT) -> list[dict]:
    """Each cell of ``root``'s ``BENCHMARK.json``: its name, chips and the
    driver its traffic mix names."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    out = []
    for w in spec["workloads"]:
        traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        out.append({"name": w["name"], "chips": int(w["chips"]), "driver": traffic["driver"]})
    return out


def small_limits(root: Path, cell: str) -> dict:
    """The small copy's limits of ``cell``, from its limits file in ``root``."""
    path = root / "bench" / "workloads" / f"{cell}.json"
    limits = json.loads(path.read_text()).get("small_limits")
    if not limits:
        raise KeyError(f"{path} has no small_limits: the limits of the benchmark's CPU copy")
    return limits


def make_small_tree(dst: Path, src: Path = ROOT) -> Path:
    """The benchmark's files of the tree ``src`` with every configuration
    cut to CPU widths, short requests and sequences, and each cell's
    ``small_limits`` as its limits. ``src`` is left as it was."""
    shutil.copytree(src / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(src / "BENCHMARK.json", dst / "BENCHMARK.json")
    (dst / "src").symlink_to((src / "src").resolve())
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        path = dst / c["file"]
        cfg = json.loads(path.read_text())
        cfg["model"].update(SMALL_MODEL)
        if "optimizer" in cfg:
            cfg["optimizer"]["warmup_steps"] = 10
        path.write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        path = dst / "bench" / "traffic" / f"{w['traffic']}.json"
        t = json.loads(path.read_text())
        if t["driver"] == "train":
            t.update(seq_len=64, mean_doc_len=32)
        else:
            t.update(prompt_len=8, gen_len=8)
        path.write_text(json.dumps(t))
        (dst / "bench" / "workloads" / f"{w['name']}.json").write_text(
            json.dumps({"limits": small_limits(src, w["name"])}))
    return dst


@pytest.fixture
def small_tree(tmp_path) -> Path:
    return make_small_tree(tmp_path / "bench_copy")


def run_child(code: str, root: Path, devices: int = 1, timeout: int = 900) -> dict:
    """Run ``code`` in a child on ``devices`` virtual CPU devices with the
    Pallas kernels interpreted; it prints one JSON object last."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", REPRO_PALLAS="interpret",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    env["PYTHONPATH"] = os.pathsep.join([str(root / "src"), str(root)])
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], cwd=root,
                          capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(f"child failed (rc={proc.returncode})\n{proc.stdout[-3000:]}\n"
                             f"{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])
