"""Helpers for the benchmark's own tests: a copy of the benchmark cut to a
size the CPU holds, and runs of it in a child process with virtual devices.

Run with ``python -m pytest bench/tests`` from the root of the checkout.
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

#: Limits of the small copy, set from CPU runs of it (program readings at
#: most 5.6e-4 / 1.0e-2 / 7.4e-3 on loss / gradient / change, 0.06 on the
#: logit gap; its float8 control read 3.5e-3 / 3.7e-2 / 9.3e-3 and 1.7).
SMALL_LIMITS = {
    "train-ep4-rails": {"loss_rel": 1.5e-3, "grad_norm_gap": 0.025, "delta_norm_gap": 0.025},
    "serve-chat-b4": {"mean_logit_gap": 0.035},
}

SMALL_MODEL = dict(d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=128,
                   moe_d_ff=128, vocab_size=512, xent_chunk=64)


def make_small_tree(dst: Path) -> Path:
    """The benchmark's files with every configuration cut to CPU widths,
    short requests and sequences, and the small copy's limits."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    (dst / "src").symlink_to(ROOT / "src")
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
            json.dumps({"limits": SMALL_LIMITS[w["name"]]}))
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
