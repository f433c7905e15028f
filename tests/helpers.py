"""Test helpers: subprocess execution with a fake multi-device CPU."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_multidevice(code: str, devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a subprocess with N fake CPU devices; returns stdout.

    Raises on nonzero exit (stderr attached). Device count is process-global
    in jax, hence the subprocess isolation — the main pytest process stays
    at 1 device per the dry-run contract.
    """
    env = dict(os.environ)
    # The N devices are virtual CPU devices: pin the child to the CPU.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n--- stdout ---\n"
            f"{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}"
        )
    return proc.stdout
