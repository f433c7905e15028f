"""``correct`` comes out false for the control and for each fault a cell
can have, at a size the CPU holds, for every cell of ``BENCHMARK.json``.

The cases are drawn from ``BENCHMARK.json`` by each cell's driver, so a
cell added as new files is covered with no file here edited. Each fault
breaks the timed path underneath a whole run (the harness's look for a
chip skipped), on as many virtual devices as the cell asks for chips:

* every training cell: a training step that returns its state unchanged;
  half of each batch left out, the mean taken over the rest;
* a training cell on more than one chip: the MoE exchange between chips
  left out;
* every serving cell: a served token altered where the decode step
  produces it; a decode step that returns its cache unchanged.

The control, for every cell, puts the plain reference, computed in
float8, in the program's place."""

import pytest

from conftest import ROOT, cells, run_child, small_limits

#: The faults of each driver's cells; a training cell on more than one
#: chip can also lose the exchange between chips (``EXCHANGE``).
FAULTS = {"train": ("state_unchanged", "half_batch"),
          "serve": ("token_altered", "cache_unchanged")}
EXCHANGE = "no_exchange"


def fault_cases(root, driver: str) -> list:
    """``(cell, chips, fault)`` for every fault of every cell of ``root``
    whose traffic names ``driver``."""
    out = []
    for c in cells(root):
        if c["driver"] != driver:
            continue
        faults = FAULTS[driver] + ((EXCHANGE,) if driver == "train" and c["chips"] > 1 else ())
        out += [(c["name"], c["chips"], f) for f in faults]
    return out


def control_cases(root) -> list:
    """``(cell, chips)`` of every cell of ``root``."""
    return [(c["name"], c["chips"]) for c in cells(root)]


def _params(cases):
    return [pytest.param(*c, id="-".join(str(x) for x in c if not isinstance(x, int)))
            for c in cases]


def test_every_cell_has_its_faults():
    """Every cell's driver is one whose faults these tests plant."""
    assert all(c["driver"] in FAULTS for c in cells(ROOT)), cells(ROOT)


TRAIN_FAULT = """
import json
from pathlib import Path
import jax.numpy as jnp
import repro.launch.steps as steps
import repro.models.moe as moe
fault = {fault!r}
if fault == "state_unchanged":
    real = steps.adamw_update
    def unchanged(grads, state, params, cfg):
        _, _, stats = real(grads, state, params, cfg)
        return params, state, stats
    steps.adamw_update = unchanged
elif fault == "half_batch":
    real_loss = steps.loss_fn
    def half(params, cfg, batch, *a, **k):
        n = batch["tokens"].shape[0] // 2
        return real_loss(params, cfg, {{key: v[:n] for key, v in batch.items()}}, *a, **k)
    steps.loss_fn = half
elif fault == "no_exchange":
    moe._a2a = lambda payload, axis, cfg: payload
from bench import run
result, _ = run.run(Path('.'), {cell!r}, 7, 1.0, False, require_chip=False)
print(json.dumps(result))
"""


@pytest.mark.parametrize("cell,chips,fault", _params(fault_cases(ROOT, "train")))
def test_train_fault_is_not_correct(small_tree, cell, chips, fault):
    out = run_child(TRAIN_FAULT.format(cell=cell, fault=fault), small_tree, devices=chips)
    assert out["correct"] is False, out["checks"]
    assert set(out["checks"]) == set(small_limits(ROOT, cell))
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


SERVE_FAULT = """
import json
from pathlib import Path
import jax.numpy as jnp
import repro.launch.steps as steps
fault = {fault!r}
real = steps.make_decode_step
def broken(cfg, ctx, **kw):
    step = real(cfg, ctx, **kw)
    def decode(params, cache, batch, pos):
        logits, new_cache, *counts = step(params, cache, batch, pos)
        if fault == "token_altered":
            logits = logits.at[:, 3].set(jnp.where(pos == 9, 1e9, logits[:, 3]))
        else:
            new_cache = cache
        return (logits, new_cache, *counts)
    return decode
steps.make_decode_step = broken
from bench import run
result, _ = run.run(Path('.'), {cell!r}, 7, 1.0, False, require_chip=False)
print(json.dumps(result))
"""


@pytest.mark.parametrize("cell,chips,fault", _params(fault_cases(ROOT, "serve")))
def test_serve_fault_is_not_correct(small_tree, cell, chips, fault):
    out = run_child(SERVE_FAULT.format(cell=cell, fault=fault), small_tree, devices=chips)
    assert out["correct"] is False, out["checks"]
    assert set(out["checks"]) == set(small_limits(ROOT, cell))


CONTROL = """
import json
from pathlib import Path
import numpy as np
from bench import run
from bench.check import checked, serve_numbers, train_numbers
cell = run.load_cell(Path('.'), {cell!r})
devices = run._devices(cell, False)
run._setup_jax()
driver = run.load_module(Path('bench/drivers') / (cell.traffic['driver'] + '.py'))
rows = []
for seed in (3, 4, 5):
    job = driver.Job(cell, seed, devices)
    if cell.traffic['driver'] == 'train':
        job.free()
        numbers = train_numbers(job.reference('fp8'), job.reference('f32'))
    else:
        job._run_batch(job.prompt_len, job.gen_len)
        job.free()
        ref, _ = job.reference_logits('f32')
        low, _ = job.reference_logits('fp8')
        numbers = serve_numbers(ref, low.argmax(1))
    rows.append(checked(numbers, cell.limits))
print(json.dumps(rows))
"""


@pytest.mark.parametrize("cell,chips", _params(control_cases(ROOT)))
def test_float8_control_is_not_correct(small_tree, cell, chips):
    rows = run_child(CONTROL.format(cell=cell), small_tree, devices=chips)
    for checks in rows:
        assert any(c["value"] > c["limit"] for c in checks), checks
    assert set(rows[0][0]) == {"name", "value", "limit"}
    assert {c["name"] for c in rows[0]} == set(small_limits(ROOT, cell))
