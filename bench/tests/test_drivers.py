"""CPU rehearsals of both drivers on the benchmark cut to CPU widths
(Pallas kernels interpreted; four virtual devices for training), and
cells, configurations, mixes, drivers and metrics added as new files
only."""

import hashlib
import json
import math
import shutil
from pathlib import Path

from conftest import ROOT, cells, make_small_tree, run_child, small_limits
from test_faults import control_cases, fault_cases

RUN = """
import json, sys
from pathlib import Path
from bench import run
result, _ = run.run(Path('.'), {cell!r}, {seed}, {seconds}, False, require_chip=False)
print(json.dumps(result))
"""


def test_serve_rehearsal(small_tree):
    out = run_child(RUN.format(cell="serve-chat-b4", seed=2**31 + 11, seconds=2.0), small_tree)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["device"]["count"] == 1
    assert list(out)[-1] == "checks" and out["checks"]["mean_logit_gap"]["limit"] == 0.035


def test_train_rehearsal_on_four_devices(small_tree):
    out = run_child(RUN.format(cell="train-ep4-rails", seed=2**31 + 12, seconds=2.0),
                    small_tree, devices=4)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["device"]["count"] == 4
    assert set(out["checks"]) == {"loss_rel", "grad_norm_gap", "delta_norm_gap"}


def test_train_cell_reports_its_metrics():
    """The training cell, as BENCHMARK.json has it, reports the training
    rate and set-up end to end, and the training step's readers."""
    from bench import run

    cell = run.load_cell(ROOT, "train-ep4-rails")
    assert cell.chips == 4 and cell.traffic["driver"] == "train"
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "idle_share.train", "train.mfu", "train.a2a_ms", "train.exposed_collective_share",
        "train.attn_kernel_roofline", "train.attn_ms", "train.moe_ms", "train.head_ms",
        "train.other_ms", "train.moe_a2a_ms"}
    assert all(m["moves"] == "train_tokens_per_s" for m in cell.per_layer)
    assert all((ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file() for m in cell.per_layer)


def test_train_cell_limits_are_set():
    """The training check's limits, set from chip readings, are finite."""
    limits = json.loads((ROOT / "bench" / "workloads" / "train-ep4-rails.json").read_text())
    limits = limits["limits"]
    assert set(limits) == {"loss_rel", "grad_norm_gap", "delta_norm_gap"}
    assert all(isinstance(v, float) and math.isfinite(v) and v > 0 for v in limits.values())


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_mix_driver_and_metric_are_new_files_only(small_tree):
    """A later PR adds a configuration, a traffic mix, a driver, a limits
    file and a per-layer metric as new files, plus BENCHMARK.json entries;
    every file the benchmark already had stays byte for byte."""
    bench = small_tree / "bench"
    before = {k: v for k, v in _digest(small_tree).items() if k != "BENCHMARK.json"}
    cfg = json.loads((bench / "configs" / "mixtral-8x7b-1chip-serve.json").read_text())
    cfg["model"]["num_layers"] = 1
    (bench / "configs" / "tiny-serve.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "chat-b4-p128-g128.json").read_text())
    mix.update(driver="serve_echo", batch=2)
    (bench / "traffic" / "chat-b2-short.json").write_text(json.dumps(mix))
    (bench / "drivers" / "serve_echo.py").write_text(
        (bench / "drivers" / "serve.py").read_text())
    (bench / "workloads" / "serve-tiny-b2.json").write_text(
        json.dumps({"limits": {"mean_logit_gap": 0.035}}))
    (bench / "metrics" / "serve.requests_per_window.py").write_text(
        '"""Requests finished in the traced window."""\n\n\n'
        "def read(r):\n    return float(r.window['steps'])\n")
    spec = json.loads((small_tree / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-serve", "source": "https://example.org/tiny",
                            "file": "bench/configs/tiny-serve.json", "reduced": [], "why": "t"})
    spec["workloads"].append({"name": "serve-tiny-b2", "config": "tiny-serve",
                              "traffic": "chat-b2-short", "chips": 1, "why": "t"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "serve-chat-b4" in m["workloads"]:
            m["workloads"].append("serve-tiny-b2")
    spec["per_layer"].append({"name": "serve.requests_per_window", "unit": "steps",
                              "better": "higher", "source": "program_counter", "layer": "t",
                              "moves": "serve_tokens_per_s", "workloads": ["serve-tiny-b2"]})
    (small_tree / "BENCHMARK.json").write_text(json.dumps(spec))

    from bench import run

    cell = run.load_cell(small_tree, "serve-tiny-b2")
    assert cell.model["num_layers"] == 1 and cell.traffic["driver"] == "serve_echo"
    assert [m["name"] for m in cell.per_layer] == ["serve.requests_per_window"]
    reader = run.load_module(bench / "metrics" / "serve.requests_per_window.py")
    assert reader.read(run.Reading(cell, None, {"steps": 3}, {}, 1)) == 3.0
    out = run_child(RUN.format(cell="serve-tiny-b2", seed=5, seconds=1.0), small_tree)
    assert out["correct"] and out["attempted"] % 2 == 0
    after = _digest(small_tree)
    assert all(after[k] == v for k, v in before.items())


def test_refuses_a_cell_it_does_not_know(small_tree):
    import pytest

    from bench import run

    with pytest.raises(run.Refused):
        run.load_cell(small_tree, "no-such-cell")
    shutil.rmtree(small_tree / "bench" / "workloads")
    with pytest.raises(run.Refused):
        run.load_cell(small_tree, "serve-chat-b4")


def _cell_files(tree: Path, config: str, base_config: str, cell: str, base_cell: str,
                traffic: str, base_traffic: str, **model) -> None:
    """A configuration, a traffic mix and a limits file for ``cell``, each
    a new file made from an existing one of its kind."""
    bench = tree / "bench"
    cfg = json.loads((bench / "configs" / f"{base_config}.json").read_text())
    cfg["model"].update(model)
    cfg.update(num_hidden_layers=model["num_layers"], num_local_experts=model["num_experts"])
    (bench / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / f"{base_traffic}.json").read_text())
    mix["batch"] //= 2
    (bench / "traffic" / f"{traffic}.json").write_text(json.dumps(mix))
    limits = json.loads((bench / "workloads" / f"{base_cell}.json").read_text())
    (bench / "workloads" / f"{cell}.json").write_text(
        json.dumps({k: limits[k] for k in ("limits", "small_limits")}))


def test_new_configuration_and_cells_are_new_files_only(tmp_path):
    """A later PR adds a configuration of a second model with a four-chip
    training cell and a one-chip serving cell: new configuration, traffic
    and limits files, and entries appended to ``BENCHMARK.json``, all
    before the small copy is made. Both cells rehearse ``correct``, report
    their end-to-end metric, and get their faults and control; every file
    the tree had stays byte for byte, and ``BENCHMARK.json`` only grows."""
    tree = tmp_path / "tree"
    shutil.copytree(ROOT / "bench", tree / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tree / "BENCHMARK.json")
    (tree / "src").symlink_to(ROOT / "src")
    before = _digest(tree)
    old_spec = json.loads((tree / "BENCHMARK.json").read_text())

    # 16 experts (4 a chip at ep = 4) in 1 layer, where the cells above run 8 in 2 or 4.
    sizes = dict(num_layers=1, num_experts=16)
    _cell_files(tree, "mixtral-16e-ep4-train", "mixtral-8x7b-ep4-train", "train-16e-ep4",
                "train-ep4-rails", "train-4x1024-zipf", "train-8x1024-zipf", **sizes)
    _cell_files(tree, "mixtral-16e-1chip-serve", "mixtral-8x7b-1chip-serve", "serve-16e-b2",
                "serve-chat-b4", "chat-b2-p128-g128", "chat-b4-p128-g128", **sizes)
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    new = {"train-16e-ep4": ("mixtral-16e-ep4-train", "train-4x1024-zipf", 4,
                             "train_tokens_per_s"),
           "serve-16e-b2": ("mixtral-16e-1chip-serve", "chat-b2-p128-g128", 1,
                            "serve_tokens_per_s")}
    for name, (config, traffic, chips, metric) in new.items():
        spec["configs"].append({"name": config, "source": "https://example.org/16e",
                                "file": f"bench/configs/{config}.json",
                                "reduced": ["num_hidden_layers", "num_local_experts"],
                                "why": "t"})
        spec["workloads"].append({"name": name, "config": config, "traffic": traffic,
                                  "chips": chips, "why": "t"})
        next(m for m in spec["end_to_end"] if m["name"] == metric)["workloads"].append(name)
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))

    small = make_small_tree(tmp_path / "small", tree)

    assert {(c["name"], c["chips"], c["driver"]) for c in cells(small)} >= {
        ("train-16e-ep4", 4, "train"), ("serve-16e-b2", 1, "serve")}
    assert {f for c, _, f in fault_cases(tree, "train") if c == "train-16e-ep4"} == {
        "state_unchanged", "half_batch", "no_exchange"}
    assert {f for c, _, f in fault_cases(tree, "serve") if c == "serve-16e-b2"} == {
        "token_altered", "cache_unchanged"}
    assert {("train-16e-ep4", 4), ("serve-16e-b2", 1)} <= set(control_cases(tree))
    for name, (_, _, chips, metric) in new.items():
        out = run_child(RUN.format(cell=name, seed=2**31 + 21, seconds=1.0), small,
                        devices=chips)
        assert out["correct"], out
        assert out["attempted"] > 0 and out["failed"] == 0
        assert set(out["metrics"]) == {metric, "setup_s"}
        assert out["device"]["count"] == chips
        assert set(out["checks"]) == set(small_limits(tree, name))

    after = _digest(tree)
    assert all(after[k] == v for k, v in before.items() if k != "BENCHMARK.json")
    grown = json.loads((tree / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "per_layer"):
        assert grown[key][:len(old_spec[key])] == old_spec[key]
    for old, now in zip(old_spec["end_to_end"], grown["end_to_end"]):
        assert {**now, "workloads": None} == {**old, "workloads": None}
        assert now.get("workloads", [])[:len(old.get("workloads", []))] == old.get("workloads", [])
