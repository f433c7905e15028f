"""The split of the step program's device time by layer (``bench/layers.py``
and the ``serve.*_ms`` and ``train.*_ms`` readers): the scope join on a
small program compiled here, the split on a hand-made trace worked by
hand, the rebuilt decode and training steps against the ones the drivers
ran, and the training step's split over every instruction it holds."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import layers
from bench import run
from bench import trace as tr
from conftest import run_child

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
READERS = ("serve.attn_ms", "serve.moe_ms", "serve.head_ms", "serve.other_ms")
TRAIN_READERS = ("train.attn_ms", "train.moe_ms", "train.head_ms", "train.other_ms")


def test_layer_of():
    assert layers.layer_of("jit(decode_step)/while/body/closed_call/moe/experts/dot_general") == "moe"
    assert layers.layer_of("jit(train_step)/transpose(jvp(moe))/experts/dot_general") == "moe"
    assert layers.layer_of("jit(f)/jvp(head)/reshape;jvp(head)/reshape") == "head"
    assert layers.layer_of("jit(f)/attn_like/dot_general") is None
    assert layers.layer_of("jit(decode_step)/while/body/add") is None
    assert layers.layer_of("") is None


def test_in_scope():
    rails = "jit(train_step)/while/body/closed_call/moe/shard_map/a2a/ppermute"
    assert layers.in_scope(rails, "moe/a2a")
    back = "jit(train_step)/transpose(jvp())/checkpoint/moe/shard_map/a2a/add_any"
    assert layers.in_scope(back, "moe/a2a")
    assert not layers.in_scope("jit(train_step)/moe/shard_map/experts/dot_general", "moe/a2a")
    assert not layers.in_scope("jit(f)/a2a/moe/add", "moe/a2a")


def test_scope_map_of_a_compiled_program():
    """Every instruction of a small scoped program compiled here, named as
    the trace names its ops, with the scope path of its source."""
    import jax
    import jax.numpy as jnp

    def f(w, x):
        with jax.named_scope("moe"):
            with jax.named_scope("experts"):
                y = jnp.tanh(x @ w)
        with jax.named_scope("head"):
            z = y @ w.T
        return z.sum() + x.sum()

    text = jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((4, 8))).compile().as_text()
    scopes = layers.scope_map(text)
    dots = [line.split("=")[0].strip().lstrip("%").removeprefix("ROOT ").lstrip("%")
            for line in text.splitlines() if " dot(" in line]
    assert len(dots) == 2
    assert sorted(layers.layer_of(scopes[d]) for d in dots) == ["head", "moe"]
    assert all(not k.startswith("%") for k in scopes)
    assert any(layers.layer_of(v) is None for v in scopes.values())  # the sums


@pytest.fixture
def hand():
    """Two runs of the step program (0..40 and 50..90) around an eager
    program, with a third run cut by the window's end at 100."""
    ops = [("fusion.1", 0, 10), ("fusion.2", 10, 30), ("copy.3", 30, 32),
           ("conv.4", 32, 38),                       # 38..40: idle in the step
           ("eager.1", 41, 44),                      # another program's op
           ("while.9", 50, 90), ("fusion.1", 50, 55), ("fusion.2", 55, 80),
           ("fusion.5", 80, 85), ("conv.4", 85, 90),
           ("fusion.1", 95, 105)]
    return tr.Trace(
        window=(0, 100),
        devices={0: [tr.Op(*o) for o in ops]},
        spans=[("bench.window", 0, 100)],
        modules={0: [tr.Op("jit_step", 0, 40), tr.Op("jit_eager", 40, 45),
                     tr.Op("jit_step", 50, 90), tr.Op("jit_step", 95, 110)]},
    )


SCOPES = {
    "fusion.1": "jit(step)/while/body/attn/dot_general",
    "fusion.2": "jit(step)/while/body/moe/experts/dot_general",
    "fusion.5": "jit(step)/while/body/transpose(jvp(moe))/shard_map/a2a/add",
    "conv.4": "jit(step)/head/dot_general",
    "copy.3": "jit(step)/while/body/dynamic_slice",
    "while.9": "jit(step)/while",
}


def test_split_by_hand(hand):
    split = layers.split_ms(hand, "jit_step", SCOPES)
    # Three runs in the window: 40 + 40 + 5 ns of device time.
    # attn 10 + 5 + 5, moe 20 + 25 + 5, head 6 + 5, other 2 + 2 (idle
    # 38..40 of the first run is in no op).
    runs = 3
    assert split["attn"] == pytest.approx(20 / runs * 1e-6)
    assert split["moe"] == pytest.approx(50 / runs * 1e-6)
    assert split["head"] == pytest.approx(11 / runs * 1e-6)
    assert split["other"] == pytest.approx(4 / runs * 1e-6)
    assert split["moe/a2a"] == pytest.approx(5 / runs * 1e-6)  # inside moe's 50
    step_ns, n = tr.mean_module(hand, "jit_step")
    assert n == runs
    total = sum(split[k] for k in (*layers.LAYERS, "other"))
    assert total == pytest.approx(step_ns / n * 1e-6)


def test_split_without_scopes_or_with_another_program(hand):
    unscoped = {k: "jit(step)/while/body/add" for k in SCOPES}
    assert layers.split_ms(hand, "jit_step", unscoped) is None
    missing = {k: v for k, v in SCOPES.items() if k != "copy.3"}
    assert layers.split_ms(hand, "jit_step", missing) is None
    assert layers.split_ms(hand, "jit_other", SCOPES) is None


def _reading(trace, module="jit_step"):
    cell = SimpleNamespace(name="hand-cell", traffic={"driver": "serve"})
    return run.Reading(cell, trace, {"steps": 3, "step_module": module}, {}, 1)


def _read(metric, reading):
    return run.load_module(ROOT / "bench" / "metrics" / f"{metric}.py").read(reading)


def test_readers_sum_to_the_step(hand, monkeypatch):
    monkeypatch.setitem(layers._built, ("hand-cell", 1), SCOPES)
    reading = _reading(hand)
    values = {m: _read(m, reading) for m in READERS}
    assert values["serve.moe_ms"] == pytest.approx(50 / 3 * 1e-6)
    step_ns, n = tr.mean_module(hand, "jit_step")
    assert sum(values.values()) == pytest.approx(step_ns / n * 1e-6)
    monkeypatch.setitem(layers._built, ("hand-cell", 1),
                        {k: "jit(step)/add" for k in SCOPES})
    assert all(_read(m, reading) is None for m in READERS)


def test_readers_on_the_recorded_chip_trace():
    """The recorded window keeps no program runs: the readers give None
    and build nothing."""
    t = tr.Trace.read(DATA / "serve-chat-b4.json.gz")
    reading = _reading(t, "jit_decode_step")
    built = dict(layers._built)
    assert all(_read(m, reading) is None for m in READERS)
    assert layers._built == built


SAME_PROGRAM = """
import json
from pathlib import Path
import jax
from bench import layers, run

run._setup_jax()
cell = run.load_cell(Path('.'), 'serve-chat-b4')
devices = jax.devices()[:1]
job = run.load_module(Path('bench/drivers/serve.py')).Job(cell, 2**31 + 5, devices)
module = f'jit_{job.decode.__name__}'
ran = [layers.scope_map(e.hlo_modules()[0].to_string())
       for e in devices[0].client.live_executables()
       if e.hlo_modules()[0].name == module]
rebuilt = layers.scope_map(layers._serve_step_text(cell, devices))
print(json.dumps({
    'module': module,
    'ran': len(ran),
    'same': all(m == rebuilt for m in ran),
    'layers': sorted({layers.layer_of(v) for v in rebuilt.values()} - {None}),
}))
"""


def test_rebuilt_step_is_the_program_the_driver_ran(small_tree):
    """The decode step that ``bench/layers.py`` compiles from shapes has the
    instructions, and their scopes, of every decode step the serve driver
    compiled and ran."""
    out = run_child(SAME_PROGRAM, small_tree)
    assert out["module"] == "jit_decode_step_counts"  # the step with its counters
    assert out["ran"] >= 1 and out["same"], out
    assert out["layers"] == ["attn", "head", "moe"]


TRAIN_PROGRAM = """
import json
from pathlib import Path
import jax
from bench import layers, run

run._setup_jax()
cell = run.load_cell(Path('.'), 'train-ep4-rails')
devices = jax.devices()[:4]
job = run.load_module(Path('bench/drivers/train.py')).Job(cell, 2**31 + 5, devices)
ran = [layers.scope_map(e.hlo_modules()[0].to_string())
       for e in devices[0].client.live_executables()
       if e.hlo_modules()[0].name == 'jit_train_step']
rebuilt = layers.scope_map(layers._train_step_text(cell, devices))
print(json.dumps({'ran': len(ran), 'same': all(m == rebuilt for m in ran), 'scopes': rebuilt}))
"""


@pytest.fixture(scope="module")
def train_program(tmp_path_factory):
    """The small training step the train driver ran on four virtual
    devices, and the one ``bench/layers.py`` rebuilds from shapes."""
    from conftest import make_small_tree

    tree = make_small_tree(tmp_path_factory.mktemp("train") / "bench_copy")
    return run_child(TRAIN_PROGRAM, tree, devices=4)


def test_rebuilt_train_step_is_the_program_the_driver_ran(train_program):
    """The training step that ``bench/layers.py`` compiles from shapes, with
    the driver's shardings and donation, has the instructions, and their
    scopes, of the step the train driver compiled and ran."""
    assert train_program["ran"] >= 1 and train_program["same"]
    scopes = train_program["scopes"]
    assert {layers.layer_of(v) for v in scopes.values()} - {None} == {"attn", "moe", "head"}


def test_train_split_over_every_instruction(train_program):
    """A trace in which every instruction of the training step runs once,
    1 ns each, on four devices, in two runs of the program: the four
    readers sum to the step's device time; the backward pass and the remat
    recompute land in their layer; the rails exchange lies inside ``moe``."""
    scopes = train_program["scopes"]
    names = sorted(scopes)
    ops, modules = {}, {}
    for dev in range(4):
        ops[dev], modules[dev] = [], []
        for run_i in range(2):
            t0 = run_i * (len(names) + 10)
            modules[dev].append(tr.Op("jit_train_step", t0, t0 + len(names)))
            ops[dev] += [tr.Op(n, t0 + i, t0 + i + 1) for i, n in enumerate(names)]
    end = 2 * (len(names) + 10)
    trace = tr.Trace(window=(0, end), devices=ops, spans=[("bench.window", 0, end)],
                     modules=modules)
    cell = SimpleNamespace(name="small-train", traffic={"driver": "train"})
    reading = run.Reading(cell, trace, {"steps": 2, "step_module": "jit_train_step"}, {}, 4)
    layers._built[("small-train", 4)] = scopes
    try:
        values = {m: _read(m, reading) for m in (*TRAIN_READERS, "train.moe_a2a_ms")}
    finally:
        del layers._built[("small-train", 4)]
    assert sum(values[m] for m in TRAIN_READERS) == pytest.approx(len(names) * 1e-6)

    def count(pred):  # ns per run, as ms; the scans' containers are not work
        return sum(1 for n, v in scopes.items()
                   if pred(v) and not tr.is_container(tr.Op(n, 0, 0))) * 1e-6

    remat = lambda v: "rematted_computation" in v
    backward = lambda v: "transpose(" in v and not remat(v)
    for layer in layers.LAYERS:
        assert values[f"train.{layer}_ms"] == pytest.approx(
            count(lambda v: layers.layer_of(v) == layer))
    for layer in ("attn", "moe"):
        assert count(lambda v: backward(v) and layers.layer_of(v) == layer) > 0
        assert count(lambda v: remat(v) and layers.layer_of(v) == layer) > 0
    assert count(lambda v: backward(v) and layers.layer_of(v) == "head") > 0
    a2a = count(lambda v: layers.in_scope(v, "moe/a2a"))
    assert values["train.moe_a2a_ms"] == pytest.approx(a2a)
    assert 0 < values["train.moe_a2a_ms"] < values["train.moe_ms"]
    assert count(lambda v: backward(v) and layers.in_scope(v, "moe/a2a")) > 0
