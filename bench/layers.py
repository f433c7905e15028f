"""Split of the step program's device time by the model's layers.

The program puts each layer's work under a ``jax.named_scope`` (``attn``,
``moe``, ``head``); the scope path is every HLO instruction's ``op_name``.
The profiler trace names each device op by its HLO instruction
(``fusion.172``), so an op's layer is the layer on the ``op_name`` of the
instruction of that name in the step program.

``bench/trace.py`` keeps no HLO, so this module compiles the cell's step
program again, as its driver builds it, and reads the ``op_name`` of every
instruction from the compiled program. Within a run that is the executable
the window ran (JAX's persistent cache, which ``bench/run.py`` turns on,
holds it): when an op of the traced program has a name the rebuilt program
lacks, the two differ and no split is given.

A layer's time is the union of the intervals of the step program's ops on
whose scope path the layer is a component (``jvp(moe)`` and
``transpose(jvp(moe))`` count as ``moe``), in milliseconds per run of the
program. ``other`` is the program's device time per run less the union of
every scoped op: pre-norms, embedding, residual adds, the layer scan's
weight slicing and copies. The four add up to the program's device time
per run. A scope below a layer (``moe/a2a`` in ``SUBSCOPES``) is read the
same way, as the union of the ops on whose scope path its components lie
in that order; it is part of its layer's time, not beside it.

The serve driver's decode step and the train driver's training step are
rebuilt; a cell of any other driver gets no split.
"""

from __future__ import annotations

import re

from bench.trace import Trace, is_container, union_ns

__all__ = ["LAYERS", "SUBSCOPES", "layer_of", "in_scope", "scope_map", "step_scopes", "split_ms",
           "read_ms"]

LAYERS = ("attn", "moe", "head")
SUBSCOPES = ("moe/a2a",)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")


def _parts(op_name: str) -> list[str]:
    """The scope path's components, autodiff wrappers (``jvp(moe)``,
    ``transpose(jvp(moe))``) taken off."""
    parts = []
    for part in re.split(r"[/;]", op_name):
        while (m := _WRAPPED.fullmatch(part)):
            part = m.group(1)
        parts.append(part)
    return parts


def layer_of(op_name: str) -> str | None:
    """The first of ``LAYERS`` on the scope path ``op_name``, else ``None``."""
    return next((part for part in _parts(op_name) if part in LAYERS), None)


def in_scope(op_name: str, path: str) -> bool:
    """Whether the components of ``path`` (``moe/a2a``) lie on the scope
    path ``op_name`` in that order, with any scopes between them
    (``moe/shard_map/a2a``)."""
    parts = iter(_parts(op_name))
    return all(want in parts for want in path.split("/"))


def scope_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` ("" where it has none) of every
    instruction of a compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1)] = name.group(1) if name else ""
    return out


def _serve_step_text(cell, devices) -> str:
    """The serve driver's decode step (``bench/drivers/serve.py``), compiled
    from shapes alone."""
    import jax
    import jax.numpy as jnp

    from repro.compat import make_mesh
    from repro.configs.base import ModelConfig
    from repro.launch.steps import make_decode_step
    from repro.models import init_cache, init_params
    from repro.parallel.mesh_view import build_mesh_context
    from repro.parallel.sharding import param_shardings

    t = cell.traffic
    cfg = ModelConfig(**cell.model)
    ctx = build_mesh_context(make_mesh((1, len(devices)), ("data", "model"), devices=devices), cfg)
    with jax.set_mesh(ctx.mesh):
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                              params, param_shardings(cfg, ctx, params))
        cache = jax.eval_shape(lambda: init_cache(cfg, t["batch"], t["prompt_len"] + t["gen_len"]))
        tokens = {"tokens": jax.ShapeDtypeStruct((t["batch"], 1), jnp.int32)}
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        step = jax.jit(make_decode_step(cfg, ctx, return_counts=True), donate_argnums=(1,))
        return step.lower(params, cache, tokens, pos).compile().as_text()


def _train_step_text(cell, devices) -> str:
    """The train driver's training step (``bench/drivers/train.py``), with
    its shardings and donation, compiled from shapes alone."""
    from bench.aot import lower_train_step

    return lower_train_step(cell, devices)[0].compile().as_text()


_STEP_TEXT = {"serve": _serve_step_text, "train": _train_step_text}
_built: dict[tuple[str, int], dict[str, str]] = {}  # one compile per process


def step_scopes(reading) -> dict[str, str] | None:
    """``scope_map`` of the reading's step program; ``None`` for a cell of
    a driver whose step is not rebuilt here."""
    import jax

    cell = reading.cell
    text = _STEP_TEXT.get(cell.traffic["driver"])
    if text is None:
        return None
    key = (cell.name, reading.chips)
    if key not in _built:
        _built[key] = scope_map(text(cell, jax.devices()[:reading.chips]))
    return _built[key]


def split_ms(trace: Trace, module: str, scopes: dict[str, str]) -> dict[str, float] | None:
    """Milliseconds per run of program ``module`` in each of ``LAYERS``,
    ``other`` and each of ``SUBSCOPES``, mean over the traced devices.
    ``None`` where no op of the program carries a layer's scope, or where
    an op's name is not in ``scopes`` (the trace ran another program)."""
    lo, hi = trace.window
    layer = {name: layer_of(op) for name, op in scopes.items()}
    sub = {path: {name for name, op in scopes.items() if in_scope(op, path)}
           for path in SUBSCOPES}
    per_dev = []
    for dev in sorted(trace.devices):
        runs = [(max(o.start, lo), min(o.end, hi)) for o in trace.modules.get(dev, ())
                if o.name == module and o.end > lo and o.start < hi]
        if not runs:
            continue
        spans: dict[str | None, list] = {}
        ops, i = trace.devices[dev], 0
        for s, e in runs:  # both sorted by start
            while i < len(ops) and ops[i].end <= s:
                i += 1
            j = i
            while j < len(ops) and ops[j].start < e:
                o = ops[j]
                j += 1
                if is_container(o) or o.end <= s:
                    continue
                if o.name not in layer:
                    return None
                iv = (max(o.start, s), min(o.end, e))
                spans.setdefault(layer[o.name], []).append(iv)
                for path, names in sub.items():
                    if o.name in names:
                        spans.setdefault(path, []).append(iv)
        if not any(name in spans for name in LAYERS):
            return None
        total = sum(e - s for s, e in runs)
        scoped = [iv for name in LAYERS for iv in spans.get(name, ())]
        ns = {name: union_ns(spans.get(name, ()), (lo, hi)) for name in (*LAYERS, *SUBSCOPES)}
        ns["other"] = total - union_ns(scoped, (lo, hi))
        per_dev.append({k: v / len(runs) * 1e-6 for k, v in ns.items()})
    if not per_dev:
        return None
    return {k: sum(d[k] for d in per_dev) / len(per_dev) for k in per_dev[0]}


_last: list = []  # [trace, module, scopes, split] of the newest split


def read_ms(reading, layer: str) -> float | None:
    """A per-layer metric's reading: ``split_ms`` of the cell's step program
    in ``layer``, one of ``LAYERS``, ``other`` or ``SUBSCOPES``."""
    module = reading.window.get("step_module")
    if not module or not any(o.name == module for mods in reading.trace.modules.values()
                             for o in mods):
        return None
    scopes = step_scopes(reading)
    if not scopes:
        return None
    # Every layer's reader of one run shares one split of its trace.
    if not (_last and _last[0] is reading.trace and _last[1] == module and _last[2] is scopes):
        _last[:] = [reading.trace, module, scopes, split_ms(reading.trace, module, scopes)]
    split = _last[3]
    return None if split is None else split[layer]

