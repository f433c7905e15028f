"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps:

* for each device plane (``/device:TPU:<n>``), the events of its ``XLA Ops``
  line: one interval per operation run on that device. A TPU trace names
  each event by its whole HLO instruction (``%fusion.160 = bf16[...]
  fusion(...)``); ``Op.name`` keeps the instruction's name
  (``fusion.160``) and ``Op.long`` the whole text;
* for each device plane, the events of its ``XLA Modules`` line: one
  interval per compiled program run on that device, named by the program
  (``jit_decode_step(12)``; ``Op.name`` drops the bracketed id);
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
  starting ``bench.``), among them ``bench.window`` around the traced window.

All times are nanoseconds on the trace's one clock. Everything after
``load`` works on that small ``Trace`` and is checked in ``bench/tests`` on
a recorded one.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Callable, Iterable

__all__ = [
    "Op",
    "op_name",
    "pallas_operands",
    "is_container",
    "Trace",
    "load",
    "union_ns",
    "busy_ns",
    "collective_intervals",
    "exposed_ns",
    "op_ns",
    "module_ns",
    "mean_module",
    "top_ops",
    "idle_gaps",
]

Interval = tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float
    long: str = ""


@dataclasses.dataclass
class Trace:
    window: Interval
    devices: dict[int, list[Op]]
    spans: list[tuple[str, float, float]]
    modules: dict[int, list[Op]] = dataclasses.field(default_factory=dict)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def to_json(self) -> dict:
        return {
            "window": list(self.window),
            "devices": {str(d): [[o.name, o.start, o.end, o.long] for o in ops]
                        for d, ops in self.devices.items()},
            "spans": [list(s) for s in self.spans],
            "modules": {str(d): [[o.name, o.start, o.end] for o in mods]
                        for d, mods in self.modules.items()},
        }

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        return cls(
            window=tuple(data["window"]),
            devices={int(d): [Op(*o) for o in ops] for d, ops in data["devices"].items()},
            spans=[tuple(s) for s in data["spans"]],
            modules={int(d): [Op(*o) for o in mods]
                     for d, mods in data.get("modules", {}).items()},
        )

    @classmethod
    def read(cls, path) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    def write(self, path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
_MODULE_ID = re.compile(r"\(\d+\)$")


def op_name(event_name: str) -> str:
    """The HLO instruction's name in an event name of the trace."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def load(profile_dir: str) -> Trace:
    """The ``Trace`` of the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: dict[int, list[Op]] = {}
    modules: dict[int, list[Op]] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append(Op(op_name(ev.name), ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append(Op(_MODULE_ID.sub("", ev.name), ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
            devices[int(m.group(1))] = sorted(ops, key=lambda o: o.start)
            modules[int(m.group(1))] = sorted(mods, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    return Trace(window=windows[-1], devices=devices, spans=sorted(spans, key=lambda s: s[1]),
                 modules=modules)


def _clip(intervals: Iterable[Interval], window: Interval) -> list[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _merge(intervals: Iterable[Interval]) -> list[Interval]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def union_ns(intervals: Iterable[Interval], window: Interval) -> float:
    return sum(e - s for s, e in _merge(_clip(intervals, window)))


_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def is_container(op: Op) -> bool:
    """A control-flow op (a layer scan's ``while``): the trace shows it
    around the ops of its body, so it is not itself work."""
    return bool(_CONTAINER.match(op.name))


def _work(trace: Trace, device: int) -> list[Interval]:
    return [(o.start, o.end) for o in trace.devices[device] if not is_container(o)]


def busy_ns(trace: Trace, device: int) -> float:
    """Time within the window in which some operation ran on ``device``."""
    return union_ns(_work(trace, device), trace.window)


_COLLECTIVE = re.compile(
    r"^(all-to-all|collective-permute|all-reduce|all-gather|reduce-scatter)"
    r"(-start|-done)?(\.\d+)?"
)


def is_collective(op: Op) -> bool:
    return bool(_COLLECTIVE.match(op.name))


def collective_intervals(ops: list[Op], kinds: tuple[str, ...] | None = None) -> list[Interval]:
    """Intervals in which a collective is in flight on one device: a
    synchronous one while its op runs, an asynchronous one from its
    ``-start`` to the end of the matching ``-done``."""
    out, open_starts = [], {}
    for o in ops:
        m = _COLLECTIVE.match(o.name)
        if not m or (kinds is not None and m.group(1) not in kinds):
            continue
        kind, phase, suffix = m.group(1), m.group(2), m.group(3) or ""
        if phase == "-start":
            open_starts[(kind, suffix)] = o.start
        elif phase == "-done":
            start = open_starts.pop((kind, suffix), o.start)
            out.append((start, o.end))
        else:
            out.append((o.start, o.end))
    return out


def exposed_ns(trace: Trace, device: int) -> float:
    """Time within the window in which a collective is in flight on
    ``device`` and no other operation runs there."""
    ops = trace.devices[device]
    coll = _merge(_clip(collective_intervals(ops), trace.window))
    other = _merge(_clip(((o.start, o.end) for o in ops
                          if not is_collective(o) and not is_container(o)), trace.window))
    covered, j = 0.0, 0
    for s, e in coll:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            covered += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return sum(e - s for s, e in coll) - covered


_CUSTOM_CALL = re.compile(r"custom-call\((.*?)\), custom_call_target=\"tpu_custom_call\"")


def pallas_operands(op: Op) -> int | None:
    """Operand count of a Pallas (Mosaic) kernel call, ``None`` for any
    other op. The trace names no kernel; the operands tell them apart."""
    m = _CUSTOM_CALL.search(op.long)
    return m.group(1).count("%") if m else None


def op_ns(trace: Trace, device: int, pred: Callable[[Op], bool]) -> tuple[float, int]:
    """Summed device time and count of the ops matching ``pred``."""
    total, n = 0.0, 0
    for s, e in _clip_ops(trace, device, pred):
        total += e - s
        n += 1
    return total, n


def module_ns(trace: Trace, device: int, name: str) -> tuple[float, int]:
    """Summed device time within the window, and count, of the runs of the
    compiled program ``name`` (``jit_<function>``) on ``device``."""
    lo, hi = trace.window
    total, n = 0.0, 0
    for o in trace.modules.get(device, ()):
        if o.name == name and o.end > lo and o.start < hi:
            total += min(o.end, hi) - max(o.start, lo)
            n += 1
    return total, n


def mean_module(trace: Trace, name: str) -> tuple[float, float]:
    """``module_ns`` of program ``name``, mean over the traced devices."""
    devs = sorted(trace.modules)
    if not devs:
        return 0.0, 0.0
    runs = [module_ns(trace, d, name) for d in devs]
    return sum(t for t, _ in runs) / len(devs), sum(n for _, n in runs) / len(devs)


def _clip_ops(trace, device, pred):
    lo, hi = trace.window
    for o in trace.devices[device]:
        if pred(o) and o.end > lo and o.start < hi:
            yield max(o.start, lo), min(o.end, hi)


def _op_class(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` op classes (HLO name without its numeric suffix) that took
    most device time in the window, in seconds per device; control-flow
    containers left out."""
    totals: dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        lo, hi = trace.window
        for o in trace.devices[dev]:
            if o.end > lo and o.start < hi and not is_container(o):
                totals[_op_class(o.name)] += (min(o.end, hi) - max(o.start, lo)) * 1e-9
    per_dev = max(len(trace.devices), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / per_dev] for name, secs in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest stretches in which a device ran nothing, in
    seconds, each named by the host span that overlapped it most
    (``idle`` where none did)."""
    gaps = []
    for dev in trace.devices:
        busy = _merge(_clip(_work(trace, dev), trace.window))
        edges = [trace.window[0]] + [x for iv in busy for x in iv] + [trace.window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    out = []
    for length, s, e in gaps[:n]:
        best, best_overlap = "idle", 0.0
        for name, hs, he in trace.spans:
            if name == "bench.window":
                continue
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        out.append([best, length * 1e-9])
    return out
