"""Device time per training step of the expert exchange: the intervals in
which a ``collective-permute`` or ``all-to-all`` is in flight (an
asynchronous one from its ``-start`` to its ``-done``), per device per
step, mean over the devices (profiler trace)."""

from bench.trace import collective_intervals, union_ns

KINDS = ("collective-permute", "all-to-all")


def read(r):
    devs = sorted(r.trace.devices)
    steps = r.window.get("steps")
    if not devs or not steps:
        return None
    per_dev = [
        union_ns(collective_intervals(r.trace.devices[d], KINDS), r.trace.window) for d in devs
    ]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(devs) / steps * 1e-6
