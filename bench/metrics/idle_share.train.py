"""Share of the traced window in which no operation ran on a device,
mean over the cell's devices (profiler trace)."""

from bench.trace import busy_ns


def read(r):
    devs = sorted(r.trace.devices)
    if not devs or r.trace.window_ns <= 0:
        return None
    busy = sum(busy_ns(r.trace, d) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / r.trace.window_ns)
