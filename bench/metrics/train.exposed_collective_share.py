"""Share of the traced window in which a collective is in flight on a
device and no other operation runs there, mean over the devices
(profiler trace)."""

from bench.trace import exposed_ns


def read(r):
    devs = sorted(r.trace.devices)
    if not devs or r.trace.window_ns <= 0:
        return None
    return 100.0 * sum(exposed_ns(r.trace, d) for d in devs) / len(devs) / r.trace.window_ns
