"""Share of the chip's HBM bandwidth that the decode step reaches: the
bytes each decode step must read (``bench.flops.decode_step_bytes``: every
weight held but the experts none of its tokens was routed to, by the
step's own count of the expert weight sets it read, and the KV cache up to
its position), over the device time of the compiled decode step (its
program's runs in the profiler trace) and the chip's peak bandwidth."""

from bench.trace import mean_module


def read(r):
    w = r.window
    ns, runs = mean_module(r.trace, w["step_module"])
    if not w.get("steps") or not runs or ns <= 0:
        return None
    per_step = sum(w["step_bytes"]) / len(w["step_bytes"])
    return 100.0 * runs * per_step / (ns * 1e-9 * r.chips * r.peaks["hbm_bytes_per_s"])
