"""Model FLOP utilisation of the decode step: the model FLOPs of each
decode step (``bench.flops.decode_step_flops``: the routed experts of
each token, attention over the cache up to its position), over the device
time of the compiled decode step (its program's runs in the profiler
trace) and the chip's peak."""

from bench.trace import mean_module


def read(r):
    w = r.window
    ns, runs = mean_module(r.trace, w["step_module"])
    if not w.get("steps") or not runs or ns <= 0:
        return None
    per_step = sum(w["step_flops"]) / len(w["step_flops"])
    return 100.0 * runs * per_step / (ns * 1e-9 * r.chips * r.peaks["bf16_flops_per_s"])
