"""Model FLOP utilisation of the training step: the model FLOPs of a step
(``bench.flops.train_step_flops``: forward and backward, routed experts
only, no recomputation), over the device time of the compiled step (its
program's runs in the profiler trace, mean over the devices) and the peak
of every chip used."""

from bench.trace import mean_module


def read(r):
    w = r.window
    ns, runs = mean_module(r.trace, w["step_module"])
    if not w.get("steps") or not runs or ns <= 0:
        return None
    return 100.0 * runs * w["step_flops"] / (ns * 1e-9 * r.chips * r.peaks["bf16_flops_per_s"])
