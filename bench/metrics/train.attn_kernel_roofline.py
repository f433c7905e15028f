"""Roofline share of the Pallas flash-attention forward kernel in
training: per call, the larger of its FLOPs over the peak and its bytes
over the bandwidth (``bench.flops.flash_attention_call``, from the shapes
each device's call sees), times the calls the trace shows, over the
kernels' summed device time.

The trace gives a Pallas kernel no name of its own: the attention kernel
is the ``tpu_custom_call`` with three operands (q, k, v); RMSNorm's has two."""

from bench.flops import flash_attention_call
from bench.trace import op_ns, pallas_operands


def is_kernel(op):
    return pallas_operands(op) == 3


def read(r):
    m, w, chips = r.cell.model, r.window, r.chips
    b = w["batch"] // chips if w["batch"] % chips == 0 else w["batch"]
    h = m["num_heads"] if w["batch"] % chips == 0 else m["num_heads"] // chips
    hkv = m["num_kv_heads"] if w["batch"] % chips == 0 else m["num_kv_heads"] // chips
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    flops, nbytes = flash_attention_call(b, w["seq"], w["seq"], h, hkv, hd,
                                         causal=True, window=m.get("sliding_window"))
    least = max(flops / r.peaks["bf16_flops_per_s"], nbytes / r.peaks["hbm_bytes_per_s"])
    total_ns, calls = 0.0, 0
    for d in sorted(r.trace.devices):
        ns, n = op_ns(r.trace, d, is_kernel)
        total_ns, calls = total_ns + ns, calls + n
    if not calls or total_ns <= 0:
        return None
    return 100.0 * least * calls / (total_ns * 1e-9)
