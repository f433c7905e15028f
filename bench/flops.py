"""Operations and bytes that the algorithms need, from shapes alone.

These are the yardstick's counts, not the program's: a FLOP is a multiply
or an add (a multiply-accumulate counts two), and bytes are the least the
device must move between HBM and the cores. Model FLOPs count the forward
and backward passes once each (backward = 2 x forward); recomputation
under rematerialisation does not count, and a routed MoE counts only the
``experts_per_token`` experts each token is routed to, never the padding
of capacity buckets nor experts computed densely.

``m`` is a configuration's ``model`` section (the program's field names).
"""

from __future__ import annotations

__all__ = [
    "attention_pairs",
    "token_forward_flops",
    "train_step_flops",
    "decode_step_flops",
    "expert_bytes",
    "decode_step_bytes",
    "flash_attention_call",
]


def _head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def attention_pairs(t: int, s: int, causal: bool, window: int | None = None,
                    q_offset: int = 0) -> int:
    """Query-key pairs that a query block of ``t`` at ``q_offset`` sees in
    ``s`` keys under the mask."""
    pairs = 0
    for q in range(q_offset, q_offset + t):
        hi = min(q + 1, s) if causal else s
        lo = max(0, q - window + 1) if window else 0
        pairs += max(0, hi - lo)
    return pairs


def _proj_flops_per_token(m: dict) -> int:
    d, h, hkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], _head_dim(m)
    attn = 2 * d * (2 * h * hd + 2 * hkv * hd)  # q, o and k, v
    router = 2 * d * m["num_experts"]
    experts = m["experts_per_token"] * 3 * 2 * d * m["moe_d_ff"]
    return attn + router + experts


def token_forward_flops(m: dict) -> int:
    """Forward FLOPs of one token through the projections, the routed
    experts and the output head (attention's score terms excluded)."""
    return m["num_layers"] * _proj_flops_per_token(m) + 2 * m["d_model"] * m["vocab_size"]


def _score_flops(m: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` query-key pairs, all heads, all layers."""
    return m["num_layers"] * 2 * 2 * m["num_heads"] * _head_dim(m) * pairs


def train_step_flops(m: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step (forward + backward)."""
    window = m.get("sliding_window")
    pairs = batch * attention_pairs(seq, seq, True, window)
    forward = batch * seq * token_forward_flops(m) + _score_flops(m, pairs)
    return 3 * forward


def decode_step_flops(m: dict, batch: int, pos: int) -> int:
    """Model FLOPs of one decode step writing position ``pos``."""
    window = m.get("sliding_window")
    pairs = batch * attention_pairs(1, pos + 1, True, window, q_offset=pos)
    return batch * token_forward_flops(m) + _score_flops(m, pairs)


def _param_bytes_held(m: dict) -> int:
    """Bytes of every weight a one-chip decode holds (bfloat16; the router
    in float32)."""
    d, h, hkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], _head_dim(m)
    e, f = m["num_experts"], m["moe_d_ff"]
    per_layer = 2 * (2 * d * h * hd + 2 * d * hkv * hd + 3 * e * d * f + 2 * d) + 4 * d * e
    return m["num_layers"] * per_layer + 2 * (m["vocab_size"] * d + d)


def expert_bytes(m: dict) -> int:
    """Bytes of one expert's weights in one layer: its gate, up and down
    projections (bfloat16)."""
    return 2 * 3 * m["d_model"] * m["moe_d_ff"]


def decode_step_bytes(m: dict, batch: int, pos: int, experts_read: int) -> int:
    """Least bytes one decode step at ``pos`` must read: every weight held
    except the embedding table (``batch`` rows of it) and the experts no
    token was routed to, and the KV cache up to ``pos`` (bfloat16).
    ``experts_read`` is the count of (layer, expert) weight sets the step's
    tokens were routed to, as the program counts them."""
    d, hkv, hd = m["d_model"], m["num_kv_heads"], _head_dim(m)
    window = m.get("sliding_window")
    seen = min(pos + 1, window) if window else pos + 1
    cache = m["num_layers"] * batch * seen * 2 * hkv * hd * 2
    embed_rows = batch * d * 2
    unread = m["num_layers"] * m["num_experts"] - experts_read
    return _param_bytes_held(m) - unread * expert_bytes(m) + embed_rows + cache


def flash_attention_call(b: int, t: int, s: int, h: int, hkv: int, hd: int,
                         causal: bool = True, window: int | None = None,
                         itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of one flash-attention forward call on one device:
    QK^T and PV over the unmasked pairs; q, k, v read and o written once."""
    flops = 2 * 2 * b * h * hd * attention_pairs(t, s, causal, window)
    nbytes = itemsize * (2 * b * t * h * hd + 2 * b * s * hkv * hd)
    return flops, nbytes
