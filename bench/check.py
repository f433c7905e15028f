"""The comparisons that decide ``correct``, shared by the drivers, the
calibration script and the tests. Each returns a list of checked numbers
``{"name", "value", "limit"}``; a run is correct only if every value is
at most its limit.
"""

from __future__ import annotations

import math

__all__ = ["train_numbers", "served_gaps", "serve_numbers", "checked", "median"]

#: A leaf whose reference gradient norm is under this share of the median
#: leaf's moves under Adam by round-off alone; its change is not compared.
STILL_LEAF = 1e-3


def median(values) -> float:
    s = sorted(values)
    n = len(s)
    if not n:
        return 0.0
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _worst_leaf_gap(got: dict, want: dict, leaves) -> float:
    """Largest ``| |got| - |want| |`` over ``leaves``, each against the
    larger of that leaf's reference norm and the median leaf's."""
    floor = median([want[k] for k in want])
    worst = 0.0
    for k in leaves:
        denom = max(want[k], floor)
        gap = abs(got[k] - want[k]) / denom if denom > 0 else abs(got[k] - want[k])
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
    return worst


def train_numbers(prog: dict, ref: dict) -> dict:
    """Gaps of a training run's first steps from the reference's.

    ``prog`` and ``ref`` hold ``losses`` (one per step), ``grad_norms``
    (per leaf, of the first clipped gradient) and ``delta_norms`` (per
    leaf, of the parameters' change over the steps)."""
    losses = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
              for a, b in zip(prog["losses"], ref["losses"])]
    g_floor = median(ref["grad_norms"].values())
    moving = [k for k, g in ref["grad_norms"].items() if g >= STILL_LEAF * g_floor]
    return {
        "loss_rel": max(losses),
        "grad_norm_gap": _worst_leaf_gap(prog["grad_norms"], ref["grad_norms"], ref["grad_norms"]),
        "delta_norm_gap": _worst_leaf_gap(prog["delta_norms"], ref["delta_norms"], moving),
    }


def served_gaps(ref_logits, tokens):
    """Per served token, the gap by which its reference logit lies below
    the reference's best at its position. ``ref_logits: (N, V)``,
    ``tokens: (N,)``; a non-finite logit reads as an infinite gap."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float64)
    got = np.take_along_axis(ref_logits, np.asarray(tokens)[:, None], 1)[:, 0]
    gap = ref_logits.max(1) - got
    return np.where(np.isfinite(gap), gap, np.inf)


def serve_numbers(ref_logits, tokens) -> dict:
    """The serving check: the mean gap over the served tokens.

    The widest gap is not compared: under top-2 routing a bfloat16 run
    and the float32 reference choose a different expert wherever the
    router's second and third choices lie within rounding of each other
    (a few dozen of 3072 routing decisions per batch on the chip), and
    such a token's logits move by up to about 3; the widest gap of sound
    runs then reaches that of the float8 control. The mean separates them."""
    return {"mean_logit_gap": float(served_gaps(ref_logits, tokens).mean())}


def checked(numbers: dict, limits: dict) -> list[dict]:
    return [{"name": k, "value": v, "limit": limits[k]} for k, v in numbers.items()]
