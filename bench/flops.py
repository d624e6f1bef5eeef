"""Operations and bytes a decode step needs, from the configuration's shapes.

These are fixed by the shapes and the traffic, not by the program, so no
change to the program can push a share computed from them past 100%:

* FLOPs per decoded token: 2 per weight of every matrix product (the
  layers and the unembedding; the token embedding is a lookup), plus
  attention over the row's live context, 4 * layers * heads * head_dim
  per cached position (q.k and p.v).
* Bytes per decode step: every weight of those products once, at 2 bytes
  a parameter (bfloat16, the compute type), plus the live keys and values
  of every active row at the cache's bytes, plus the new ones written.
  The program stores float32 weights today, so it must move at least
  twice the weight bytes counted here.
"""

from __future__ import annotations

from typing import Dict, Iterable

WEIGHT_BYTES = 2


def param_counts(s: Dict[str, int]) -> Dict[str, int]:
    """Parameters by part, from ``weights.sizes``: the layer stack (with
    its norm weights), each vocabulary matrix, and the total."""
    d, H, KV, hd, F, L, V = (s["d"], s["H"], s["KV"], s["hd"], s["F"],
                             s["L"], s["V"])
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * F
    layer = attn + mlp + 2 * d
    layers = L * layer
    return {"layer": layer, "layers": layers, "embed": V * d,
            "unembed": d * V, "final_norm": d,
            "total": layers + 2 * V * d + d,
            "matmul": L * (attn + mlp) + d * V}


def decode_step_flops(s: Dict[str, int], live_lens: Iterable[int]) -> float:
    """Model FLOPs of one decode step whose active rows attend over
    ``live_lens`` cached positions each (the new token included)."""
    lens = list(live_lens)
    dense = 2.0 * param_counts(s)["matmul"] * len(lens)
    attn = 4.0 * s["L"] * s["H"] * s["hd"] * sum(lens)
    return dense + attn


def decode_step_bytes(s: Dict[str, int], live_lens: Iterable[int],
                      kv_bytes: int) -> float:
    """Least bytes one decode step must move: the weights once and the
    live cache of every active row (read, and the new entry written)."""
    lens = list(live_lens)
    per_pos = 2 * s["L"] * s["KV"] * s["hd"] * kv_bytes
    weights = WEIGHT_BYTES * (param_counts(s)["matmul"] + s["d"] * len(lens))
    return weights + per_pos * (sum(lens) + len(lens))
