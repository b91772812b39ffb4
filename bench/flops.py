"""Operations and bytes of the work, from shapes alone, and the chip peaks.

Counts are of the work an operation needs, whatever implements it: the
expert FFN counts the rows routed and kept (never capacity padding), and
dispatch/combine count the bytes of the rows they move (never the one-hot
matmuls that implement them today).  So a later kernel change can neither
read over its roofline nor leave a count stale.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS_FILE.name}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def share_pct(least_s: float, took_s: float):
    """Least time over measured time, in %; None when nothing ran."""
    if took_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / took_s


# --- matrix parameters touched per token ---------------------------------

def layer_params(cfg: dict, k: int) -> dict:
    """Multiply-accumulate weights one token meets in one layer, with k
    experts: attention projections, router, the k experts' FFN."""
    d, f, e = cfg["d_model"], cfg["d_ff"], cfg["n_experts"]
    hd = d // cfg["n_heads"]
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    n_mats = 3 if cfg["ffn_type"] == "swiglu" else 2
    return {"attn": attn, "router": d * e, "experts": k * n_mats * d * f}


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward model FLOPs per token: 6 x the weights a token
    meets (top-k experts, LM head; the embedding is a lookup), plus causal
    attention's score and value products (6 x layers x seq x d, half of the
    full-square count).  Recomputation is not counted."""
    per_layer = sum(layer_params(cfg, cfg["top_k"]).values())
    n = cfg["n_layers"] * per_layer + cfg["d_model"] * cfg["vocab_size"]
    return 6.0 * n + 6.0 * cfg["n_layers"] * seq * cfg["d_model"]


def serve_prefill_flops(cfg: dict, s: int) -> float:
    """Forward FLOPs of one prompt of length ``s``: every position through
    every layer with the serving top-k, causal attention, and the LM head
    for the last position only."""
    per_layer = sum(layer_params(cfg, cfg["serve_top_k"]).values())
    return (2.0 * cfg["n_layers"] * per_layer * s
            + 2.0 * cfg["n_layers"] * s * s * cfg["d_model"]
            + 2.0 * cfg["d_model"] * cfg["vocab_size"])


def serve_decode_flops(cfg: dict, pos: int) -> float:
    """Forward FLOPs of one decoded token at absolute position ``pos``
    (attending over pos + 1 cached positions), LM head included."""
    per_layer = sum(layer_params(cfg, cfg["serve_top_k"]).values())
    return (2.0 * cfg["n_layers"] * per_layer
            + 4.0 * cfg["n_layers"] * (pos + 1) * cfg["d_model"]
            + 2.0 * cfg["d_model"] * cfg["vocab_size"])


# --- kernels ---------------------------------------------------------------

def expert_ffn_work(rows: float, experts: float, cfg: dict, *,
                    passes: int = 1, bytes_per_el: int = 2):
    """(FLOPs, bytes) of the grouped expert FFN over ``rows`` kept
    (token, expert) rows touching ``experts`` distinct experts.

    ``passes`` is 1 for a forward and 3 for forward and backward (the
    backward is a data gradient and a weight gradient per matrix).  Bytes
    are each touched expert's weights read once per pass plus the rows in
    and out; for a backward the weight gradients are written as well."""
    d, f = cfg["d_model"], cfg["d_ff"]
    n_mats = 3 if cfg["ffn_type"] == "swiglu" else 2
    flops = 2.0 * rows * d * f * n_mats * passes
    weights = experts * n_mats * d * f * bytes_per_el
    nbytes = weights * passes + 2.0 * rows * d * bytes_per_el * passes
    if passes > 1:
        nbytes += weights            # weight gradients written
    return flops, nbytes


def dispatch_combine_work(kept_rows: float, tokens: float, cfg: dict, *,
                          bytes_per_el: int = 2):
    """(FLOPs, bytes) of dispatch plus combine: dispatch reads and writes
    each kept row, combine reads each kept row and writes each token's
    output row.  The weighted sum in combine is 2 FLOPs per element."""
    d = cfg["d_model"]
    nbytes = (3.0 * kept_rows + tokens) * d * bytes_per_el
    return 2.0 * kept_rows * d, nbytes
