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

from bench import blocks

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


# --- model FLOPs, from the block's counts ----------------------------------

def _head(cfg: dict) -> int:
    return cfg["d_model"] * cfg["vocab_size"]


def train_flops_per_token(block, cfg: dict, seq: int) -> float:
    """Forward and backward model FLOPs per token: 6 x the weights a token
    meets (in every layer with top-k experts, and the LM head; the
    embedding is a lookup), plus the block's attention FLOPs of a training
    token.  Recomputation is not counted."""
    n = sum(block.layer_params(cfg, cfg["top_k"])) + _head(cfg)
    return 6.0 * n + block.attention_flops_train(cfg, seq)


def serve_prefill_flops(block, cfg: dict, s: int) -> float:
    """Forward FLOPs of one prompt of length ``s``: every position through
    every layer with the serving top-k, the block's attention, and the LM
    head for the last position only."""
    n = sum(block.layer_params(cfg, cfg["serve_top_k"]))
    return (2.0 * n * s + block.attention_flops_prefill(cfg, s)
            + 2.0 * _head(cfg))


def serve_decode_flops(block, cfg: dict, pos: int) -> float:
    """Forward FLOPs of one decoded token at absolute position ``pos``
    (attending over pos + 1 cached positions), LM head included."""
    n = sum(block.layer_params(cfg, cfg["serve_top_k"]))
    return (2.0 * n + block.attention_flops_decode(cfg, pos)
            + 2.0 * _head(cfg))


# --- kernels ---------------------------------------------------------------

def expert_ffn_work(rows: float, experts: float, cfg: dict, *,
                    passes: int = 1, bytes_per_el: int = 2):
    """(FLOPs, bytes) of the grouped expert FFN over ``rows`` kept
    (token, expert) rows touching ``experts`` distinct experts of the
    block the configuration names.

    ``passes`` is 1 for a forward and 3 for forward and backward (the
    backward is a data gradient and a weight gradient per matrix).  Bytes
    are each touched expert's weights read once per pass plus the rows in
    and out; for a backward the weight gradients are written as well."""
    d, p = cfg["d_model"], blocks.named(cfg).expert_params(cfg)
    flops = 2.0 * rows * p * passes
    weights = experts * p * bytes_per_el
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
