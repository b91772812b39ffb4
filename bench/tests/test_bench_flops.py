"""Operation and byte counts of the work, pinned at the configurations'
published widths, and the peaks table."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import blocks, flops  # noqa: E402

GPT2 = json.loads((ROOT / "bench/configs/gpt2-moe.json").read_text())
TXL = json.loads((ROOT / "bench/configs/transformer-xl-moe.json").read_text())
BLOCK = blocks.named(GPT2)


def test_expert_ffn_counts_at_gpt2_moe_width():
    # 1024 kept rows through 16 experts of d 768, F 3072 (gelu: 2 matrices)
    fl, nb = flops.expert_ffn_work(1024, 16, GPT2)
    assert fl == 2 * 1024 * 768 * 3072 * 2 == 9_663_676_416
    # 16 experts' two bf16 matrices, plus the rows in and out
    assert nb == 16 * 2 * 768 * 3072 * 2 + 2 * 1024 * 768 * 2 == 154_140_672
    fl3, nb3 = flops.expert_ffn_work(1024, 16, GPT2, passes=3)
    assert fl3 == 3 * fl
    assert nb3 == 3 * nb + 16 * 2 * 768 * 3072 * 2


def test_dispatch_combine_counts_at_gpt2_moe_width():
    fl, nb = flops.dispatch_combine_work(1024, 1024, GPT2)
    assert nb == (3 * 1024 + 1024) * 768 * 2 == 6_291_456
    assert fl == 2 * 1024 * 768


def test_mfu_flops_per_token():
    # gpt2-moe, 12 layers, top-2, seq 512: 6 x 180,302,592 weights met per
    # token + 6 x 12 x 512 x 768 of causal attention
    assert flops.train_flops_per_token(BLOCK, GPT2, 512) == 1_110_127_104
    # transformer-xl-moe at the cell's 3 layers
    assert TXL["n_layers"] == 3
    assert blocks.named(TXL) is BLOCK
    assert flops.train_flops_per_token(BLOCK, TXL, 512) == 583_827_456
    assert flops.serve_decode_flops(BLOCK, GPT2, 0) == pytest.approx(
        2 * (12 * (4 * 768 ** 2 + 768 * 16 + 2 * 768 * 3072))
        + 4 * 12 * 768 + 2 * 768 * 50257)


def test_roofline_share():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    least = flops.roofline_s(197e12, 1.0, peak)
    assert least == pytest.approx(1.0)
    assert flops.share_pct(least, 2.0) == pytest.approx(50.0)
    assert flops.share_pct(least, 0.0) is None


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_source"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError):
        flops.peaks(kind)
