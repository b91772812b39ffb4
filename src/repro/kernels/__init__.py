"""Pallas TPU kernels for the perf-critical compute layers, with pure-jnp
oracles (ref.py) and backend-dispatching wrappers (ops.py):

  moe_ffn          grouped expert FFN GEMM (the MoE hot spot, paper Fig. 2)
                   + grouped_matmul, the dgrad/wgrad primitive of its VJP
  topk_gating      fused router matmul + softmax + top-k
  dispatch         fused capacity-buffer scatter / gate-weighted combine
  flash_attention  online-softmax attention (causal/SWA/bidirectional, GQA)
  rwkv6            chunked WKV recurrence (rwkv6-1.6b)
  ssd              Mamba2 chunk scan (zamba2-1.2b)

Kernels compile natively (Mosaic) on a TPU backend.  On the CPU test
backend they run with ``interpret=True`` (kernel bodies executed on the
host) against ref.py, and tests/test_tpu_compile.py compiles the main-path
ones ahead of time for a described v5e chip.
"""
from repro.kernels.ops import (dispatch_combine_op, flash_attention_op,
                               grouped_ffn_op, on_tpu, resolve_backend,
                               rwkv6_op, ssd_op, topk_gating_op)
from repro.kernels.topk_gating import topk_gating_fused
