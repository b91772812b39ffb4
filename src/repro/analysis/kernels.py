"""Pass 1 — Pallas kernel contracts.

Hybrid AST + call-site registry:

* The **AST walk** enumerates every ``pl.pallas_call`` expression under
  ``src/repro/kernels/`` (module, enclosing function, grid arity, literal
  in_spec count).  Any site without a registry entry is an
  ``unregistered-kernel`` error — the regression gate that forces future
  kernels to declare their contract here — and arity disagreements between
  the AST and the registry are ``site-mismatch`` errors (stale registry).

* The **registry** evaluates each site numerically at every paper model
  shape (``configs/paper_models.py``, at scales 1 and 4): concrete block
  shapes via the same ``tiling.block_and_pad`` the kernels call, dtypes,
  index-map structure and scratch.  From that the checks compute:

  - ``vmem-over-budget``: static per-grid-step footprint (resident blocks
    once, streamed blocks twice for the double-buffered pipeline, plus
    scratch) exceeding the per-core budget;
  - ``misaligned-block``: a block's last dim that is neither the full
    array extent nor a multiple of the 128-lane tile, or its second-to-last
    dim that is neither the full extent nor a multiple of the dtype's
    sublane tile (a dim of 1 passes only at full extent, or outside the
    last two dims) — the rule Mosaic enforces when it compiles the call;
  - ``untiled-block``: blocks covering the full extent of a dim that scales
    with tokens (T), dispatch rows (R = E*C) or a contraction (K) — the
    PR-4 VMEM ceilings surfaced here until the dispatch/combine/matmul
    kernels were re-tiled (this check now guards against regressions);
  - ``grid-uncovered``: affine index maps whose tile x grid-steps product
    does not cover the padded array extent (or const-indexed dims smaller
    than the array — regions the kernel would silently never visit).

Index-map components are ``("c",)`` const, ``("g", axis)`` affine in one
grid axis, or ``("x",)`` computed (e.g. flash attention's GQA head map) —
computed maps stream (double-buffer) but are exempt from coverage.
"""
from __future__ import annotations

import ast
import dataclasses
import os

from repro.analysis.findings import Finding
from repro.configs.base import MoEConfig
from repro.configs.paper_models import (BERT2GPT2, BERT_LARGE, GPT2_MOE,
                                        TRANSFORMER_XL)
from repro.core.gating import capacity
from repro.core.microop import resolve_chunk_count
from repro.kernels.dispatch import combine_vmem_bytes, dispatch_vmem_bytes
from repro.kernels.tiling import (LANE, SUBLANE, VMEM_BUDGET_BYTES,
                                  block_and_pad, block_bytes, pad_to,
                                  sublane_for)

PAPER_MODELS = (TRANSFORMER_XL, GPT2_MOE, BERT2GPT2, BERT_LARGE)

# chunk count for the re-entrant micro-op pipeline variants: the default
# MoEConfig.n_microops, resolved per shape exactly as the runtime does
# (core.microop.resolve_chunk_count picks the largest divisor of C)
PIPELINE_MICROOPS = MoEConfig().n_microops

# token count for the static shape cases: global tokens at scale 1 (the
# per-device a2a payload of the paper's 16-expert training runs), shrunk
# with the model at smaller scales but floored at two lane tiles
BASE_TOKENS = 4096


# ---------------------------------------------------------------- shapes --

@dataclasses.dataclass(frozen=True)
class ShapeCase:
    """One numeric evaluation point: a paper model at a benchmark scale."""
    name: str
    T: int     # tokens entering the MoE layer
    D: int     # model width
    F: int     # expert FFN width
    E: int     # experts
    K: int     # top-k
    C: int     # per-expert capacity (core.gating.capacity)
    R: int     # dispatch rows = E * C
    H: int     # attention heads
    HD: int    # head dim


def build_cases(scales=(1, 4)) -> list[ShapeCase]:
    cases = []
    for cfg in PAPER_MODELS:
        for s in scales:
            d = max(128, cfg.d_model // s)
            f = max(128, (cfg.moe.d_ff or cfg.d_ff) // s)
            t = max(256, BASE_TOKENS // s)
            c = capacity(t, cfg.moe.n_experts, cfg.moe.top_k,
                         cfg.moe.capacity_factor)
            cases.append(ShapeCase(
                name=f"{cfg.name}/s{s}", T=t, D=d, F=f,
                E=cfg.moe.n_experts, K=cfg.moe.top_k, C=c,
                R=cfg.moe.n_experts * c, H=cfg.n_heads,
                HD=max(8, d // cfg.n_heads)))
    return cases


# ------------------------------------------------------------- AST sites --

@dataclasses.dataclass
class AstSite:
    module: str            # repo-relative posix path
    qualname: str          # innermost enclosing function
    lineno: int
    grid_len: int | None   # None when the grid kwarg is not a literal tuple
    n_in_specs: int | None  # None when in_specs is not a literal list


def _is_pallas_call(node: ast.Call) -> bool:
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr == "pallas_call"
    return isinstance(fn, ast.Name) and fn.id == "pallas_call"


def _kwarg(node: ast.Call, name: str):
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


class _SiteVisitor(ast.NodeVisitor):
    def __init__(self, module: str):
        self.module = module
        self.stack: list[str] = []
        self.sites: list[AstSite] = []

    def visit_FunctionDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        if _is_pallas_call(node):
            grid = _kwarg(node, "grid")
            specs = _kwarg(node, "in_specs")
            self.sites.append(AstSite(
                module=self.module,
                qualname=self.stack[-1] if self.stack else "<module>",
                lineno=node.lineno,
                grid_len=len(grid.elts) if isinstance(grid, ast.Tuple)
                else None,
                n_in_specs=len(specs.elts)
                if isinstance(specs, (ast.List, ast.Tuple)) else None))
        self.generic_visit(node)


def iter_pallas_sites(kernels_dir: str, rel_prefix: str = "") -> list[AstSite]:
    sites = []
    for fname in sorted(os.listdir(kernels_dir)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(kernels_dir, fname)
        rel = os.path.join(rel_prefix, fname).replace(os.sep, "/") \
            if rel_prefix else fname
        with open(path) as fh:
            tree = ast.parse(fh.read(), filename=path)
        v = _SiteVisitor(rel)
        v.visit(tree)
        sites.extend(v.sites)
    return sites


# ------------------------------------------------------- numeric model ----

CONST = ("c",)
EXPR = ("x",)


def grid_dim(axis: int):
    return ("g", axis)


@dataclasses.dataclass
class Block:
    name: str
    shape: tuple
    dtype: str
    index: tuple               # per-dim CONST / grid_dim(i) / EXPR
    array_shape: tuple | None = None   # padded full extents
    roles: dict = dataclasses.field(default_factory=dict)  # dim -> T/R/K

    @property
    def resident(self) -> bool:
        return all(c == CONST for c in self.index)

    @property
    def nbytes(self) -> int:
        return block_bytes(self.shape, self.dtype)


@dataclasses.dataclass
class SiteEval:
    module: str                # basename, e.g. "dispatch.py"
    qualname: str
    case: str
    grid: tuple
    inputs: list
    outputs: list
    scratch: list = dataclasses.field(default_factory=list)  # (shape, dtype)
    variant: str = ""          # distinguishes multiple call shapes per site

    def blocks(self):
        return list(self.inputs) + list(self.outputs)

    def footprint(self) -> int:
        """Static per-grid-step VMEM bytes: resident blocks live once for
        the whole call, streamed blocks are double-buffered by the
        pipeline, scratch persists."""
        total = 0
        for b in self.blocks():
            total += b.nbytes if b.resident else 2 * b.nbytes
        for shape, dtype in self.scratch:
            total += block_bytes(shape, dtype)
        return total

    def block_key(self, b: Block) -> str:
        return f"{self.variant}:{b.name}" if self.variant else b.name


# ------------------------------------------------------------- registry ---

def _eval_topk_gating(c: ShapeCase):
    bt, t_pad = block_and_pad(c.T, 1024)
    return [SiteEval(
        "topk_gating.py", "topk_gating_fused", c.name, (t_pad // bt,),
        inputs=[
            Block("x", (bt, c.D), "float32", (grid_dim(0), CONST),
                  (t_pad, c.D)),
            Block("router", (c.D, c.E), "float32", (CONST, CONST),
                  (c.D, c.E)),
        ],
        outputs=[
            Block("idx", (bt, c.K), "int32", (grid_dim(0), CONST),
                  (t_pad, c.K)),
            Block("w", (bt, c.K), "float32", (grid_dim(0), CONST),
                  (t_pad, c.K)),
            Block("probs", (bt, c.E), "float32", (grid_dim(0), CONST),
                  (t_pad, c.E)),
        ])]


def _chunk_capacity(c: ShapeCase) -> int:
    """Per-chunk capacity of the micro-op pipeline at this shape: C split
    into ``PIPELINE_MICROOPS`` uniform chunks, resolved like the runtime."""
    return c.C // resolve_chunk_count(c.C, PIPELINE_MICROOPS)


def _dispatch_rows_eval(c: ShapeCase, rows: int, variant: str) -> SiteEval:
    br, r_pad = block_and_pad(rows, 1024)
    bx, t_pad = block_and_pad(c.T, 512)
    ev = SiteEval(
        "dispatch.py", "dispatch_rows", c.name,
        (r_pad // br, t_pad // bx),
        inputs=[
            Block("src_tok", (br, 1), "int32", (grid_dim(0), CONST),
                  (r_pad, 1)),
            Block("scale", (br, 1), "float32", (grid_dim(0), CONST),
                  (r_pad, 1)),
            Block("x", (bx, c.D), "float32", (grid_dim(1), CONST),
                  (t_pad, c.D)),
        ],
        outputs=[
            Block("out", (br, c.D), "float32", (grid_dim(0), CONST),
                  (r_pad, c.D)),
        ],
        variant=variant)
    assert ev.footprint() == dispatch_vmem_bytes(br, bx, c.D), \
        "analyzer estimate diverged from kernels.dispatch.dispatch_vmem_bytes"
    return ev


def _eval_dispatch_rows(c: ShapeCase):
    # full-buffer call plus the chunk-granular shape the re-entrant micro-op
    # pipeline dispatches per landed chunk (R/n rows of the slot buffer)
    return [_dispatch_rows_eval(c, c.R, ""),
            _dispatch_rows_eval(c, c.E * _chunk_capacity(c), "chunk")]


def _combine_rows_eval(c: ShapeCase, rows: int, variant: str) -> SiteEval:
    bt, t_pad = block_and_pad(c.T, 1024)
    brf, r_pad = block_and_pad(rows, 512)
    ev = SiteEval(
        "dispatch.py", "combine_rows", c.name,
        (t_pad // bt, r_pad // brf),
        inputs=[
            Block("rows", (bt, c.K), "int32", (grid_dim(0), CONST),
                  (t_pad, c.K)),
            Block("weights", (bt, c.K), "float32", (grid_dim(0), CONST),
                  (t_pad, c.K)),
            Block("buf", (brf, c.D), "float32", (grid_dim(1), CONST),
                  (r_pad, c.D)),
        ],
        outputs=[
            Block("out", (bt, c.D), "float32", (grid_dim(0), CONST),
                  (t_pad, c.D)),
        ],
        variant=variant)
    assert ev.footprint() == combine_vmem_bytes(bt, brf, c.D, c.K), \
        "analyzer estimate diverged from kernels.dispatch.combine_vmem_bytes"
    return ev


def _eval_combine_rows(c: ShapeCase):
    return [_combine_rows_eval(c, c.R, ""),
            _combine_rows_eval(c, c.E * _chunk_capacity(c), "chunk")]


# the weighted replica split keeps only metadata resident: the [E, R]
# integer-cumsum weight table and the replica->slot map.  R here is the
# replica-table width — bounded by the device count; 64 is a conservative
# upper bound for the paper's largest testbed.
ROUTE_REPLICA_W = 64


def _eval_weighted_route(c: ShapeCase):
    bt, t_pad = block_and_pad(c.T, 1024)
    rw = ROUTE_REPLICA_W
    return [SiteEval(
        "dispatch.py", "weighted_route", c.name, (t_pad // bt,),
        inputs=[
            Block("expert_idx", (bt, c.K), "int32", (grid_dim(0), CONST),
                  (t_pad, c.K)),
            Block("position", (bt, c.K), "int32", (grid_dim(0), CONST),
                  (t_pad, c.K)),
            Block("cum_weights", (c.E, rw), "int32", (CONST, CONST),
                  (c.E, rw)),
            Block("slot_of", (c.E, rw), "int32", (CONST, CONST),
                  (c.E, rw)),
        ],
        outputs=[
            Block("rows", (bt, c.K), "int32", (grid_dim(0), CONST),
                  (t_pad, c.K)),
        ])]


def _eval_topk_positions(c: ShapeCase):
    bt, t_pad = block_and_pad(c.T, 512)
    e_pad = pad_to(max(c.E, 1), LANE)
    # grid (phase, T tiles); the position block's index is (i * phase, 0)
    return [SiteEval(
        "topk_gating.py", "topk_positions", c.name, (2, t_pad // bt),
        inputs=[
            Block("idx", (bt, c.K), "int32", (grid_dim(1), CONST),
                  (t_pad, c.K)),
        ],
        outputs=[
            Block("pos", (bt, c.K), "int32", (EXPR, CONST),
                  (t_pad, c.K)),
            Block("cnt", (SUBLANE, e_pad), "int32", (CONST, CONST),
                  (SUBLANE, e_pad)),
        ])]


def _grouped_ffn_eval(c: ShapeCase, cap: int, variant: str) -> SiteEval:
    bt, t_pad = block_and_pad(cap, 256)
    bf, f_pad = block_and_pad(c.F, 512, sub=LANE)
    g3 = (grid_dim(0), grid_dim(1), CONST)
    return SiteEval(
        "moe_ffn.py", "grouped_ffn", c.name,
        (c.E, t_pad // bt, f_pad // bf),
        inputs=[
            Block("x", (1, bt, c.D), "float32", g3, (c.E, t_pad, c.D)),
            Block("wi", (1, c.D, bf), "float32",
                  (grid_dim(0), CONST, grid_dim(2)), (c.E, c.D, f_pad)),
            Block("wu", (1, c.D, bf), "float32",
                  (grid_dim(0), CONST, grid_dim(2)), (c.E, c.D, f_pad)),
            Block("wo", (1, bf, c.D), "float32",
                  (grid_dim(0), grid_dim(2), CONST), (c.E, f_pad, c.D)),
        ],
        outputs=[
            Block("out", (1, bt, c.D), "float32", g3, (c.E, t_pad, c.D)),
        ],
        variant=variant)


def _eval_grouped_ffn(c: ShapeCase):
    # per-expert token extent is the dispatch capacity; the "chunk" variant
    # is the re-entrant call the micro-op pipeline issues per landed a2a
    # chunk (core.microop.pipelined_expert_ffn): same kernel, capacity C/n
    return [_grouped_ffn_eval(c, c.C, ""),
            _grouped_ffn_eval(c, _chunk_capacity(c), "chunk")]


# the grouped-FFN backward (kernels/ops.py::_grouped_ffn_bwd) expresses
# every dgrad/wgrad as a grouped_matmul; these are its gelu-path GEMM
# shapes.  The contraction dim is tiled (grid axis 3, innermost) with the
# output block revisited and accumulated — no full-K resident block.
_GMM_VARIANTS = (
    ("recompute_h", "C", "D", "F"),   # h  = x    @ wi
    ("dgrad_x", "C", "F", "D"),       # dx = dh   @ wi.T
    ("wgrad_in", "D", "C", "F"),      # dwi = x.T @ dh
    ("wgrad_out", "F", "C", "D"),     # dwo = act.T @ dy
)


def _eval_grouped_matmul(c: ShapeCase):
    evs = []
    dims = {"T": c.T, "C": c.C, "D": c.D, "F": c.F}
    for variant, m_r, k_r, n_r in _GMM_VARIANTS:
        m, k, n = dims[m_r], dims[k_r], dims[n_r]
        bm, m_pad = block_and_pad(m, 256)
        bn, n_pad = block_and_pad(n, 512, sub=LANE)
        bk, k_pad = block_and_pad(k, 512, sub=LANE)
        evs.append(SiteEval(
            "moe_ffn.py", "grouped_matmul", c.name,
            (c.E, m_pad // bm, n_pad // bn, k_pad // bk),
            inputs=[
                Block("a", (1, bm, bk), "float32",
                      (grid_dim(0), grid_dim(1), grid_dim(3)),
                      (c.E, m_pad, k_pad)),
                Block("b", (1, bk, bn), "float32",
                      (grid_dim(0), grid_dim(3), grid_dim(2)),
                      (c.E, k_pad, n_pad)),
            ],
            outputs=[
                Block("out", (1, bm, bn), "float32",
                      (grid_dim(0), grid_dim(1), grid_dim(2)),
                      (c.E, m_pad, n_pad)),
            ],
            variant=variant))
    return evs


def _eval_flash_attention(c: ShapeCase):
    b = 1
    s, hd = c.T, c.HD
    bq = bk = min(128, s)
    # GQA head map is computed, not affine: streamed, coverage-exempt
    kv_index = (EXPR, grid_dim(2), CONST)
    return [SiteEval(
        "flash_attention.py", "flash_attention", c.name,
        (b * c.H, s // bq, s // bk),
        inputs=[
            Block("q", (1, bq, hd), "float32",
                  (grid_dim(0), grid_dim(1), CONST), (b * c.H, s, hd)),
            Block("k", (1, bk, hd), "float32", kv_index, (b * c.H, s, hd)),
            Block("v", (1, bk, hd), "float32", kv_index, (b * c.H, s, hd)),
        ],
        outputs=[
            Block("out", (1, bq, hd), "float32",
                  (grid_dim(0), grid_dim(1), CONST), (b * c.H, s, hd)),
        ],
        scratch=[((bq, 1), "float32"), ((bq, 1), "float32"),
                 ((bq, hd), "float32")])]


def _eval_rwkv6(_c=None):
    # canonical rwkv6-1.6b time-mix shape: hd = 64, chunk = 64
    b, h, t, hd, chunk = 8, 32, 1024, 64, 64
    tile = (grid_dim(0), grid_dim(1), CONST)
    blk = [Block(n, (1, chunk, hd), "float32", tile, (b * h, t, hd))
           for n in ("r", "k", "v", "w")]
    return [SiteEval(
        "rwkv6.py", "rwkv6_wkv", "canonical", (b * h, t // chunk),
        inputs=blk + [Block("u", (1, 1, hd), "float32",
                            (grid_dim(0), CONST, CONST), (b * h, 1, hd))],
        outputs=[Block("out", (1, chunk, hd), "float32", tile,
                       (b * h, t, hd))],
        scratch=[((hd, hd), "float32")])]


def _eval_ssd(_c=None):
    # canonical zamba2 SSD shape: P = 64, N = 128, chunk Q = 128
    bsz, h, t, p, n, q = 8, 24, 1024, 64, 128, 128
    tile = (grid_dim(0), grid_dim(1), CONST)
    return [SiteEval(
        "ssd.py", "ssd_scan", "canonical", (bsz * h, t // q),
        inputs=[
            Block("x", (1, q, p), "float32", tile, (bsz * h, t, p)),
            Block("dt", (1, 1, q), "float32",
                  (grid_dim(0), CONST, grid_dim(1)), (bsz * h, 1, t)),
            Block("a_log", (1, 1, 1), "float32", (grid_dim(0), CONST, CONST),
                  (bsz * h, 1, 1)),
            Block("b", (1, q, n), "float32", tile, (bsz * h, t, n)),
            Block("c", (1, q, n), "float32", tile, (bsz * h, t, n)),
            Block("d_skip", (1, 1, 1), "float32",
                  (grid_dim(0), CONST, CONST), (bsz * h, 1, 1)),
        ],
        outputs=[Block("out", (1, q, p), "float32", tile, (bsz * h, t, p))],
        scratch=[((p, n), "float32")])]


@dataclasses.dataclass(frozen=True)
class RegistryEntry:
    eval_fn: object            # ShapeCase -> list[SiteEval]
    per_case: bool = True      # False: one canonical evaluation


REGISTRY = {
    ("topk_gating.py", "topk_gating_fused"):
        RegistryEntry(_eval_topk_gating),
    ("topk_gating.py", "topk_positions"):
        RegistryEntry(_eval_topk_positions),
    ("dispatch.py", "dispatch_rows"): RegistryEntry(_eval_dispatch_rows),
    ("dispatch.py", "combine_rows"): RegistryEntry(_eval_combine_rows),
    ("dispatch.py", "weighted_route"): RegistryEntry(_eval_weighted_route),
    ("moe_ffn.py", "grouped_ffn"): RegistryEntry(_eval_grouped_ffn),
    ("moe_ffn.py", "grouped_matmul"): RegistryEntry(_eval_grouped_matmul),
    ("flash_attention.py", "flash_attention"):
        RegistryEntry(_eval_flash_attention),
    ("rwkv6.py", "rwkv6_wkv"): RegistryEntry(_eval_rwkv6, per_case=False),
    ("ssd.py", "ssd_scan"): RegistryEntry(_eval_ssd, per_case=False),
}


# --------------------------------------------------------------- checks ---

def check_vmem(ev: SiteEval, budget: int, module: str) -> list:
    fp = ev.footprint()
    if fp <= budget:
        return []
    top = max(ev.blocks(), key=lambda b: b.nbytes)
    key = f"{ev.variant}@{ev.case}" if ev.variant else ev.case
    return [Finding(
        "vmem-over-budget", module, ev.qualname, key,
        f"{ev.qualname}{'/' + ev.variant if ev.variant else ''} at "
        f"{ev.case}: static VMEM footprint {fp:,} B > budget {budget:,} B "
        f"(largest block: {top.name} {list(top.shape)} {top.dtype}, "
        f"{top.nbytes:,} B{' resident' if top.resident else ''})",
        data={"footprint_bytes": fp, "budget_bytes": budget,
              "largest_block": top.name})]


def check_alignment(ev: SiteEval, module: str) -> list:
    out = []
    for b in ev.blocks():
        if len(b.shape) < 1:
            continue
        needs = [(len(b.shape) - 1, LANE)]
        if len(b.shape) >= 2:
            needs.append((len(b.shape) - 2, sublane_for(b.dtype)))
        for dim, need in needs:
            size = int(b.shape[dim])
            full = b.array_shape and int(b.array_shape[dim]) == size
            if full or size % need == 0:
                continue
            out.append(Finding(
                "misaligned-block", module, ev.qualname,
                f"{ev.block_key(b)}[dim{dim}]",
                f"{ev.qualname}: block {b.name} dim {dim} = {size} is not "
                f"a multiple of the {need}-wide hardware tile for "
                f"{b.dtype} (and not the full array extent) — the "
                f"MXU/VPU will run under-utilized or relayout"))
    return out


def check_untiled(ev: SiteEval, module: str) -> list:
    out = []
    for b in ev.blocks():
        for dim, role in sorted(b.roles.items()):
            if b.array_shape is None:
                continue
            if int(b.shape[dim]) != int(b.array_shape[dim]):
                continue
            out.append(Finding(
                "untiled-block", module, ev.qualname,
                f"{ev.block_key(b)}[{role}]",
                f"{ev.qualname}{'/' + ev.variant if ev.variant else ''}: "
                f"block {b.name} holds the full {role}-extent "
                f"({int(b.shape[dim])} at {ev.case}) in VMEM — footprint "
                f"scales with {role} instead of the tile (known re-tiling "
                f"target)",
                severity="warning",
                data={"dim": dim, "role": role,
                      "extent": int(b.shape[dim])}))
    return out


def check_coverage(ev: SiteEval, module: str) -> list:
    out = []
    for b in ev.blocks():
        if b.array_shape is None:
            continue
        for dim, comp in enumerate(b.index):
            size = int(b.shape[dim])
            extent = int(b.array_shape[dim])
            if comp == CONST:
                covered = size == extent
            elif comp == EXPR:
                continue
            else:
                steps = int(ev.grid[comp[1]])
                covered = size * steps == extent
            if not covered:
                out.append(Finding(
                    "grid-uncovered", module, ev.qualname,
                    f"{ev.block_key(b)}[dim{dim}]@{ev.case}",
                    f"{ev.qualname}: block {b.name} dim {dim} tile {size} "
                    f"x its grid steps does not cover the padded extent "
                    f"{extent} at {ev.case} — part of the array is never "
                    f"visited (or written) by the index map"))
    return out


# ------------------------------------------------------------ entry points

def _module_path(basename: str, sites: list) -> str:
    for s in sites:
        if os.path.basename(s.module) == basename:
            return s.module
    return basename


def analyze_kernels(kernels_dir: str, *, budget: int = VMEM_BUDGET_BYTES,
                    scales=(1, 4), registry: dict | None = None,
                    rel_prefix: str = "src/repro/kernels") -> list:
    """Run pass 1: AST inventory x registry numerics -> findings."""
    registry = REGISTRY if registry is None else registry
    sites = iter_pallas_sites(kernels_dir, rel_prefix=rel_prefix)
    findings: list[Finding] = []
    seen: set[str] = set()

    def add(fs):
        for f in fs:
            if f.fingerprint not in seen:
                seen.add(f.fingerprint)
                findings.append(f)

    site_keys = {(os.path.basename(s.module), s.qualname) for s in sites}
    for s in sites:
        if (os.path.basename(s.module), s.qualname) not in registry:
            add([Finding(
                "unregistered-kernel", s.module, s.qualname, s.qualname,
                f"pl.pallas_call in {s.qualname} ({s.module}:{s.lineno}) "
                f"has no entry in repro.analysis.kernels.REGISTRY — declare "
                f"its block shapes so the VMEM/tiling contract is checked",
                lineno=s.lineno)])
    for (basename, qual), entry in registry.items():
        module = _module_path(basename, sites)
        if (basename, qual) not in site_keys:
            add([Finding(
                "missing-kernel", module, qual, qual,
                f"registry entry ({basename}, {qual}) matches no "
                f"pl.pallas_call site — kernel renamed or removed; update "
                f"the registry", severity="warning")])
            continue
        ast_site = next(s for s in sites
                        if os.path.basename(s.module) == basename
                        and s.qualname == qual)
        cases = build_cases(scales) if entry.per_case else [None]
        for case in cases:
            for ev in entry.eval_fn(case):
                if ast_site.grid_len is not None \
                        and ast_site.grid_len != len(ev.grid):
                    add([Finding(
                        "site-mismatch", module, qual,
                        f"grid{'@' + ev.variant if ev.variant else ''}",
                        f"{qual}: registry grid arity {len(ev.grid)} != "
                        f"AST literal grid arity {ast_site.grid_len} — "
                        f"the registry is stale",
                        lineno=ast_site.lineno)])
                if ast_site.n_in_specs is not None \
                        and ast_site.n_in_specs != len(ev.inputs):
                    add([Finding(
                        "site-mismatch", module, qual,
                        f"in_specs{'@' + ev.variant if ev.variant else ''}",
                        f"{qual}: registry declares {len(ev.inputs)} input "
                        f"blocks but the AST in_specs list has "
                        f"{ast_site.n_in_specs} — the registry is stale",
                        lineno=ast_site.lineno)])
                add(check_vmem(ev, budget, module))
                add(check_alignment(ev, module))
                add(check_untiled(ev, module))
                add(check_coverage(ev, module))
    return findings


# ----------------------------------------------------- bench annotation ---

def _bench_case(**kw) -> ShapeCase:
    base = dict(name=kw.pop("name", "bench"), T=0, D=0, F=0, E=1, K=2,
                C=0, R=0, H=1, HD=8)
    base.update(kw)
    return ShapeCase(**base)


def bench_row_vmem(row: dict) -> int | None:
    """Static VMEM estimate (bytes, max over the kernels the bench row
    exercises) for one BENCH_kernels.json row; None for unknown benches."""
    shape = row.get("shape", {})
    kind = row.get("bench")
    evs: list[SiteEval] = []
    if kind == "gating":
        c = _bench_case(T=shape["T"], D=shape["D"], E=shape["E"],
                        K=shape.get("k", 2))
        evs += _eval_topk_gating(c)
    elif kind == "dispatch_combine":
        c = _bench_case(T=shape["T"], D=shape["D"], E=shape["E"],
                        C=shape["C"], R=shape["E"] * shape["C"],
                        K=shape.get("k", 2))
        evs += _eval_dispatch_rows(c) + _eval_combine_rows(c)
    elif kind == "routing":
        c = _bench_case(T=shape["T"], E=shape["E"], K=shape.get("k", 2))
        evs += _eval_topk_positions(c) + _eval_weighted_route(c)
    elif kind == "grouped_ffn":
        # the bench's T is already the per-expert row count
        c = _bench_case(E=shape["E"], C=shape["T"], D=shape["D"],
                        F=shape["F"])
        evs += _eval_grouped_ffn(c)
    elif kind == "layer_fwdbwd":
        t = shape["B"] * shape["S"]
        e, k = shape["E"], shape.get("k", 2)
        cap = capacity(t, e, k, 1.25)
        c = _bench_case(T=t, D=shape["D"], F=shape["F"], E=e, K=k,
                        C=cap, R=e * cap)
        evs += (_eval_topk_gating(c) + _eval_topk_positions(c)
                + _eval_dispatch_rows(c) + _eval_combine_rows(c)
                + _eval_grouped_ffn(c) + _eval_grouped_matmul(c))
    else:
        return None
    return max(ev.footprint() for ev in evs)


def annotate_bench_rows(rows: list, budget: int = VMEM_BUDGET_BYTES) -> list:
    """Attach static_vmem_bytes / vmem_budget_bytes / vmem_fits to each
    bench row (in place; returns rows)."""
    for row in rows:
        est = bench_row_vmem(row)
        if est is None:
            continue
        row["static_vmem_bytes"] = est
        row["vmem_budget_bytes"] = budget
        row["vmem_fits"] = est <= budget
    return rows
