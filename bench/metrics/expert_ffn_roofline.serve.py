"""Grouped expert FFN's share of its roofline while serving: the least
time for the rows routed and kept and the experts they touch
(``bench/flops.expert_ffn_work``, one forward pass) over the summed device
time of the grouped-FFN kernel.

The kernel (``kernels/moe_ffn.grouped_ffn``) has no name in the trace; it
is the Mosaic custom call with operands x [S, T, d], wi [S, d, F],
wu [S, d, F], wo [S, F, d] and one result [S, T, d] (S expert slots).
"""
from bench import flops


def is_grouped_ffn(op, d: int, f: int) -> bool:
    if not op.is_kernel or len(op.operands) != 4 or len(op.results) != 1:
        return False
    x, wi, wu, wo = (dims for _, dims in op.operands)
    return (len(x) == 3 and x[2] == d and wi == (x[0], d, f)
            and wu == wi and wo == (x[0], f, d))


def read(rec):
    if rec.trace is None:
        return None
    d, f = rec.cfg["d_model"], rec.cfg["d_ff"]
    took = rec.trace.kernel_seconds(lambda op: is_grouped_ffn(op, d, f))
    fl, nb = flops.expert_ffn_work(rec.work["kept_rows"],
                                   rec.work["experts_touched"], rec.cfg)
    return flops.share_pct(flops.roofline_s(fl, nb, rec.peak), took)
