"""Grouped expert FFN's share of its roofline in training: the least time
for the forward and backward of the rows routed and kept (tokens x k x the
kept share the reference measured on the checked steps; three passes, see
``bench/flops.expert_ffn_work``) over the summed device time of the
grouped-FFN forward kernel and the grouped-matmul kernels of its backward.

Neither kernel has a name in the trace.  The forward
(``kernels/moe_ffn.grouped_ffn``) is the Mosaic custom call with operands
x [S, T, d], wi [S, d, F], wu [S, d, F], wo [S, F, d]; the backward's
``grouped_matmul`` is a Mosaic custom call a [S, M, K] @ b [S, K, N] with
d or F among its dimensions.
"""
from bench import flops


def is_grouped_ffn(op, d: int, f: int) -> bool:
    if not op.is_kernel or len(op.operands) != 4 or len(op.results) != 1:
        return False
    x, wi, wu, wo = (dims for _, dims in op.operands)
    return (len(x) == 3 and x[2] == d and wi == (x[0], d, f)
            and wu == wi and wo == (x[0], f, d))


def is_grouped_matmul(op, d: int, f: int) -> bool:
    if not op.is_kernel or len(op.operands) != 2 or len(op.results) != 1:
        return False
    (_, a), (_, b) = op.operands
    return (len(a) == 3 and len(b) == 3 and a[0] == b[0] and a[2] == b[1]
            and bool({d, f} & {a[1], a[2], b[2]}))


def read(rec):
    if rec.trace is None:
        return None
    d, f = rec.cfg["d_model"], rec.cfg["d_ff"]
    took = rec.trace.kernel_seconds(
        lambda op: is_grouped_ffn(op, d, f) or is_grouped_matmul(op, d, f))
    fl, nb = flops.expert_ffn_work(rec.work["kept_rows"],
                                   rec.work["experts_touched"], rec.cfg,
                                   passes=3)
    return flops.share_pct(flops.roofline_s(fl, nb, rec.peak), took)
