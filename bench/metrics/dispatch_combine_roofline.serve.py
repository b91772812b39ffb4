"""Dispatch and combine kernels' share of their roofline while serving:
the least time to move the rows they gather and scatter
(``bench/flops.dispatch_combine_work``) over their summed device time.

The kernels (``kernels/dispatch.dispatch_rows`` and ``combine_rows``) have
no name in the trace; both are Mosaic custom calls with operands
(int32 [a, b], float32 [a, b], rows [c, d]) and one result [a, d].
"""
from bench import flops


def is_dispatch_or_combine(op, d: int) -> bool:
    if not op.is_kernel or len(op.operands) != 3 or len(op.results) != 1:
        return False
    (ti, idx), (tw, w), (_, rows) = op.operands
    _, out = op.results[0]
    return (ti == "s32" and tw == "f32" and len(idx) == 2 and idx == w
            and len(rows) == 2 and rows[1] == d and out == (idx[0], d))


def read(rec):
    if rec.trace is None:
        return None
    d = rec.cfg["d_model"]
    took = rec.trace.kernel_seconds(lambda op: is_dispatch_or_combine(op, d))
    fl, nb = flops.dispatch_combine_work(rec.work["kept_rows"],
                                         rec.work["tokens"], rec.cfg)
    return flops.share_pct(flops.roofline_s(fl, nb, rec.peak), took)
