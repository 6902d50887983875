"""Whole step: model operations over the chip's peak.

``mfu``: the operations the traced window's tokens needed, over the
window's length times the peak.  Prefill and decode tokens count,
attention at each token's real context, the vocabulary projection only
where a token is sampled (each decode token and each first token).

``mfu.decode``: the operations of the decode calls alone, over the decode
program's summed device time times the peak: the whole decode step's
share of the peak, which bounds what its attention kernel can gain.
"""
import numpy as np

from chipbench import flops, peaks, trace

KERNEL = "paged_decode"  # the decode program is the one running it


def _decode_flops(ctx) -> int:
    b, d = ctx.block, ctx.dims
    total = 0
    for (lens,) in ctx.calls.decode:
        att = np.asarray(lens)
        att = att[att > 0] + 1
        total += len(att) * (b.flops_per_token(d) + b.logits_flops(d))
        total += b.attention_flops(d, int(att.sum()))
    return total


def _extend_flops(ctx) -> int:
    b, d = ctx.block, ctx.dims
    total = 0
    for lens, wphys in ctx.calls.extend:
        start = int(np.asarray(lens)[0])
        n = int(np.count_nonzero(np.asarray(wphys)))
        total += n * b.flops_per_token(d)
        total += b.attention_flops(d, flops.prefill_attended(start, n))
    t0, t1 = ctx.traced
    firsts = sum(1 for r in ctx.records if r["engine_first"] is not None
                 and t0 <= r["engine_first"] < t1)
    return total + firsts * b.logits_flops(d)


def read(ctx, variant):
    peak = peaks.peaks(ctx.device.device_kind)["bf16_flops_per_s"]
    if variant == "decode":
        runs, _ = ctx.trace.programs(KERNEL)
        ops = _decode_flops(ctx)
        return (100.0 * ops / (trace.seconds(runs) * peak)
                if runs and ops else None)
    total = _decode_flops(ctx) + _extend_flops(ctx)
    return 100.0 * total / (ctx.trace.window_s * peak) if total else None
