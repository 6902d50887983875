"""Kernels: the paged decode attention kernel's least time (the bytes it
must move: K and V of every attended position in every layer, the query
and the output, over the chip's HBM bandwidth) over its summed device
time in the traced window."""
import numpy as np

from chipbench import peaks

KERNEL = "paged_decode"


def read(ctx, variant):
    secs, runs = ctx.trace.op_seconds(KERNEL)
    if not runs:
        return None
    b, d = ctx.block, ctx.dims
    need = 0
    for (lens,) in ctx.calls.decode:
        att = np.asarray(lens)
        att = att[att > 0] + 1
        need += d["layers"] * (int(att.sum()) * b.kv_bytes_per_position(d)
                               + len(att) * b.decode_attention_bytes(d, 0))
    bw = peaks.peaks(ctx.device.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / secs
