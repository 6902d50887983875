"""MoE expert layer: the held experts' grouped matmuls' least time (each
reads its whole stack of held-expert weights, ``expert_weight_bytes`` of
the cell's kind, at the chip's HBM bandwidth) over their device time, in
the program runs that lie wholly inside the traced window.

The compiler copies each stack from HBM into on-chip memory in an
operation of its own (a dynamic-slice fusion over the layer axis) just
before the grouped matmul (``gmm``) that reads it, so the weights' bytes
move in that copy: the device time is the ``gmm`` operations' and that of
the operation that produces each one's weight operand, found by name in
the same program run.  Compute never bounds a call here: that would take
more than n_held x peak FLOP/s / HBM bytes/s (about 1,900 at 8 held
experts on a v5e) routed pairs in one call, and the largest chunk routes
about 512 x 6 x 8 / 64 = 384."""
import re

from chipbench import peaks, trace

KERNEL = "gmm"
# an operand of a grouped matmul's custom call: its shape, then its name
_OPERAND = re.compile(r"bf16\[(\d+),\d+,\d+\]\{[^}]*\} (%[\w.\-]+)")


def _weight_operand(event_name: str, n_held: int):
    """The name of a ``gmm`` call's weight stack operand: its 3-D bf16
    operand whose leading axis is the held experts."""
    args = event_name.split("custom-call(", 1)[-1]
    args = args.split("custom_call_target", 1)[0]
    names = [n for e, n in _OPERAND.findall(args) if int(e) == n_held]
    return names[-1] if names else None


def read(ctx, variant):
    if not hasattr(ctx.block, "expert_weight_bytes"):
        return None
    tr, d = ctx.trace, ctx.dims
    dev = tr.planes[0]
    ops = sorted((e for e in tr.ops if e["plane"] == dev),
                 key=lambda e: e["start_ns"])
    secs, calls = 0.0, 0
    for m in tr.modules:
        end = m["start_ns"] + m["dur_ns"]
        if m["plane"] != dev or m["start_ns"] <= tr.t0 or end > tr.t1:
            continue
        run = [e for e in ops if m["start_ns"] <= e["start_ns"] < end]
        gmms = [e for e in run if KERNEL in trace.op_name(e["name"])]
        if not gmms:
            continue
        wanted = {_weight_operand(e["name"], d["n_held"]) for e in gmms}
        feeds = [e for e in run if trace.op_name(e["name"]) in wanted]
        secs += trace.seconds(gmms) + trace.seconds(feeds)
        calls += len(gmms)
    if not calls:
        return None
    bw = peaks.peaks(ctx.device.device_kind)["hbm_bytes_per_s"]
    need = calls * ctx.block.expert_weight_bytes(d) / bw
    return 100.0 * need / secs
