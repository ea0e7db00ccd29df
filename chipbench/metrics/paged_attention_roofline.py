"""The paged decode-attention kernel's share of its roofline: the least
time the chip could take for the KV pages, queries and outputs of the
decode steps started in the traced part of the window (the larger of
operations over peak FLOP/s and bytes over peak HBM bandwidth), over the
kernel's device time there (its ``paged_attention`` custom calls inside
``jit_paged_decode_step``). Memory-bound at these shapes."""
from flops import paged_attention_cost
from stats import in_window


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    kernel_s = t["op_self_s"].get("jit_paged_decode_step/paged_attention")
    rows = [c for s, cs in ctx.decodes if in_window(s, ctx.trace_window)
            for c in cs]
    if not kernel_s or not rows or p is None:
        return None
    ops, nbytes = paged_attention_cost(ctx.conf, rows, ctx.block_tokens)
    least = max(ops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
