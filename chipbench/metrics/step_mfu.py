"""The whole model step's share of the chip's bf16 peak: operations of
the tokens that the decode and prefill step programs processed in the
traced part of the window (real rows only; from the configuration's
shapes), over the device time of those programs and of the output head
(``jit_paged_decode_step``, ``jit_paged_prefill_step``,
``jit_head_logits``), over the peak."""
from flops import decode_flops, prefill_flops
from stats import in_window

STEPS = ("jit_paged_decode_step", "jit_paged_prefill_step",
         "jit_head_logits")


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    busy = sum(sum(t["launches"].get(k, [])) for k in STEPS) / 1e9
    if not busy or p is None:
        return None
    ops = sum(decode_flops(ctx.conf, cs) for s, cs in ctx.decodes
              if in_window(s, ctx.trace_window))
    ops += sum(prefill_flops(ctx.conf, c, n) for s, rows in ctx.prefills
               if in_window(s, ctx.trace_window) for c, n in rows)
    return 100.0 * ops / busy / p["bf16_flops"]
