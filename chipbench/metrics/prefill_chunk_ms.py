"""Device milliseconds per launch of the chunked prefill step program
(``jit_paged_prefill_step``) in the traced part of the window."""


def read(ctx):
    d = ctx.trace["launches"].get("jit_paged_prefill_step")
    return sum(d) / len(d) / 1e6 if d else None
