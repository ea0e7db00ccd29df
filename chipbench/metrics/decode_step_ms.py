"""Device milliseconds per launch of the decode step program
(``jit_paged_decode_step``) in the traced part of the window."""


def read(ctx):
    d = ctx.trace["launches"].get("jit_paged_decode_step")
    return sum(d) / len(d) / 1e6 if d else None
