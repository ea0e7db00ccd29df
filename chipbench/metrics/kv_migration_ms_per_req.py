"""Milliseconds of KV movement per finished request: the wall time, to
the end of its device work, of each transfer-plane call of the backend
(``copy_out``, ``copy_in``, ``offload_blocks``, ``promote_blocks``,
``copy_blocks``) started in the window, over the requests that finished
in it; 0 where nothing moved."""
from stats import in_window, spans

MOVES = ("backend.copy_out", "backend.copy_in", "backend.offload_blocks",
         "backend.promote_blocks", "backend.copy_blocks")


def read(ctx):
    moved = [e - s for n, s, e in spans(ctx, "backend.") if n in MOVES]
    done = [r for r in ctx.records if r["ok"] and in_window(r["done"],
                                                           ctx.window)]
    if not done:
        return None
    return 1e3 * sum(moved) / len(done)
