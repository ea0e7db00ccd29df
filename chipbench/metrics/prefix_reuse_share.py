"""Share of the prompt tokens admitted in the window whose KV the prefix
store supplied instead of prefill computing it: the engine's
``prefix_saved_tokens`` against that plus ``prefill_tokens``, as counted
between the window's start and end."""


def read(ctx):
    before, after = ctx.counters
    saved = after["prefix_saved_tokens"] - before["prefix_saved_tokens"]
    computed = after["prefill_tokens"] - before["prefill_tokens"]
    if saved + computed <= 0:
        return None
    return 100.0 * saved / (saved + computed)
