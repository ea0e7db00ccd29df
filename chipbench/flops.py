"""Operations and bytes a dense decoder's work needs, from its shapes.

Counts are of the algorithm, not of what the program pads: a batch's
padded rows and a chunk's padded positions do no useful work and are not
counted. A multiply-add is two operations.
"""
from __future__ import annotations


def _dims(conf: dict):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"]
    dh = d // h
    return (conf["num_hidden_layers"], d, h, hkv, dh,
            conf["intermediate_size"], conf["vocab_size"])


def weight_flops_per_token(conf: dict) -> float:
    """Projections and feed-forward of every layer, for one token."""
    nl, d, h, hkv, dh, f, _ = _dims(conf)
    return 2.0 * nl * (d * (h + 2 * hkv) * dh + h * dh * d + 3 * d * f)


def head_flops(conf: dict) -> float:
    """The output head (unembedding) for one position."""
    _, d, *_, v = _dims(conf)
    return 2.0 * d * v


def attention_flops(conf: dict, context: int) -> float:
    """Scores and weighted values of one query over ``context`` keys,
    every layer."""
    nl, _, h, _, dh, _, _ = _dims(conf)
    return 4.0 * nl * h * dh * context


def decode_flops(conf: dict, contexts) -> float:
    """One decode step: a token per row attending over ``contexts[i]``
    keys (its own included), and the head for every row."""
    return sum(weight_flops_per_token(conf) + head_flops(conf)
               + attention_flops(conf, c) for c in contexts)


def prefill_flops(conf: dict, cached: int, suffix: int) -> float:
    """A suffix of ``suffix`` tokens after ``cached`` resident ones, each
    attending causally; the head for the last position only."""
    attn = sum(attention_flops(conf, cached + i + 1) for i in range(suffix))
    return (suffix * weight_flops_per_token(conf) + attn
            + (head_flops(conf) if suffix else 0.0))


def paged_attention_cost(conf: dict, contexts, block_tokens: int,
                         itemsize: int = 2) -> tuple:
    """(operations, bytes) of the paged decode-attention kernel over a
    batch: every layer reads each row's K and V pages (whole pages, the
    unit the kernel fetches) and its query, and writes its output."""
    nl, _, h, hkv, dh, _, _ = _dims(conf)
    ops = sum(attention_flops(conf, c) for c in contexts)
    pages = sum(-(-c // block_tokens) for c in contexts)
    kv = 2 * pages * block_tokens * hkv * dh * itemsize
    qo = 2 * len(contexts) * h * dh * itemsize
    return ops, float(nl * (kv + qo))
