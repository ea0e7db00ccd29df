"""A cell at a size the CPU can serve: a two-layer dense decoder of the
served family, short agent sessions, a few seconds of window. Shared by
the CPU tests of the harness."""
from __future__ import annotations

import copy
import json
from types import SimpleNamespace

from run import ROOT

CONF = {
    "name": "dense-smoke", "source": "test",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "max_position_embeddings": 4096, "rope_theta": 10000,
    "partial_rotary_factor": 1.0, "rms_norm_eps": 1e-06,
    "attention_bias": True, "torch_dtype": "bfloat16",
    "reference": "dense_decoder",
    "engine": {"mode": "tokencake", "continuous_batching": True,
               "sessions": True, "max_running": 4},
}

MIX = {
    "session_id": True, "shared_prompt_tokens": 64,
    "role_prompt_tokens": 32, "max_context_tokens": 384,
    "first_context": {"mu": 3.5, "sigma": 0.5, "min": 8, "max": 64},
    "output": {"mu": 3.6, "sigma": 0.3, "min": 24, "max": 48},
    "agents": {"coder": {"tools": ["file_read", "search"]},
               "tester": {"tools": ["external_test"]}},
    "tools": {
        "file_read": {"seconds": 0.1, "variability": 0.5,
                      "result_tokens": [16, 48]},
        "search": {"seconds": 0.3, "variability": 1.0,
                   "result_tokens": [16, 32]},
        "external_test": {"seconds": 0.5, "variability": 0.5,
                          "result_tokens": [8, 24]}},
}

LOAD = {"sessions_per_s": 3.0, "ramp_s": 2, "drain_s": 60, "trace_s": 2,
        "check": {"sample_tokens": 400, "max_logit_gap": 0.1}}


def cell(index: int = 0, **load) -> SimpleNamespace:
    """The ``index``-th cell of ``BENCHMARK.json``, with its metric
    entries, at the smoke size."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = spec["workloads"][index]["name"]
    from run import load_cell
    c = load_cell(name, spec)
    c.conf, c.mix = copy.deepcopy(CONF), copy.deepcopy(MIX)
    c.load = dict(copy.deepcopy(LOAD), **load)
    return c
