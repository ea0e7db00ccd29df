"""Serving launcher: the TokenCake engine as a long-running service loop.

Offline-container stand-in for the paper's HTTP frontend (§6.1/§6.2): the
``MCPFrontend`` below exposes the same three entry points the paper's REST
API provides — ``register_graph``, ``call_start``, ``call_finish`` — driven
here by the workload generator instead of network clients. On a real
deployment these map 1:1 onto the OpenAI-compatible endpoint extensions.

Endpoint results are structured (``{"ok": ...}`` dicts, never silent
no-ops): an unknown rid or a wrong-state call is an *external client
error* — it is reported back, logged, and counted in
``frontend_bad_calls`` so a misbehaving tool adapter is visible in the
report instead of silently degrading the schedule.

    PYTHONPATH=src python -m repro.launch.serve --mode tokencake \
        --apps 20 --qps 1.0 [--arch stablelm_3b] [--prefetch]

``--arch NAME`` serves the named config at its published widths through
``JaxBackend`` on the TPU v5e platform model (:func:`build_model_engine`);
without it the engine runs the pure simulation.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.costmodel import PLATFORMS, TPU_V5E
from repro.core.engine import Engine, EngineConfig
from repro.core.request import ReqState
from repro.core.temporal import TemporalConfig
from repro.data.workloads import build_workload
from repro.models import model as M

log = logging.getLogger("repro.serve")

REPO_ROOT = Path(__file__).resolve().parents[3]

# Device memory of a served model: the parameters, the K and V pools, and
# the paged steps' temporaries, which hold about one more copy of both
# pools (the compiler relays out each layer's pool slice around every
# kernel call). A sixteenth of the device's limit stays free for
# activations, logits and migration staging.
HBM_MARGIN = 1 / 16
# The host tier holds every device block twice over, within this bound.
HOST_TIER_BYTES = 8 << 30


def use_compile_cache() -> None:
    """Persistent compile cache for the entry points. JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself when it is set; otherwise the
    cache lives at a fixed, git-ignored directory of the checkout, so the
    next run from the same checkout finds it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def hbm_limit() -> int:
    """Bytes the first device lets one process allocate."""
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if "bytes_limit" not in stats:
        raise RuntimeError(f"{dev} reports no memory limit: a model is "
                           "served only on an accelerator")
    return stats["bytes_limit"]


def _block_bytes(cfg, block_tokens: int) -> int:
    """Unpadded bytes of one block of one K or V pool, all layers."""
    return (cfg.num_layers * block_tokens * cfg.num_kv_heads * cfg.head_dim
            * jnp.dtype(M._dtype(cfg)).itemsize)


def _pool_bytes(cfg, n_blocks: int, block_tokens: int) -> int:
    """Device bytes of one K or V pool of ``n_blocks`` plus the scratch
    block, as the backend lays it out (a TPU pads tiled dimensions)."""
    shape = (cfg.num_layers, n_blocks + 1, block_tokens, cfg.num_kv_heads,
             cfg.head_dim)
    zeros = jax.jit(lambda: jnp.zeros(shape, M._dtype(cfg)))
    return zeros.lower().compile().memory_analysis().output_size_in_bytes


def pool_blocks(cfg, block_tokens: int, limit: int) -> int:
    """Largest device pool whose K and V pools, twice over (resident plus
    the steps' temporaries), fit in ``limit`` after the parameters and
    the margin."""
    params = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves(M.param_specs(cfg)))
    budget = limit * (1 - HBM_MARGIN) - params
    lo, hi = 0, max(int(budget // (4 * _block_bytes(cfg, block_tokens))), 0)
    while lo < hi:                               # bytes grow with blocks
        mid = (lo + hi + 1) // 2
        if 4 * _pool_bytes(cfg, mid, block_tokens) <= budget:
            lo = mid
        else:
            hi = mid - 1
    if lo == 0:
        raise RuntimeError(f"{cfg.name}: {params} parameter bytes leave no "
                           f"room for a KV pool in {limit} device bytes")
    return lo


def build_model_engine(cfg, mode: str = "tokencake", seed: int = 0,
                       **engine_kw) -> Engine:
    """Engine serving ``cfg`` through ``JaxBackend`` (weights drawn from
    ``seed``) on the TPU v5e platform model. The device pool is sized
    from the device's memory limit (:func:`pool_blocks`) and the host
    tier from it (:data:`HOST_TIER_BYTES`); both override ``engine_kw``."""
    from repro.core.backend import JaxBackend

    plat = TPU_V5E
    gpu = pool_blocks(cfg, plat.block_tokens, hbm_limit())
    host = min(2 * gpu,
               HOST_TIER_BYTES // (2 * _block_bytes(cfg, plat.block_tokens)))
    kw = dict(dict(max_running=64), **engine_kw)
    kw.update(gpu_blocks=gpu, host_blocks=host)
    ecfg = EngineConfig.preset(mode, **kw)
    backend = JaxBackend(cfg, ecfg, plat, key=jax.random.PRNGKey(seed))
    return Engine(ecfg, plat, backend=backend)


class MCPFrontend:
    """§6.2 endpoints, object form. The engine drives call_start/call_finish
    internally for simulated tools; external tools would POST here."""

    def __init__(self, engine: Engine):
        self.engine = engine
        self.bad_calls = 0

    def register_graph(self, graph, arrival: float = 0.0,
                       prompts=None) -> str:
        return self.engine.submit_app(graph, arrival, prompts)

    def _bad(self, op: str, rid: str, error: str) -> dict:
        self.bad_calls += 1
        log.warning("%s(%s): %s", op, rid, error)
        return {"ok": False, "op": op, "rid": rid, "error": error}

    def call_start(self, rid: str, estimate: float | None = None) -> dict:
        req = self.engine._find(rid)
        if req is None:
            return self._bad("call_start", rid, "unknown rid")
        if req.state != ReqState.RUNNING:
            return self._bad("call_start", rid,
                             f"bad state {req.state.value!r} "
                             f"(expected 'running')")
        if req.next_fc() is None:
            return self._bad("call_start", rid, "no pending function call")
        if estimate is not None:
            req.next_fc().predict_time = estimate
        self.engine.call_start(req)
        return {"ok": True, "op": "call_start", "rid": rid}

    def call_finish(self, rid: str, elapsed: float | None = None) -> dict:
        req = self.engine._find(rid)
        if req is None:
            return self._bad("call_finish", rid, "unknown rid")
        if req.current_fc is None:
            return self._bad("call_finish", rid, "no call in flight")
        self.engine.call_finish(req)
        return {"ok": True, "op": "call_finish", "rid": rid}

    def states(self, verbose: bool = False) -> dict:
        """rid -> state map; ``verbose`` wraps it with the engine's
        transfer-plane ledger and the frontend's bad-call count."""
        reqs = {}
        for app in self.engine.apps.values():
            for r in app.node_request.values():
                reqs[r.rid] = r.state.value
        if not verbose:
            return reqs
        return {
            "requests": reqs,
            "transfers": self.engine.transfer_report(),
            "frontend_bad_calls": self.bad_calls,
        }

    def report(self) -> dict:
        rep = self.engine.report()
        rep["frontend_bad_calls"] = self.bad_calls
        rep["transfers"] = self.engine.transfer_report()
        return rep


class ClusterFrontend:
    """The same §6.2 surface over a replicated deployment: one router,
    N engines. ``call_start``/``call_finish`` locate the replica that
    owns the rid (the router may have placed any node anywhere), and the
    observability endpoints add the routing plane — placement decisions,
    cross-replica pulls, summary staleness — next to each replica's
    transfer ledger."""

    def __init__(self, router):
        self.router = router
        self.bad_calls = 0

    def register_graph(self, graph, arrival: float = 0.0,
                       prompts=None) -> str:
        return self.router.submit_app(graph, arrival, prompts)

    def _find(self, rid: str):
        for h in self.router.replicas:
            req = h.engine._find(rid)
            if req is not None:
                return h.engine, req
        return None, None

    def call_start(self, rid: str, estimate: float | None = None) -> dict:
        eng, req = self._find(rid)
        if req is None or req.state != ReqState.RUNNING \
                or req.next_fc() is None:
            self.bad_calls += 1
            return {"ok": False, "op": "call_start", "rid": rid,
                    "error": "unknown rid or bad state"}
        if estimate is not None:
            req.next_fc().predict_time = estimate
        eng.call_start(req)
        return {"ok": True, "op": "call_start", "rid": rid}

    def call_finish(self, rid: str, elapsed: float | None = None) -> dict:
        eng, req = self._find(rid)
        if req is None or req.current_fc is None:
            self.bad_calls += 1
            return {"ok": False, "op": "call_finish", "rid": rid,
                    "error": "unknown rid or no call in flight"}
        eng.call_finish(req)
        return {"ok": True, "op": "call_finish", "rid": rid}

    def states(self, verbose: bool = False) -> dict:
        reqs = {}
        for h in self.router.replicas:
            for app in h.engine.apps.values():
                for r in app.node_request.values():
                    reqs[r.rid] = r.state.value
        if not verbose:
            return reqs
        return {
            "requests": reqs,
            "routing": dict(self.router.metrics),
            "replicas": [
                {"index": h.index,
                 "load": h.load(),
                 "clock": h.engine.clock,
                 "summary_age_s": (h.engine.clock
                                   - self.router.summaries[h.index]
                                   .refreshed_at),
                 "transfers": h.engine.transfer_report()}
                for h in self.router.replicas],
            "frontend_bad_calls": self.bad_calls,
        }

    def report(self) -> dict:
        rep = self.router.report()
        rep["frontend_bad_calls"] = self.bad_calls
        rep["transfers"] = [h.engine.transfer_report()
                            for h in self.router.replicas]
        return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="tokencake",
                    choices=["baseline", "vllm_prefix", "agent", "offload",
                             "tokencake", "mooncake", "parrot"])
    ap.add_argument("--app", default="code_writer")
    ap.add_argument("--apps", type=int, default=20)
    ap.add_argument("--qps", type=float, default=1.0)
    ap.add_argument("--blocks", type=int, default=640)
    ap.add_argument("--platform", default="a100_pcie_qwen14b",
                    choices=list(PLATFORMS))
    ap.add_argument("--arch", default=None, metavar="NAME",
                    help="serve this config (e.g. stablelm_3b) at its "
                         "published widths through JaxBackend on the "
                         "TPU v5e platform model; pool sizes are derived "
                         "from device memory (--blocks/--platform unused)")
    ap.add_argument("--prefetch", action="store_true",
                    help="host-tier promotion + workflow-aware KV prefetch")
    ap.add_argument("--sessions", action="store_true",
                    help="multi-turn sessions with TTL-scheduled KV "
                         "pinning (session_id on /generate + "
                         "/v1/session/* endpoints)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="cluster mode: route over N engine replicas")
    ap.add_argument("--route", default="affinity",
                    choices=["affinity", "round_robin"],
                    help="cluster placement policy")
    ap.add_argument("--link", default="rdma_100g",
                    choices=["rdma_100g", "tcp_25g", "none"],
                    help="inter-replica fabric for KV pulls")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve the §6.2 endpoints + /generate over HTTP "
                         "on PORT instead of running a simulated workload "
                         "(see docs/SERVING_API.md)")
    args = ap.parse_args()

    if args.arch is not None and args.replicas > 1:
        ap.error("--arch serves one replica (cluster replicas own no "
                 "device)")
    plat = PLATFORMS[args.platform]
    kw = dict(gpu_blocks=args.blocks, max_running=64)
    if args.prefetch:
        kw.update(host_promotion=True,
                  temporal=TemporalConfig(prefetch=True))
    if args.sessions:
        kw.update(sessions=True)
    use_compile_cache()
    if args.http is not None:
        import asyncio

        from repro.launch.http_server import HttpServer
        kw.update(continuous_batching=True)
        if args.arch is not None:
            srv = HttpServer(port=args.http, engine=build_model_engine(
                get_config(args.arch), **kw))
        else:
            srv = HttpServer(port=args.http, engine_kw=kw)
        log.info("serving on http://%s:%d", srv.host, args.http)
        asyncio.run(srv.serve_forever())
        return
    if args.replicas > 1:
        _serve_cluster(args, plat, kw)
        return
    if args.arch is not None:
        eng = build_model_engine(get_config(args.arch), args.mode, **kw)
    else:
        eng = Engine(EngineConfig.preset(args.mode, **kw), plat)
    front = MCPFrontend(eng)

    for t, g in build_workload(args.app, qps=args.qps, n_apps=args.apps,
                               seed=1):
        if args.arch is not None:    # real compute: cut the traffic
            for n in g.nodes.values():
                n.prompt_len = min(n.prompt_len, 64)
                n.decode_segments = [min(s, 16) for s in n.decode_segments]
        front.register_graph(g, t)

    eng.run(max_time=1e6)
    rep = front.report()
    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        print(f"[{args.mode}] {rep['apps_finished']}/{args.apps} apps, "
              f"avg {rep['avg_latency']:.1f}s p90 {rep['p90_latency']:.1f}s "
              f"offloads {rep['offloads']} "
              f"prefetch {rep['prefetch_hits']}/{rep['prefetch_issued']} "
              f"effective-util {rep['effective_utilization']:.1%}")


def _serve_cluster(args, plat, kw) -> None:
    from repro.cluster import Router
    from repro.core.costmodel import make_link

    pull = args.link != "none"
    if pull:
        kw = dict(kw, remote_pull=True)
    router = Router(
        lambda i: Engine(EngineConfig.preset(args.mode, **kw), plat),
        args.replicas, policy=args.route,
        link=make_link(plat, args.link) if pull else None)
    front = ClusterFrontend(router)
    for t, g in build_workload(args.app, qps=args.qps, n_apps=args.apps,
                               seed=1):
        front.register_graph(g, t)
    router.run(max_time=1e6)
    rep = front.report()
    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        r = rep["routing"]
        print(f"[{args.mode} x{args.replicas} {args.route}] "
              f"{rep['apps_finished']}/{args.apps} apps, "
              f"avg {rep['avg_latency']:.1f}s p90 {rep['p90_latency']:.1f}s "
              f"skew {rep['load_skew']:.2f} "
              f"affinity {r['affinity_hits']}/{r['placements']} "
              f"overrides {r['overrides']} spills {r['spills']} "
              f"pulls {rep['pulls']} ({rep['cross_replica_bytes']} B) "
              f"stale {r['staleness_avg_s']:.1f}s")


if __name__ == "__main__":
    main()
