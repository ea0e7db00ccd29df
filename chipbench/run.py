#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<mix>.json``, which holds the load too); its traced
span and the limit of its check are in ``chipbench/cells/<cell>.json``.
Each metric is read by ``chipbench/metrics/<metric>.py``.

One process holds the chip. It builds the served engine from the
configuration with weights drawn from ``--seed``, starts the HTTP front
door, warms every shape the cell's traffic can reach (the set-up,
``setup_s``), and hands the schedule, made from the seed, to a load
generator in a child process that never imports JAX. Sessions start
during a ramp before the window; requests due inside the window of
``--seconds`` are the ones measured, and are followed through a bounded
drain. With ``--trace 1`` the run also wraps the program's layers in
host spans, profiles the middle of the window, and prints the per-layer
metrics instead of the end-to-end ones.

After the window, a sample of the finished requests is compared with the
configuration's plain reference (``check.py``), once the program's device
state has been freed. The compared number and its limit are the last
lines on standard error and the ``compared`` key of the result line.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before any work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import check  # noqa: E402
import generator  # noqa: E402

# the monotonic times at which JAX compiled a program (compiles_in_window)
COMPILES: list = []


class NoChip(RuntimeError):
    pass


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, spec: dict | None = None) -> SimpleNamespace:
    """A cell's entry, configuration, mix, load and metric entries, found
    by the names in ``BENCHMARK.json``."""
    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    mix = generator.load_mix(cell["traffic"])
    return SimpleNamespace(
        name=name, cell=cell,
        conf=json.loads((ROOT / entry["file"]).read_text()),
        mix=mix,
        load={**mix["load"], **json.loads(
            (BENCH / "cells" / f"{name}.json").read_text())},
        end_to_end=e2e, per_layer=per_layer)


def _sleep_until(t: float) -> None:
    time.sleep(max(t - time.monotonic(), 0.0))


def _counters(eng) -> dict:
    return {**eng.metrics, **eng.session_metrics}


def _prompts(schedule: list, records: list) -> None:
    """Give every record the prompt it was sent: the turns' added tokens
    and the tokens served before it, in order."""
    by_sid = {s["sid"]: s for s in schedule}
    served = {}
    for r in sorted(records, key=lambda r: (r["sid"], r["turn"])):
        turns = by_sid[r["sid"]]["turns"]
        prompt = []
        for k in range(r["turn"]):
            prompt += turns[k]["add"] + served[(r["sid"], k)]
        r["prompt"] = prompt + turns[r["turn"]]["add"]
        served[(r["sid"], r["turn"])] = r["tokens"]
        r["ok"] = (r["status"] == 200 and r["done"] is not None
                   and len(r["tokens"]) == r["max_tokens"])


def set_up(c: SimpleNamespace, seed: int, t_start: float):
    """The served engine and its server, every shape of the cell warm.
    Returns (engine, server, port, setup seconds since ``t_start``)."""
    import jax

    import harness
    from repro.launch.serve import use_compile_cache

    use_compile_cache()
    # the block-copy kernels compile in well under a second; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    eng, srv = harness.build(c.conf, seed)
    least, most, least_new = generator.context_bounds(c.mix)
    harness.warm(eng, least, most, least_new,
                 generator.blocks_moved(c.mix, eng.backend.block_tokens))
    port = srv.start_background()
    return eng, srv, port, time.monotonic() - t_start


def serve_window(c: SimpleNamespace, eng, srv, port: int, seed: int,
                 seconds: float, trace: bool, workdir: str,
                 rate: float | None = None) -> SimpleNamespace:
    """Play the cell's schedule (sessions started at ``rate``, by default
    the cell's) through a ramp, the window and the drain, and return
    what the metrics read."""
    import jax

    import harness

    load = c.load
    ramp, drain = load["ramp_s"], load["drain_s"]
    schedule = generator.make_schedule(
        c.mix, rate or load["sessions_per_s"], ramp + seconds,
        c.conf["vocab_size"], seed)
    t0 = time.monotonic() + 1.0
    ws, we = t0 + ramp, t0 + ramp + seconds
    plan = {"port": port, "t0": t0, "window": [ramp, ramp + seconds],
            "drain_s": drain, "sessions": schedule}
    plan_path = os.path.join(workdir, "plan.json")
    rec_path = os.path.join(workdir, "records.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    probes = harness.Probes() if trace else None
    if probes:
        probes.install(eng, srv)
    child = subprocess.Popen([sys.executable, str(BENCH / "loadgen.py"),
                              plan_path, rec_path])
    trace_dir = os.path.join(workdir, "trace")
    reduced, tr = None, None
    try:
        _sleep_until(ws)
        before = _counters(eng)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            _sleep_until(ws + (seconds - load["trace_s"]) / 2)
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tr0 = time.monotonic()
            _sleep_until(tr0 + load["trace_s"])
            # the window ends when tracing stops, not when the trace has
            # been written out
            tr = (tr0, time.monotonic())
            jax.profiler.stop_trace()
        _sleep_until(we)
        after = _counters(eng)
        rc = child.wait(timeout=max(we + drain + 60 - time.monotonic(), 1))
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        raise RuntimeError(f"load generator exited {rc}")
    if trace:
        from devtrace import load as load_trace, reduce
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        reduced = reduce(load_trace(path), (tr[1] - tr[0]) * 1e9)
    with open(rec_path) as f:
        records = json.load(f)
    _prompts(schedule, records)
    return SimpleNamespace(
        seconds=seconds, window=(ws, we), records=records,
        due=[r for r in records if ws <= r["due"] < we],
        counters=(before, after), compiles=list(COMPILES),
        spans=probes.spans if probes else [],
        decodes=probes.decodes if probes else [],
        prefills=probes.prefills if probes else [],
        trace=reduced, trace_window=tr, conf=c.conf,
        block_tokens=eng.backend.block_tokens)


def stop(srv, timeout: float = 300.0) -> None:
    """Stop the server and wait until its thread, which owns the engine,
    has ended. Requests still in flight after the drain have no client
    left; their handlers would wait for tokens forever and keep the
    server from closing, so they are ended first the way the server ends
    them when its engine fails (an error chunk). An engine step in
    flight finishes first."""
    def end_streams():
        for q in list(srv._streams.values()):
            q.put_nowait(("error", None))
    srv._loop.call_soon_threadsafe(end_streams)
    srv.stop()
    srv._thread.join(timeout)
    if srv._thread.is_alive():
        raise RuntimeError("the server's thread did not end")


def require_chips(c: SimpleNamespace) -> None:
    """Raise ``NoChip`` unless JAX has as many TPU chips as the cell asks
    for."""
    import jax

    n, chips = len(jax.devices()), c.cell["chips"]
    if jax.default_backend() != "tpu" or n < chips:
        raise NoChip(f"cell {c.name} needs {chips} TPU chip(s); JAX has "
                     f"{n} {jax.default_backend()} device(s)")


def run(c: SimpleNamespace, seed: int, seconds: float, trace: bool,
        t_start: float, chips: int | None = None) -> dict:
    """One run of cell ``c``; returns the result line as a dict. With
    ``chips`` unset (the CPU tests) the look for the chip is skipped."""
    import jax

    from peaks import peak

    if chips is not None:
        require_chips(c)
    devices = jax.devices()
    kind = devices[0].device_kind
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_kw: COMPILES.append(time.monotonic())
        if event == "/jax/core/compile/backend_compile_duration" else None)
    eng, srv, port, setup_s = set_up(c, seed, t_start)
    with tempfile.TemporaryDirectory(prefix="chipbench-") as workdir:
        ctx = serve_window(c, eng, srv, port, seed, seconds, trace, workdir)
    ctx.setup_s = setup_s
    ctx.peak_bytes = (devices[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    ctx.peaks = peak(kind) if chips is not None else None
    picked = check.sample(ctx.due, c.load["check"]["sample_tokens"], seed)
    # the reference runs with the program's device state freed
    stop(srv)
    del eng, srv
    gc.collect()
    print(f"chipbench: device bytes in use once the program's state is "
          f"freed: {(devices[0].memory_stats() or {}).get('bytes_in_use')}",
          file=sys.stderr)
    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        value = _module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    ref = _module(BENCH / "references" / f"{c.conf['reference']}.py")
    res = check.compare(ref, c.conf, seed, picked,
                        c.mix["max_context_tokens"])
    limit = c.load["check"]["max_logit_gap"]
    gap = res["max_logit_gap"]
    ok = [r for r in ctx.due if r["ok"]]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": ctx.peak_bytes}
    out = {"correct": check.decide(gap, limit),
           "attempted": len(ctx.due), "failed": len(ctx.due) - len(ok),
           "metrics": metrics, "device": device}
    if trace:
        t = ctx.trace
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                               key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": top(t["op_self_s"]),
                            "idle_gaps": top(t["idle_by_host_s"])}
    out["compared"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    print(f"chipbench: compared {res['requests']} requests, "
          f"{res['served_tokens']} served tokens", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = load_cell(args.workload)
        out = run(c, args.seed, args.seconds, bool(args.trace), T_START,
                  chips=c.cell["chips"])
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 3
    except Exception:  # noqa: BLE001 — any failure: no result line
        traceback.print_exc()
        return 1
    for name, v in out["compared"].items():
        print(f"compared: {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
