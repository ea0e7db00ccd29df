#!/usr/bin/env python3
"""Knee sweep: one set-up, the cell's traffic at several rates in turn.

    python3 chipbench/knee.py --workload <cell> --seed <n> --seconds <s> \
        --rates r1 r2 ... [--out results.json]

For each rate (sessions started per second) the cell's schedule is played
through its ramp, a window of ``--seconds`` and its drain, on the same
server. Each line reports the requests due in the window, those
finished, the backlog (requests due but unanswered) when the window
opened and when it closed, the time-to-first-token median and 90th
percentile, and the output tokens per second. The knee is the highest
rate whose backlog does not grow through the window; a cell's fixed rate
is set below it once, from one such sweep, and written into its mix's
``load``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import run  # noqa: E402
from stats import percentile, ttfts  # noqa: E402


def backlog(records: list, t: float) -> int:
    return sum(1 for r in records if r["due"] <= t
               and (r["done"] is None or r["done"] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    try:
        run.require_chips(c)
    except run.NoChip as e:
        print(f"knee: {e}; nothing was run", file=sys.stderr)
        return 3
    eng, srv, port, setup_s = run.set_up(c, args.seed, T_START)
    rows = []
    try:
        for i, rate in enumerate(args.rates):
            with tempfile.TemporaryDirectory(prefix="chipbench-") as d:
                ctx = run.serve_window(c, eng, srv, port, args.seed + i,
                                       args.seconds, False, d, rate=rate)
            ws, we = ctx.window
            t = ttfts(ctx)
            row = {"sessions_per_s": rate, "due": len(ctx.due),
                   "finished": sum(r["ok"] for r in ctx.due),
                   "backlog_open": backlog(ctx.records, ws),
                   "backlog_close": backlog(ctx.records, we),
                   "ttft_p50_s": percentile(t, 50),
                   "ttft_p90_s": percentile(t, 90),
                   "output_tok_s": sum(
                       k for r in ctx.records for tt, k in r["chunks"]
                       if ws <= tt < we) / args.seconds}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        run.stop(srv)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "setup_s": setup_s,
                       "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
