#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's widest
logit gap and its float8 control's, seed by seed, in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds n1 n2 ... [--out readings.json]

For each seed the cell runs as ``run.py`` runs it (weights and traffic
from the seed, the cell's load, a window of ``--seconds``), its sample of
finished requests is scored against the plain reference, and the same
prompts and served tokens are scored once more with the reference
computed in float8 in the program's place: at each position, the gap of
the token that float8 puts first. A sound program reads low, the control
high; a cell's limit lies between the largest program reading and the
smallest control reading. Each row also gives ``correct`` for the
program and ``control_correct`` for the control, by the decision that
``run.py`` makes (``check.decide``) against the cell's limit: a limit
that holds reads true and false. The benchmark's own runs do not run
this.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import check  # noqa: E402
import run  # noqa: E402


def readings(c, seed: int, seconds: float, t_start: float) -> dict:
    """One seed: serve the cell's window, free the program, and score its
    sample with the reference and with the float8 control."""
    eng, srv, port, setup_s = run.set_up(c, seed, t_start)
    try:
        with tempfile.TemporaryDirectory(prefix="chipbench-") as d:
            ctx = run.serve_window(c, eng, srv, port, seed, seconds, False,
                                   d)
    finally:
        run.stop(srv)
    picked = check.sample(ctx.due, c.load["check"]["sample_tokens"], seed)
    del eng, srv
    gc.collect()
    ref = run._module(run.BENCH / "references" / f"{c.conf['reference']}.py")
    out = check.compare(ref, c.conf, seed, picked,
                        c.mix["max_context_tokens"], control=True)
    limit = c.load["check"]["max_logit_gap"]
    out.update(seed=seed, setup_s=setup_s, due=len(ctx.due),
               finished=sum(r["ok"] for r in ctx.due), limit=limit,
               correct=check.decide(out["max_logit_gap"], limit),
               control_correct=check.decide(out["control_max_logit_gap"],
                                            limit))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    try:
        run.require_chips(c)
    except run.NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 3
    rows = []
    for seed in args.seeds:
        rows.append(readings(c, seed, args.seconds, time.monotonic()))
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
