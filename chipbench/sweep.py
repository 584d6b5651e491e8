#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell once, to place its rate.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates <r> ...

One server is set up as a run sets it up; then for each rate the open-loop
window is driven for ``--seconds`` and a line is printed: p50/p95 latency,
answers delivered in the window over those offered, and the mean latency
of the last quarter of arrivals over that of the first (a backlog that
grows through the window reads well above 1). The highest rate whose
backlog does not grow is the knee; the cell runs at four fifths of it.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run, spec, trace_reduce  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if cell["traffic"]["driver"] != "open_loop":
        print("sweep: not an open-loop cell", file=sys.stderr)
        return 2
    try:
        devices = run.find_chips(cell["workload"]["chips"])
    except run.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    ctx = run.Context(cell, args.seed, devices[:cell["workload"]["chips"]])
    drv = run._module(spec.driver_file("open_loop")).Driver(ctx)
    drv.setup()
    off = trace_reduce.Tracer(False, 0.0, "")
    for rate in args.rates:
        drv.t = dict(cell["traffic"], rate_per_s=rate)
        drv.kept = []
        drv.n_flush0 = len(drv._flushes())
        res = drv.window(args.seconds, off)
        lat = np.asarray([h.t_done - h.t_submit for _, h in drv.kept
                          if h.done()])
        q = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate_per_s": rate, "offered": len(drv.kept),
            "delivered_share": len(drv.delivered) / max(len(drv.kept), 1),
            "p50_submit_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": res["p95_ms"],
            "growth": float(lat[-q:].mean() / lat[:q].mean())}),
            flush=True)
    drv.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
