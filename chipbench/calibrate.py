#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n> ... [--control-seeds <n> ...] [--controls a4 bf16]

For each ``--seeds`` seed it runs the cell as a benchmark run does and
records the numbers compared (the lower readings: the program when it is
sound). For each ``--control-seeds`` seed it puts each control (the
reference one precision step down, ``reference.CONTROLS``) in the
program's place over the same inputs and records the same numbers against
the reference (the upper readings). One JSON line per reading goes to
standard output. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run, spec  # noqa: E402
from chipbench.reference import (CONTROLS, Reference,  # noqa: E402
                                 make_params)


def control_numbers(name: str, seed: int, control: str, devices,
                    cell: dict = None) -> dict:
    """The numbers a run compares, with ``control`` in the program's place."""
    cell = cell or spec.cell(name)
    ctx = run.Context(cell, seed, devices[:cell["workload"]["chips"]])
    drv = run._module(spec.driver_file(cell["traffic"]["driver"])).Driver(
        ctx)
    params = make_params(seed, ctx.model, ctx.devices[0])
    drv.control(Reference(params, ctx.model, ctx.mode, CONTROLS[control]),
                cell["traffic"].get("check_segments", 0) + 1)
    return drv.check(Reference(params, ctx.model, ctx.mode), seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="*", default=sorted(CONTROLS))
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        devices = run.find_chips(cell["workload"]["chips"])
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    for seed in args.seeds:
        nums: dict = {}
        out = run.run_cell(args.workload, seed, args.seconds, False, devices,
                           cell, t_start=time.monotonic(), numbers=nums)
        print(json.dumps({"kind": "program", "seed": seed,
                          "correct": out["correct"], "numbers": nums,
                          "metrics": out["metrics"]}), flush=True)
    for seed in args.control_seeds:
        for c in args.controls:
            nums = control_numbers(args.workload, seed, c, devices, cell)
            print(json.dumps({"kind": "control", "control": c, "seed": seed,
                              "numbers": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
