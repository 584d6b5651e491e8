#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Steps, in order: find the chips (exit 2, naming the platform, when JAX
finds no TPU or too few); keep JAX's compilation cache in
``<checkout>/.jax_cache``; make the weights on the device from the seed;
warm the cell's own shapes; measure for ``--seconds``; read the device's
peak memory, free the program's state and compare what the window
produced with the reference; print one JSON line last on standard output.
With ``--trace 1`` the profiler records the last seconds of the window and
the line carries the cell's per-layer metrics instead of its end-to-end
ones.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import compare, spec, trace_reduce  # noqa: E402

TRACE_S = 3.0   # seconds of the window the profiler records
# substrings of the kernels' device op names in a TPU trace
KERNELS = ("w8a8_matmul", "w4a8_matmul", "_edge_softmax_kernel")


class NoChip(RuntimeError):
    pass


def find_chips(chips: int):
    """The JAX devices, after checking they are at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX runs on platform "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def enable_compile_cache(root: Path = ROOT) -> Path:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, for every program however fast."""
    import jax
    path = root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _module(path: Path):
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Context:
    """What a driver is given: the configuration, traffic, seed, devices."""

    def __init__(self, cell: dict, seed: int, devices):
        self.model = cell["config"]["model"]
        self.mode = cell["config"]["mode"]
        self.traffic = cell["traffic"]
        self.seed = seed
        self.devices = devices

    def log(self, msg: str) -> None:
        print(f"chipbench: {msg}", file=sys.stderr, flush=True)

    def model_cfg(self):
        from repro.models.so3krates import So3kratesConfig
        return So3kratesConfig(**self.model)


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _finite(x: float):
    return x if x == x and abs(x) != float("inf") else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             cell: dict = None, t_start: float = T_START,
             numbers: dict = None) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``cell`` replaces what ``spec.cell(name)`` would load; ``numbers``,
    when given, receives every number the check computed, judged or
    not."""
    import jax
    from chipbench.reference import Reference, make_params
    cell = cell or spec.cell(name)
    chips = cell["workload"]["chips"]
    used = list(devices[:chips])
    ctx = Context(cell, seed, used)
    drv = _module(spec.driver_file(cell["traffic"]["driver"])).Driver(ctx)
    log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    tracer = trace_reduce.Tracer(trace, TRACE_S, log_dir, KERNELS)
    metrics: dict = {}
    try:
        drv.setup()
        setup_s = time.monotonic() - t_start
        ctx.log(f"setup_s {setup_s:.3f}")
        e2e = drv.window(seconds, tracer)
        summary = tracer.stop()
        peak = _peak_bytes(used)
        counts = drv.collect()
        gc.collect()
        if trace:
            from chipbench import ops
            obs = {"trace": summary, "chips": chips,
                   "window_s": getattr(drv, "window_s", seconds)}
            obs.update(drv.observations(ops.peaks(used[0].device_kind)))
            for m in cell["per_layer"]:
                v = _module(spec.metric_reader(m["name"])).read(obs)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            e2e["setup_s"] = setup_s
            for m in cell["end_to_end"]:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        params = make_params(seed, ctx.model, used[0])
        ref = Reference(params, ctx.model, ctx.mode)
        found = drv.check(ref, seed)
        ctx.log(f"numbers {found}")
        if numbers is not None:
            numbers.update(found)
        ok, rows = compare.judge(found, cell["limits"])
        correct = ok and counts["failed"] == 0
    except Exception:
        traceback.print_exc()
        summary, peak, counts = None, _peak_bytes(used), {}
        correct, rows = False, []
    finally:
        if tracer.active:
            jax.profiler.stop_trace()
        shutil.rmtree(log_dir, ignore_errors=True)
    d0 = used[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct),
           "attempted": int(counts.get("attempted", 0)),
           "failed": int(counts.get("failed", 0)),
           "metrics": metrics, "device": device}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {k: {"value": _finite(v), "limit": _finite(lim)}
                     for k, v, lim in rows}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devices = find_chips(cell["workload"]["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    try:
        import repro.md  # noqa: F401  the program under test
        import repro.server  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 3
    enable_compile_cache()
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices, cell)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
