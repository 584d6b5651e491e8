"""The serving cells' check on the CPU at a tiny size: the reference agrees
with the served forward, a sound run is correct, and a broken answer, half
a batch left out, or the control make it incorrect."""
import dataclasses

import jax
import numpy as np
import pytest

from chipbench import calibrate, compare, generator, run
from chipbench.reference import Reference, make_params
from tests.chipbench._tiny import tiny_cell

SEED = 2 ** 36 + 5


def test_reference_matches_served_forward():
    from repro.models.so3krates import So3kratesConfig
    from repro.serving import QuantizedEngine, ServeConfig
    from repro.serving.bucketing import Graph
    cell = tiny_cell("serve-rmd17-closed64-w4a8")
    model, t = cell["config"]["model"], cell["traffic"]
    params = make_params(SEED, model)
    pool = generator.request_pool(t["molecules"], t["geometry"], 10,
                                  generator.rng(SEED, 1))
    for mode in ("w4a8", "w8a8"):
        eng = QuantizedEngine(So3kratesConfig(**model), params,
                              ServeConfig(mode=mode, bucket_sizes=(16, 32),
                                          max_batch=8))
        res = eng.infer_batch([Graph(sp, co) for _, sp, co in pool])
        e, f = compare.reference_answers([p[1:] for p in pool],
                                         Reference(params, model, mode))
        for r, ei, fi in zip(res, e, f):
            assert abs(r.energy - ei) <= 1e-5 * max(abs(ei), 1e-3)
            assert np.abs(r.forces - fi).max() <= 1e-5 * np.abs(fi).max()


def _run(name, monkeypatch=None, fault=None):
    from repro.serving.engine import QuantizedEngine
    if fault is not None:
        real = QuantizedEngine.infer_batch

        def broken(self, graphs, *a, **k):
            return fault(real(self, graphs, *a, **k))
        monkeypatch.setattr(QuantizedEngine, "infer_batch", broken)
    return run.run_cell(name, SEED, 1.0, False, jax.devices(),
                        tiny_cell(name))


@pytest.mark.parametrize("name", ["serve-rmd17-closed64-w4a8",
                                  "serve-rmd17-p95-w8a8"])
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2


def _half_left_out(results):
    half = len(results) // 2
    return [dataclasses.replace(r, energy=0.0, forces=r.forces * 0)
            if i < half else r for i, r in enumerate(results)]


def _answer_altered(results):
    return [dataclasses.replace(r, energy=r.energy * 1.1,
                                forces=r.forces * 1.1) for r in results]


@pytest.mark.parametrize("fault", [_half_left_out, _answer_altered],
                         ids=["half_batch_left_out", "answer_altered"])
def test_fault_is_incorrect(monkeypatch, fault):
    out = _run("serve-rmd17-closed64-w4a8", monkeypatch, fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("name", ["serve-rmd17-closed64-w4a8",
                                  "serve-rmd17-p95-w8a8"])
def test_control_fails_the_limits(name):
    cell = tiny_cell(name)
    nums = calibrate.control_numbers(name, SEED, "a4", jax.devices(), cell)
    ok, rows = compare.judge(nums, cell["limits"])
    assert not ok, rows
