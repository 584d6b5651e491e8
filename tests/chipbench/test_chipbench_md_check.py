"""The MD cell's check on the CPU at a tiny size: a sound run is correct,
each fault the cell can have makes it incorrect, and so does the control.
The harness's look for a chip is skipped: ``run_cell`` is given the CPU."""
import jax
import numpy as np
import pytest

from chipbench import calibrate, compare, run
from tests.chipbench._tiny import tiny_cell

CELL = "md-aspirin21x32-w4a8"
SEED = 2 ** 35 + 17


def _run(monkeypatch=None, fault=None):
    from repro.md.engine import MDEngine
    if fault is not None:
        real = MDEngine.run

        def broken(self, state, *a, **k):
            new, rec = real(self, state, *a, **k)
            return fault(state, new), rec
        monkeypatch.setattr(MDEngine, "run", broken)
    return run.run_cell(CELL, SEED, 1.0, False, jax.devices(),
                        tiny_cell(CELL))


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"md_ns_per_day", "setup_s"}
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] < 1e-4 * c["limit"] or c["value"] < 1e-4


def _unchanged(old, new):
    return old


def _half_left_out(old, new):
    keep = np.arange(old.coords.shape[0]) < old.coords.shape[0] // 2
    pick = lambda a, b: np.where(  # noqa: E731
        keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
    return new._replace(coords=pick(new.coords, old.coords),
                        veloc=pick(new.veloc, old.veloc),
                        forces=pick(new.forces, old.forces),
                        e_pot=pick(new.e_pot, old.e_pot))


def _forces_altered(old, new):
    return new._replace(forces=new.forces * 1.2)


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _forces_altered],
                         ids=["state_unchanged", "half_batch_left_out",
                              "answer_altered"])
def test_fault_is_incorrect(monkeypatch, fault):
    out = _run(monkeypatch, fault)
    assert out["correct"] is False, out["checks"]


def test_control_fails_the_limits():
    cell = tiny_cell(CELL)
    nums = calibrate.control_numbers(CELL, SEED, "a4", jax.devices(), cell)
    ok, rows = compare.judge(nums, cell["limits"])
    assert not ok, rows
