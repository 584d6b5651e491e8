"""BENCHMARK.json and the files it names: contract shape, names, links,
and seeded traffic. CPU only; no TPU topology is described."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import generator, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME, UNIT = spec.NAME, spec.UNIT


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_entry_keys_and_names():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        for text in (c["why"], c["source"]):
            assert 0 < len(text) <= 200 and not re.search(r"[\n\t]", text)
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(BENCH["paths"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = spec.cell(name)
    assert spec.driver_file(cell["traffic"]["driver"]).is_file()
    for m in cell["per_layer"]:
        assert spec.metric_reader(m["name"]).is_file()
    assert "force_rms" in cell["limits"]
    assert cell["config"]["name"] == cell["workload"]["config"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_what_its_layers_move(name):
    cell = spec.cell(name)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert m["moves"] in e2e, (m["name"], name)


def test_every_config_is_used_and_unreduced():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["reduced"] == []
        model = json.loads((ROOT / c["file"]).read_text())["model"]
        assert (model["feat"], model["vec_feat"], model["n_layers"],
                model["n_rbf"], model["cutoff"], model["dir_bits"]) \
            == (64, 16, 3, 16, 10.0, 16)


def test_request_pool_is_seeded():
    t = spec.cell("serve-rmd17-closed64-w4a8")["traffic"]
    a = generator.request_pool(t["molecules"], t["geometry"], 50,
                               generator.rng(2 ** 40 + 3, 1))
    b = generator.request_pool(t["molecules"], t["geometry"], 50,
                               generator.rng(2 ** 40 + 3, 1))
    c = generator.request_pool(t["molecules"], t["geometry"], 50,
                               generator.rng(2 ** 40 + 4, 1))
    for (na, sa, ca), (nb, sb, cb) in zip(a, b):
        assert na == nb and (sa == sb).all() and (ca == cb).all()
    assert [x[0] for x in a] != [x[0] for x in c]
    sizes = lambda pool: sorted(sp.size for _, sp, _ in pool)  # noqa: E731
    assert sizes(a) == sizes(c)
    assert sorted(set(sizes(a))) == [9, 12, 15, 16, 18, 20, 21, 24]


def test_arrivals_same_gaps_every_seed():
    a = generator.arrival_times(400.0, 5.0, generator.rng(1, 4))
    b = generator.arrival_times(400.0, 5.0, generator.rng(1, 4))
    c = generator.arrival_times(400.0, 5.0, generator.rng(2, 4))
    assert (a == b).all() and len(a) == 2000
    assert not (a == c).all()
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(c, prepend=0)))
    assert abs(np.mean(np.diff(a)) - 1 / 400.0) < 1e-4


def test_md_molecule_is_aspirin_sized():
    t = json.loads((ROOT / "chipbench/traffic/md-aspirin21x32.json")
                   .read_text())
    sp, co = generator.molecule(t["molecule"]["formula"], t["geometry"],
                                generator.rng(7, 1))
    assert sp.size == 21 and sorted(set(sp.tolist())) == [1, 6, 8]
    d = np.linalg.norm(co[:, None] - co[None], axis=-1)
    assert d[~np.eye(21, dtype=bool)].min() > 0.8


def test_run_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert p.stdout.strip() == ""
