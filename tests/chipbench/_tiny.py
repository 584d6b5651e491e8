"""A cell shrunk to a size the CPU test runs can hold.

Cells that BENCHMARK.json lists come from ``spec.cell``; the MD and
open-loop cells, whose files are kept for a later benchmark PR (PERF.md,
Open questions), are assembled from their files here."""
import copy
import json

from chipbench import spec

TINY = {"feat": 16, "vec_feat": 4, "n_layers": 1, "dir_bits": 8}
UNLISTED = {  # cell -> (configuration, traffic, end-to-end metric)
    "md-aspirin21x32-w4a8": ("so3krates-gaq-w4a8", "md-aspirin21x32",
                             "md_ns_per_day"),
    "serve-rmd17-p95-w8a8": ("so3krates-gaq-w8a8", "rmd17-poisson",
                             "p95_ms"),
}


def _load(rel: str) -> dict:
    return json.loads((spec.HERE / rel).read_text())


def full_cell(name: str) -> dict:
    if name not in UNLISTED:
        return spec.cell(name)
    conf, traffic, metric = UNLISTED[name]
    return {"workload": {"name": name, "config": conf, "traffic": traffic,
                         "chips": 1},
            "config": _load(f"configs/{conf}.json"),
            "traffic": _load(f"traffic/{traffic}.json"),
            "limits": _load(f"workloads/{name}.json")["limits"],
            "end_to_end": [{"name": metric, "unit": "-"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def tiny_cell(name: str) -> dict:
    cell = copy.deepcopy(full_cell(name))
    cell["config"]["model"].update(TINY)
    t = cell["traffic"]
    if t["driver"] == "md":
        t.update(replicas=2, check_segments=2)
    else:
        t["max_batch"] = 8
        if "in_flight" in t:
            t["in_flight"] = 8
        if "rate_per_s" in t:
            t["rate_per_s"] = 40.0
    return cell
