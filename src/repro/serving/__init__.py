"""repro.serving — batched quantized-inference engine.

The deployment layer of the GAQ reproduction: takes variable-size
molecular graphs, buckets and pads them into MXU-aligned (multiple-of-128)
shape classes to bound recompilation, runs the quantized SO3krates forward
pass — dense O(n^2) oracle or sparse O(E) edge-list path with its fused
segment-softmax kernel, selected per batch by ``ServeConfig.path`` —
through the fused W8A8/W4A8 Pallas kernels (compiled on TPU,
interpreted on the CPU backend for the tests), and returns
per-molecule energies and conservative forces with padding masked out of
both results and LEE diagnostics.

Public API:

* :class:`QuantizedEngine` — ``from_config(...)``, ``infer_batch(graphs)``,
  ``warmup(buckets)``, ``lee_diagnostic(...)``, ``memory_report()``
* :class:`ServeConfig` — serving mode (fp32/w8a8/w4a8), bucket ladder,
  max batch
* :class:`Graph` / :class:`MoleculeResult` — input/output records
* :class:`BucketSpec`, :func:`plan_batches`, :func:`pad_graphs` — the
  bucketing layer, usable standalone
* :func:`quantize_so3_params`, :func:`qmatmul` — serve-time weight
  conversion and the kernel-backed matmul with straight-through VJP

See docs/serving.md for the full semantics and docs/architecture.md for
where this layer sits in the module map.
"""
from repro.serving.bucketing import (BatchPlan, BucketSpec, EDGE_LANE,
                                     EdgeList, Graph, MXU_LANE,
                                     assign_bucket, build_edge_list,
                                     count_edges, default_edge_capacity,
                                     device_edge_list, pad_graphs,
                                     plan_batches, random_graph,
                                     random_graphs)
from repro.serving.engine import MoleculeResult, QuantizedEngine, ServeConfig
from repro.serving.forward import (batched_energy, batched_energy_and_forces,
                                   sparse_energy, sparse_energy_and_forces)
from repro.serving.qparams import (QTensor, qmatmul, quantize_so3_params,
                                   ref_qmatmul, serving_bytes)

__all__ = [
    "BatchPlan", "BucketSpec", "EDGE_LANE", "EdgeList", "Graph", "MXU_LANE",
    "assign_bucket", "build_edge_list", "count_edges",
    "default_edge_capacity", "device_edge_list", "pad_graphs",
    "plan_batches", "random_graph", "random_graphs",
    "MoleculeResult", "QuantizedEngine", "ServeConfig",
    "batched_energy", "batched_energy_and_forces",
    "sparse_energy", "sparse_energy_and_forces",
    "QTensor", "qmatmul", "quantize_so3_params", "ref_qmatmul",
    "serving_bytes",
]
