"""Analytic operation and byte counts of the served model, and the peaks.

Counts are of the model's contractions over real atoms and real cutoff
edges only: padding rows, padded contraction widths and the MDDQ codeword
scan are not model work (the scan is a search, and a closed-form snap
would remove it). An energy-and-forces call is the forward plus its
backward, counted as twice the forward. Forward quantized matmuls run
against the int8 peak, every other operation against the bf16 peak, so
the least time a count can take is ``q_ops / int8 + f_ops / bf16``.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: add them with their source")
    return table[device_kind]


def layer_ops(model: dict, n_atoms: int, n_edges: int) -> Dict[str, int]:
    """Multiply-add operations (2 per MAC) of one layer's forward."""
    F, Fv, K = model["feat"], model["vec_feat"], model["n_rbf"]
    n, e = n_atoms, n_edges
    return {
        "trunk": 2 * n * F * (3 * F + 2 * Fv),      # q | k | msg | a | b
        "update": 2 * 2 * n * F * F,                # two-matmul update
        "vnorm": 2 * n * Fv * F,                    # vector-norm feedback
        "radial": 2 * e * K * (1 + F + 2 * Fv),     # bias | gates
        "attention": 2 * e * F,                     # q . k per edge
        "messages": 2 * e * (F + 3 * Fv),           # alpha-weighted values
    }


QUANTIZED = ("trunk", "update", "vnorm")


def forward_ops(model: dict, n_atoms: int, n_edges: int) -> Tuple[int, int]:
    """(quantized, float) operations of one forward over ``n_atoms`` real
    atoms and ``n_edges`` real directed cutoff edges."""
    F, Fv = model["feat"], model["vec_feat"]
    lay = layer_ops(model, n_atoms, n_edges)
    q = model["n_layers"] * sum(lay[k] for k in QUANTIZED)
    f = model["n_layers"] * sum(v for k, v in lay.items()
                                if k not in QUANTIZED)
    q += 2 * n_atoms * (F + Fv) * F                 # readout matmul
    f += 2 * n_atoms * F                            # float energy head
    return q, f


def energy_forces_ops(model: dict, n_atoms: int,
                      n_edges: int) -> Tuple[int, int]:
    """(int8-peak, bf16-peak) operations of forward plus forces."""
    q, f = forward_ops(model, n_atoms, n_edges)
    return q, f + 2 * (q + f)


def least_seconds(q_ops: float, f_ops: float, pk: Dict[str, float]) -> float:
    return q_ops / pk["int8_ops_per_s"] + f_ops / pk["bf16_flops_per_s"]


def qmatmul_launches(model: dict, mode: str, rows: int
                     ) -> List[Tuple[int, int, int, int]]:
    """(M, K, N, weight_bits) of each quantized-matmul kernel launch of one
    forward of the edge-list path over ``rows`` real atoms: per layer the
    trunk (grouped by weight kind), the two update matmuls and the norm
    feedback, then the readout."""
    F, Fv = model["feat"], model["vec_feat"]
    if mode == "w4a8":
        trunk = [(rows, F, 3 * F, 8), (rows, F, 2 * Fv, 4)]
    else:
        trunk = [(rows, F, 3 * F + 2 * Fv, 8)]
    layer = trunk + [(rows, F, F, 8), (rows, F, F, 8), (rows, Fv, F, 8)]
    return layer * model["n_layers"] + [(rows, F + Fv, F, 8)]


def qmatmul_cost(m: int, k: int, n: int, w_bits: int) -> Tuple[int, int]:
    """(operations, bytes) of one launch: int8 activations and their row
    scales in, packed weights and column scales in, float32 out."""
    ops = 2 * m * k * n
    nbytes = m * k + 4 * m + k * n * w_bits // 8 + 4 * n + 4 * m * n
    return ops, nbytes


def edge_softmax_cost(model: dict, n_nodes: int, n_edges: int
                      ) -> Tuple[int, int]:
    """(operations, bytes) of one edge-softmax launch: a (F+1)-wide
    query . key-with-bias dot per edge and the alpha-weighted sum of its
    F + 3 Fv values; queries, keys, receiver indices and values in,
    float32 node sums out."""
    F, Fv = model["feat"], model["vec_feat"]
    w = F + 3 * Fv
    ops = 2 * n_edges * (F + 1) + 2 * n_edges * w
    nbytes = 4 * (n_nodes * (F + 1) + n_edges * (F + 1) + n_edges
                  + n_edges * w + n_nodes * w)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peak_ops: float,
                     pk: Dict[str, float]) -> float:
    """Least time of one launch: the larger of its compute and its
    memory bound."""
    return max(ops / peak_ops, nbytes / pk["hbm_bytes_per_s"])
