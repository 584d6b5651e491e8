"""Traffic from a seed: molecules, request pools, arrival gaps.

One general generator for every traffic file under ``traffic/``. A seed
changes the order of the work and the molecules' exact coordinates, never
the amount: every seed gets the same multiset of molecule sizes and the
same set of arrival gaps, so runs with different seeds measure the same
work.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

# atomic numbers (the species codes the model embeds) and masses in amu
ELEMENTS = {"H": (1, 1.008), "C": (6, 12.011), "N": (7, 14.007),
            "O": (8, 15.999)}

Molecule = Tuple[str, np.ndarray, np.ndarray]   # name, species, coords


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use (``stream``) of ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([stream, int(seed)]))


def species_of(formula: Dict[str, int]) -> np.ndarray:
    return np.asarray([ELEMENTS[el][0] for el in sorted(formula)
                       for _ in range(formula[el])], np.int32)


def masses_of(species: np.ndarray) -> np.ndarray:
    by_z = {z: m for z, m in ELEMENTS.values()}
    return np.asarray([by_z[int(z)] for z in species], np.float32)


def molecule(formula: Dict[str, int], geometry: dict,
             r: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """A synthetic molecule of ``formula``: atoms on a cubic grid of
    ``spacing_A`` with Gaussian jitter ``jitter_A`` (molecule-like
    spacing, no overlaps), elements in a random order."""
    sp = species_of(formula)
    r.shuffle(sp)
    n = sp.size
    side = int(np.ceil(n ** (1.0 / 3.0) - 1e-9))
    grid = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"),
                    -1).reshape(-1, 3)[:n]
    coords = grid * geometry["spacing_A"] \
        + r.normal(0.0, geometry["jitter_A"], (n, 3))
    return sp, coords.astype(np.float32)


def request_pool(molecules: Sequence[dict], geometry: dict, count: int,
                 r: np.random.Generator) -> List[Molecule]:
    """``count`` requests, each molecule of the list equally often, in a
    random order; ``count`` must be a multiple of the list's length."""
    if count % len(molecules):
        raise ValueError(f"pool of {count} is not a multiple of "
                         f"{len(molecules)} molecules")
    order = r.permutation(np.repeat(np.arange(len(molecules)),
                                    count // len(molecules)))
    out = []
    for i in order:
        sp, co = molecule(molecules[i]["formula"], geometry, r)
        out.append((molecules[i]["name"], sp, co))
    return out


def arrival_times(rate: float, seconds: float,
                  r: np.random.Generator) -> np.ndarray:
    """Open-loop arrivals at ``rate`` per second over ``seconds``: the
    ``n = rate * seconds`` exponential quantiles as gaps, in a random
    order, so every seed offers the same gaps and count."""
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(r.permutation(gaps))
