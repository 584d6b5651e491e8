"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the reference gives for the same inputs.

Each gap is taken by the worst case:

* ``force_gap``: per molecule (or MD replica), the largest absolute
  difference of a force component over the largest absolute reference
  component; the worst molecule.
* ``energy_gap``: the largest absolute energy difference over the median
  absolute reference energy of the sample (one molecule's energy near zero
  does not blow it up).
* ``force_rms`` and ``energy_rms``: the root-mean-square difference over
  every atom (every molecule) of the sample over the root-mean-square
  reference value: steady from seed to seed where the worst case swings
  with a single flipped code.
* ``traj_gap`` (MD): per replica, the largest coordinate difference after
  one segment, between the program's next kept state and the reference's
  own Verlet segment from the same kept state, over the largest
  displacement the reference made in that segment; the worst replica.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

BLOCK = 16          # molecules per reference call


def real_edges(coords: np.ndarray, mask: np.ndarray, cutoff: float) -> int:
    """Directed pairs closer than ``cutoff`` between real atoms."""
    d = np.linalg.norm(coords[:, :, None] - coords[:, None, :], axis=-1)
    n = coords.shape[1]
    adj = (d < cutoff) & ~np.eye(n, dtype=bool) & mask[:, :, None] \
        & mask[:, None, :]
    return int(adj.sum())


def _force_gap(f: np.ndarray, f_ref: np.ndarray) -> float:
    return float(np.abs(f - f_ref).max() / max(np.abs(f_ref).max(), 1e-12))


def reference_answers(molecules: Sequence, ref):
    """(energies, forces) of ``ref`` for (species, coords) molecules,
    evaluated in padded blocks of ``BLOCK``."""
    n_max = max(sp.size for sp, _ in molecules)
    e_ref: List[float] = []
    f_ref: List[np.ndarray] = []
    for lo in range(0, len(molecules), BLOCK):
        blk = molecules[lo:lo + BLOCK]
        sp = np.zeros((BLOCK, n_max), np.int32)
        co = np.zeros((BLOCK, n_max, 3), np.float32)
        m = np.zeros((BLOCK, n_max), bool)
        for i, (s, c) in enumerate(blk):
            sp[i, :s.size], co[i, :s.size], m[i, :s.size] = s, c, True
        e, f = ref.energy_forces(sp, co, m)
        for i, (s, _) in enumerate(blk):
            e_ref.append(float(e[i]))
            f_ref.append(f[i, :s.size])
    return e_ref, f_ref


def _rms_ratio(diff, ref) -> float:
    d = np.concatenate([np.ravel(x) for x in diff])
    r = np.concatenate([np.ravel(x) for x in ref])
    return float(np.sqrt(np.mean(d * d)) / max(np.sqrt(np.mean(r * r)),
                                               1e-12))


def serve_numbers(answers: Sequence, molecules: Sequence, ref) -> Dict:
    """Gaps of served (energy, forces) answers against the reference for
    their molecules."""
    e_ref, f_ref = reference_answers(molecules, ref)
    e_scale = max(float(np.median(np.abs(e_ref))), 1e-12)
    e_diff = [a[0] - er for a, er in zip(answers, e_ref)]
    return {
        "force_gap": max(_force_gap(a[1], fr)
                         for a, fr in zip(answers, f_ref)),
        "energy_gap": max(abs(d) for d in e_diff) / e_scale,
        "force_rms": _rms_ratio([a[1] - fr for a, fr in zip(answers, f_ref)],
                                f_ref),
        "energy_rms": _rms_ratio(e_diff, e_ref),
    }


def md_numbers(states: Sequence[dict], ks: Sequence[int], ref, species,
               mask, masses, dt_fs: float, seg_steps: int) -> Dict:
    """Gaps of kept MD states ``ks`` (forces and energy at their
    coordinates) and of the segments that leave them."""
    f_gap = traj_gap = 0.0
    e_diff, e_abs, f_diff, f_ref = [], [], [], []
    for k in ks:
        s, nxt = states[k], states[k + 1]
        e, f = ref.energy_forces(species, s["coords"], mask)
        for b in range(mask.shape[0]):
            n = mask[b]
            f_gap = max(f_gap, _force_gap(s["forces"][b, n], f[b, n]))
            f_diff.append(s["forces"][b, n] - f[b, n])
            f_ref.append(f[b, n])
        e_diff.append(np.abs(s["e_pot"] - e))
        e_abs.append(np.abs(e))
        r, _, _, _ = ref.verlet(species, s["coords"], s["veloc"], f, mask,
                                masses, dt_fs, seg_steps)
        for b in range(mask.shape[0]):
            n = mask[b]
            moved = np.abs(r[b, n] - s["coords"][b, n]).max()
            miss = np.abs(nxt["coords"][b, n] - r[b, n]).max()
            traj_gap = max(traj_gap, float(miss / max(moved, 1e-12)))
    e_scale = max(float(np.median(np.concatenate(e_abs))), 1e-12)
    return {"force_gap": f_gap,
            "energy_gap": float(np.concatenate(e_diff).max()) / e_scale,
            "force_rms": _rms_ratio(f_diff, f_ref),
            "energy_rms": _rms_ratio(e_diff, e_abs),
            "traj_gap": traj_gap}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]) over the numbers that have a
    limit: each at or under it; a limit without a number fails."""
    rows = [(k, float(numbers.get(k, float("nan"))), float(lim))
            for k, lim in sorted(limits.items())]
    ok = all(np.isfinite(v) and np.isfinite(lim) and v <= lim
             for _, v, lim in rows)
    return ok, rows
