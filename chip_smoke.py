"""Bring-up smoke run of the paper-width GAQ force field on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the replica cluster

The model is the paper's (``configs/so3krates_paper.py``: F=64, Fv=16,
3 layers, 16 radial basis functions, 10 A cutoff, 16-bit direction
codebook) with random weights from a fixed seed. It is driven through
the entry points users call: ``QuantizedEngine`` behind a
``MicroBatchScheduler`` in W4A8 and W8A8, ``MDEngine`` in W4A8, and,
with ``--four-chips``, a ``ClusterPool`` of four W4A8 replicas, one per
chip, and no other phase.

Every check raises, so any failed phase exits non-zero. The last line
of standard output is one JSON object naming the device JAX ran on.
The script exits non-zero before any phase when JAX finds no TPU:
interpret mode is for the tests, and a CPU run would prove nothing.
The compile cache lives where ``repro.launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
# rMD17 molecule sizes (ethanol, benzene, toluene, naphthalene, aspirin,
# azobenzene): the 16- and 32-atom buckets, dense under path="auto"
RMD17_SIZES = (9, 12, 15, 18, 21, 24)
# one molecule each for the 64- and 128-atom buckets. At the paper's
# 10 A cutoff a molecule at liquid density is nearly fully connected and
# overflows the default 16-neighbour edge capacity, which sends it dense;
# 0.003 atoms/A^3 keeps ~12 neighbours inside the cutoff, so "auto"
# takes the sparse path with the edge-softmax kernel
LARGE_SIZES = (50, 100)
LARGE_DENSITY = 0.003
MD_ATOMS, MD_REPLICAS, MD_STEPS = 21, 4, 100


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _log(msg: str) -> None:
    print(msg, flush=True)


def _tpu_devices(count: int):
    """The JAX devices, after checking that they are ``count`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX runs on platform "
                 f"{devs[0].platform!r}")
    _require(len(devs) >= count,
             f"{count} TPU devices, found {len(devs)}")
    _log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
         f"count={len(devs)}")
    return devs


def _graphs(seed: int):
    """rMD17-size molecules plus one each for the 64/128 buckets."""
    from repro.serving.bucketing import Graph, random_graph
    rng = np.random.default_rng(seed)
    out = []
    for n in RMD17_SIZES:
        # jittered 1.5 A cubic grid: molecule-like spacing, no overlaps
        grid = np.stack(np.meshgrid(*[np.arange(3)] * 3), -1).reshape(-1, 3)
        coords = grid[:n] * 1.5 + rng.normal(0.0, 0.1, (n, 3))
        out.append(Graph(rng.integers(0, 20, n).astype(np.int32),
                         coords.astype(np.float32)))
    out += [random_graph(rng, n, 20, density=LARGE_DENSITY)
            for n in LARGE_SIZES]
    return out


def _kernels_in(jitted, *args) -> set:
    """Names of the Pallas kernels the lowered program calls as compiled
    TPU custom calls (an interpreted kernel lowers to plain HLO)."""
    import re
    text = jitted.lower(*args).as_text()
    return (set(re.findall(r'kernel_name = "(\w+)"', text))
            if "tpu_custom_call" in text else set())


def _check_compiled_kernels(engine) -> None:
    """Both jitted forwards call the quantized-matmul kernels as TPU
    custom calls, and the sparse one the edge-softmax kernel too."""
    from repro.serving.bucketing import build_edge_list
    cap, bsz = 64, 2                      # sparse under path="auto"
    species = np.zeros((bsz, cap), np.int32)
    coords = np.zeros((bsz, cap, 3), np.float32)
    mask = np.zeros((bsz, cap), bool)
    el = build_edge_list(coords, mask, engine.model_cfg.cutoff,
                         next(b.edges for b in engine.serve.buckets()
                              if b.capacity == cap))
    matmuls = {"_w8a8_kernel"} | ({"_w4a8_kernel"}
                                  if engine.serve.mode == "w4a8" else set())
    dense = _kernels_in(engine._forward_dense, species, coords, mask)
    sparse = _kernels_in(engine._forward_sparse, species, coords, mask,
                         el.senders, el.receivers, el.edge_mask)
    _log(f"  tpu_custom_call kernels: dense={sorted(dense)} "
         f"sparse={sorted(sparse)}")
    _require(matmuls <= dense, f"dense forward calls {sorted(matmuls)}")
    _require(matmuls | {"_edge_softmax_kernel"} <= sparse,
             "sparse forward calls the matmul and edge-softmax kernels")


def _reference(engine, graphs, results):
    """Per-molecule (energy, forces) of the plain reference for the same
    quantized weights: integer-jnp matmuls (``ref_qmatmul``), the
    segment-op edge softmax (``ref.edge_softmax_ref``), and every float
    matmul at highest precision. Each molecule takes the path its served
    result took, padded alone to its bucket: the server batched it with
    others or with padding, so a result that depends on its batch shows
    here."""
    import jax
    from repro.core import make_codebook
    from repro.serving.bucketing import BatchPlan, build_edge_list, pad_graphs
    from repro.serving.forward import (batched_energy_and_forces,
                                       sparse_energy_and_forces)
    cfg, qp = engine.model_cfg, engine.qparams
    codebook = make_codebook(cfg.dir_bits)
    dense = jax.jit(lambda s, c, m: batched_energy_and_forces(
        qp, cfg, s, c, m, codebook, use_kernels=False))
    sparse = jax.jit(lambda s, c, m, snd, rcv, em: sparse_energy_and_forces(
        qp, cfg, s, c, m, snd, rcv, em, codebook, use_kernels=False,
        edge_kernel=False))
    specs = {b.capacity: b for b in engine.serve.buckets()}
    out = []
    with jax.default_matmul_precision("highest"):
        for i, r in enumerate(results):
            spec = specs[r.bucket_capacity]
            s, c, m = pad_graphs(graphs, BatchPlan(spec, 1, (i,)))
            if r.path == "sparse":
                el = build_edge_list(c, m, cfg.cutoff, spec.edges)
                e, f = sparse(s, c, m, el.senders, el.receivers,
                              el.edge_mask)
            else:
                e, f = dense(s, c, m)
            out.append((float(e[0]), np.asarray(f[0, :r.n_atoms])))
    return out


# The served forward runs its float matmuls (radial gemm, fp32 readout,
# the straight-through backward against dequantized weights, the edge
# kernel's one-hot gathers) at the chip's default precision, one bf16
# pass with relative rounding 2^-9 ~ 2e-3; the reference runs them at
# highest precision. Through 3 layers and the force backward that
# compounds to ~1e-2 of the force scale, and a rounding difference can
# also move an A8 activation code by one step (1/127 of its row's max)
# or an MDDQ direction to the neighbouring codeword (~0.01 rad). A wrong
# kernel layout gives errors of order 1, far above this bound.
REF_TOL = 5e-2   # of the molecule's largest |reference| force / energy


def _serve_phase(cfg, mode: str, graphs):
    """W4A8 or W8A8 one-shot serving through the scheduler, checked for
    paths, compiled kernels, finiteness and agreement with the reference.
    Returns the engine."""
    import jax
    from repro.server import MicroBatchScheduler, SchedulerConfig
    from repro.serving import QuantizedEngine, ServeConfig
    engine = QuantizedEngine.from_config(
        cfg, seed=SEED, serve=ServeConfig(mode=mode, path="auto",
                                          bucket_sizes=(16, 32, 64, 128),
                                          max_batch=2))
    _log(f"serve {mode}:")
    _require(not engine.interpret, "kernels are compiled, not interpreted")
    with MicroBatchScheduler(engine, SchedulerConfig(max_batch=2,
                                                     deadline_ms=5.0)) as s:
        _log(f"  warmup {s.warmup_s:.3f} s for "
             f"{len(engine.warmup_report)} programs")
        handles = [s.submit(g) for g in graphs]
        results = [h.result(timeout=600) for h in handles]
    from repro.obs import REGISTRY
    snaps = {p: REGISTRY.counter("mddq_snap_programs_total", mode=mode,
                                 path=p).value
             for p in ("closed_form", "scan")}
    _log(f"  mddq_snap_programs_total {snaps}")
    _require(snaps["scan"] == 0 and snaps["closed_form"] > 0,
             "every served program snaps in closed form")
    stats = engine.stats_snapshot()
    _log(f"  dispatch_stats {stats}; paths "
         f"{[(r.n_atoms, r.path) for r in results]}")
    _require(stats["dense"] > 0 and stats["sparse"] > 0,
             "both dense and sparse batches dispatched")
    _check_compiled_kernels(engine)
    for r in results:
        _require(np.isfinite(r.energy) and np.isfinite(r.forces).all(),
                 f"finite energy and forces ({r.n_atoms} atoms)")
    _log(f"  energies {[round(r.energy, 4) for r in results]}")

    worst_e = worst_f = 0.0
    for r, (e_ref, f_ref) in zip(results, _reference(engine, graphs,
                                                     results)):
        e_scale = max(abs(e_ref), 1e-3)
        f_scale = max(float(np.abs(f_ref).max()), 1e-3)
        worst_e = max(worst_e, abs(r.energy - e_ref) / e_scale)
        worst_f = max(worst_f, float(np.abs(r.forces - f_ref).max())
                      / f_scale)
    _log(f"  vs reference (highest precision): worst relative energy "
         f"deviation {worst_e:.3e}, worst relative force deviation "
         f"{worst_f:.3e} (tolerance {REF_TOL})")
    _require(worst_e <= REF_TOL and worst_f <= REF_TOL,
             f"served results within {REF_TOL} of the reference")
    if mode == "w4a8":
        lee = engine.lee_diagnostic(graphs, jax.random.PRNGKey(SEED),
                                    n_rotations=2)
        mean_f = float(np.mean([np.abs(r.forces).mean() for r in results]))
        _log(f"  LEE (w4a8): mean {lee['lee_mean']:.4e} max "
             f"{lee['lee_max']:.4e} over {lee['n_graphs']} molecules x "
             f"{lee['n_rotations']} rotations; mean |F| component "
             f"{mean_f:.4e}")
        _require(np.isfinite(lee["lee_max"]), "finite LEE")
    return engine


# the shapes of the vectors the forwards snap (leading dims; Fv and 3
# follow): a dense batch (B, cap), a sparse batch (B * cap,), an MD
# replica batch. On the chip the codes of an MXU einsum depended on
# this shape, so each is probed as it comes.
MDDQ_SHAPES = ((8, 16), (2, 64), (128,), (256,), (MD_REPLICAS * MD_ATOMS,))


# random directions beyond MDDQ_SHAPES for the served snap
MDDQ_RANDOM = 10 ** 6


def _mddq_phase(cfg) -> None:
    """Share of MDDQ direction codes that differ from a float64 argmax
    over the same random unit vectors: for the served snap
    (``core.mddq.mddq_encode``, closed form for the Fibonacci codebook)
    at each of ``MDDQ_SHAPES`` and over ``MDDQ_RANDOM`` more directions,
    for ``core.codebook.nearest_code`` (the scan) at each shape, and for
    a default-precision einsum over the shapes (what the snap was before
    it took elementwise scores). A difference counts as a tie when the
    two codewords' float64 scores are within 1e-6."""
    import jax
    import jax.numpy as jnp
    from repro.core import make_codebook, mddq_encode, nearest_code, snap_path
    mcfg = cfg.mddq()
    cb = make_codebook(cfg.dir_bits)
    cb64 = np.asarray(cb, np.float64)
    rng = np.random.default_rng(SEED)
    us = []
    for shape in [lead + (cfg.vec_feat,) for lead in MDDQ_SHAPES] \
            + [(MDDQ_RANDOM,)]:
        v = rng.normal(size=shape + (3,))
        us.append((v / np.linalg.norm(v, axis=-1, keepdims=True))
                  .astype(np.float32))
    flat = np.concatenate([u.reshape(-1, 3) for u in us]).astype(np.float64)
    exact = np.concatenate([np.argmax(flat[i:i + 512] @ cb64.T, axis=1)
                            for i in range(0, len(flat), 512)])
    n_shapes = sum(u.size // 3 for u in us[:-1])

    def beyond_ties(idx, ref):
        diff = idx != ref
        f = flat[:len(ref)]
        gap = np.abs(np.sum(f[diff] * (cb64[idx[diff]] - cb64[ref[diff]]),
                            axis=1))
        return diff.sum(), int((gap > 1e-6).sum())

    served = np.concatenate([
        np.asarray(jax.jit(lambda u_: mddq_encode(u_, mcfg, cb)[0])(
            jnp.asarray(u))).reshape(-1) for u in us])
    scan = np.concatenate([
        np.asarray(jax.jit(nearest_code)(jnp.asarray(u), cb)).reshape(-1)
        for u in us[:-1]])
    default = np.asarray(jax.jit(lambda u_: jax.lax.map(
        lambda x: jnp.argmax(jnp.einsum("d,nd->n", x, cb)), u_,
        batch_size=128))(jnp.asarray(flat[:n_shapes], jnp.float32)))
    for name, idx, what in (
            (f"served snap, {snap_path(mcfg)}", served,
             f"shapes {MDDQ_SHAPES} x ({cfg.vec_feat}, 3) and "
             f"{MDDQ_RANDOM} random"),
            ("nearest_code", scan, f"shapes {MDDQ_SHAPES}"),
            ("default-precision einsum", default, f"shapes {MDDQ_SHAPES}")):
        n_diff, n_beyond = beyond_ties(idx, exact[:len(idx)])
        _log(f"  MDDQ codes differing from the float64 argmax ({name}): "
             f"{n_diff / len(idx):.4%} ({n_diff}; {n_beyond} beyond ties) "
             f"of {len(idx)} over {what}")
    _require(beyond_ties(served, exact)[1] == 0,
             "the served snap finds the nearest codeword everywhere")
    _require(beyond_ties(scan, exact[:n_shapes])[1] == 0,
             "nearest_code snaps to the nearest codeword at every shape")


def _md_phase(engine) -> None:
    """~100 device-resident velocity-Verlet steps of a few replicas of
    one rMD17-size molecule through the W4A8 engine's weights."""
    import jax
    from repro.md import MDConfig, pad_replicas
    md = engine.md_engine(MDConfig(mode="w4a8", dt_fs=0.5, record_every=25))
    rng = np.random.default_rng(SEED + 1)
    grid = np.stack(np.meshgrid(*[np.arange(3)] * 3), -1).reshape(-1, 3)
    coords = grid[:MD_ATOMS] * 1.5 + rng.normal(0.0, 0.1, (MD_ATOMS, 3))
    species = rng.integers(0, 20, MD_ATOMS).astype(np.int32)
    sp, co, mask = pad_replicas(species, coords, MD_REPLICAS)
    masses = np.full(MD_ATOMS, 12.0, np.float32)
    t0 = time.monotonic()
    st = md.init_state(jax.random.PRNGKey(SEED), sp, co, mask, masses,
                       300.0)
    e_kin = 0.5 * np.sum(masses[:, None] * np.asarray(st.veloc) ** 2,
                         axis=(1, 2))
    e_start = np.asarray(st.e_pot) + e_kin
    st, rec = md.run(st, sp, mask, masses, n_steps=MD_STEPS)
    e_tot = np.concatenate([e_start[None], rec["e_tot"]])
    _require(np.isfinite(e_tot).all() and np.isfinite(rec["e_pot"]).all(),
             "finite MD energies")
    drift = np.abs(e_tot - e_tot[0]).max(axis=0)
    _log(f"md w4a8: {MD_REPLICAS} replicas x {MD_ATOMS} atoms, {MD_STEPS} "
         f"steps at 0.5 fs in {time.monotonic() - t0:.3f} s (compile "
         f"included); n_rebuilds {rec['n_rebuilds']}")
    _log(f"  e_tot of replica 0 at start and every 25 steps "
         f"{e_tot[:, 0].tolist()} eV; max |e_tot - e_tot(start)| per "
         f"replica {drift.tolist()} eV")


def one_chip(cfg) -> None:
    graphs = _graphs(SEED)
    w4 = _serve_phase(cfg, "w4a8", graphs)
    _serve_phase(cfg, "w8a8", graphs)
    _mddq_phase(cfg)
    _md_phase(w4)


def four_chips(cfg, devs) -> None:
    """Four W4A8 replicas, one per chip, behind the router; every result
    must equal a single engine's on device 0 (routing identity)."""
    from repro.cluster import ClusterConfig, ClusterPool
    from repro.serving import QuantizedEngine, ServeConfig
    from repro.serving.bucketing import random_graph
    serve = ServeConfig(mode="w4a8", bucket_sizes=(32, 64), max_batch=2)
    rng = np.random.default_rng(SEED)
    graphs = [random_graph(rng, int(n), 20, density=LARGE_DENSITY)
              for n in rng.integers(9, 60, 48)]
    t0 = time.monotonic()
    with ClusterPool.from_config(
            cfg, serve=serve, seed=SEED,
            cluster=ClusterConfig(n_replicas=4, max_batch=2,
                                  deadline_ms=5.0)) as pool:
        st = pool.stats()
        used = [r["device"] for r in st["replicas"]]
        _log(f"cluster: warmup {time.monotonic() - t0:.3f} s; n_live "
             f"{st['n_live']}; replica devices {used}")
        _require(st["n_live"] == 4, "4 live replicas after warmup")
        _require(len(set(used)) == 4
                 and {str(d) for d in devs[:4]} == set(used),
                 "4 distinct TPU devices")
        results = pool.infer(graphs, timeout=600)
    served = collections.Counter(r.replica_id for r in results)
    _log(f"  requests per replica {dict(sorted(served.items()))}")
    _require(set(served) == {0, 1, 2, 3}, "every replica served")
    single = QuantizedEngine.from_config(cfg, serve=serve, seed=SEED,
                                         device=devs[0])
    worst = 0.0
    for r, d in zip(results, single.infer_batch(graphs)):
        worst = max(worst, abs(r.energy - d.energy),
                    float(np.abs(r.forces - d.forces).max()))
    _log(f"  worst |pool - single engine on device 0| over {len(graphs)} "
         f"molecules: {worst:.3e} (bound 1e-6)")
    _require(worst <= 1e-6, "routing identity within 1e-6")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica cluster phase")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    devs = _tpu_devices(count)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import so3krates_paper
    from repro.launch.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    cfg = so3krates_paper.config()
    _log(f"model: {cfg}")
    t0 = time.monotonic()
    if args.four_chips:
        four_chips(cfg, devs)
    else:
        one_chip(cfg)
    _log(f"total {time.monotonic() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
