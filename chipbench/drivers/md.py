"""Device-resident NVE through ``MDEngine.run``: replicas of one molecule.

The window runs whole ``record_every``-step segments, one ``run`` call
each, until the window's seconds are spent. Every segment's state is kept
for the check: the forces and energy it carries at its coordinates against
the reference's, and the reference's own Verlet segment from a kept state
to the next one.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import compare, generator, ops
from chipbench.reference import Reference, make_params, prng_key


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic
        self.model = ctx.model

    def _inputs(self):
        """Replicas of the seed's molecule: species, coordinates, mask and
        masses, each (replicas, atoms, ...)."""
        t = self.t
        sp, co = generator.molecule(t["molecule"]["formula"], t["geometry"],
                                    generator.rng(self.ctx.seed, 1))
        reps = t["replicas"]
        self.sp = np.tile(sp, (reps, 1))
        self.mask = np.ones(self.sp.shape, bool)
        self.masses = np.tile(generator.masses_of(sp), (reps, 1))
        return np.tile(co, (reps, 1, 1))

    def setup(self):
        from repro.md import MDConfig, MDEngine
        ctx, t = self.ctx, self.t
        t0 = time.monotonic()
        params = jax.block_until_ready(
            make_params(ctx.seed, self.model, ctx.devices[0]))
        self.engine = MDEngine(ctx.model_cfg(), params=params,
                               md=MDConfig(mode=ctx.mode, dt_fs=t["dt_fs"],
                                           record_every=t["record_every"]))
        t1 = time.monotonic()
        co = self._inputs()
        self.state0 = jax.block_until_ready(self.engine.init_state(
            prng_key(generator.rng(ctx.seed, 2).integers(2 ** 62)),
            self.sp, co, self.mask, self.masses, t["temperature_K"]))
        t2 = time.monotonic()
        # compiles the segment; run() copies the state it is given
        self._segment(self.state0)
        t3 = time.monotonic()
        ctx.log(f"setup: weights and engine {t1 - t0:.3f} s, init_state "
                f"{t2 - t1:.3f} s, first segment {t3 - t2:.3f} s")

    def _segment(self, state):
        state, _ = self.engine.run(state, self.sp, self.mask, self.masses,
                                   n_steps=self.t["record_every"])
        return state

    def window(self, seconds: float, tracer):
        states = [self.state0]
        traced = 0
        t0 = time.monotonic()
        t_end = t0 + seconds
        now = t0
        while now < t_end:
            tracer.maybe_start(t_end - now)
            with jax.profiler.TraceAnnotation("bench.md_segment"):
                states.append(self._segment(states[-1]))
            traced += tracer.active
            now = time.monotonic()
        self.window_s = now - t0
        self.states = states
        self.traced_segments = traced
        steps = (len(states) - 1) * self.t["record_every"]
        ns = steps * self.t["dt_fs"] * 1e-6
        return {"md_ns_per_day": ns / self.window_s * 86400.0}

    def collect(self):
        """Kept states to the host; the engine and its buffers go."""
        keys = ("coords", "veloc", "forces", "e_pot")
        self.host = [{k: np.asarray(getattr(s, k)) for k in keys}
                     for s in self.states]
        del self.states, self.state0, self.engine
        return {"attempted": len(self.host) - 1, "failed": 0}

    def observations(self, pk):
        """Counts the per-layer readers take: model work in the window,
        and the kernels' work in the traced segments."""
        m, t = self.model, self.t
        n_rows = int(self.mask.sum())
        edges = np.mean([compare.real_edges(h["coords"], self.mask,
                                            m["cutoff"]) for h in self.host])
        steps = (len(self.host) - 1) * t["record_every"]
        q, f = ops.energy_forces_ops(m, n_rows, int(round(edges)))
        traced_steps = self.traced_segments * t["record_every"]
        qmm = sum(ops.roofline_seconds(*ops.qmatmul_cost(*l),
                                       pk["int8_ops_per_s"], pk)
                  for l in ops.qmatmul_launches(m, self.ctx.mode, n_rows))
        es = ops.roofline_seconds(
            *ops.edge_softmax_cost(m, n_rows, int(round(edges))),
            pk["bf16_flops_per_s"], pk) * m["n_layers"]
        return {"least_s": steps * ops.least_seconds(q, f, pk),
                "qmatmul_least_s": traced_steps * qmm,
                "edge_softmax_least_s": traced_steps * es}

    def control(self, ctrl: Reference, segments: int):
        """The control in the program's place: ``segments`` segments of its
        own Verlet from Maxwell-Boltzmann velocities drawn from the seed."""
        t = self.t
        co = self._inputs()
        kt = 8.617333e-5 * t["temperature_K"]
        v = generator.rng(self.ctx.seed, 2).normal(size=co.shape) \
            * np.sqrt(kt / self.masses)[..., None]
        v -= (self.masses[..., None] * v).sum(1, keepdims=True) \
            / self.masses.sum(1)[:, None, None]
        e, f = ctrl.energy_forces(self.sp, co, self.mask)
        self.host = [{"coords": co, "veloc": v.astype(np.float32),
                      "forces": f, "e_pot": e}]
        for _ in range(segments):
            s = self.host[-1]
            r, v, f, e = ctrl.verlet(self.sp, s["coords"], s["veloc"],
                                     s["forces"], self.mask, self.masses,
                                     t["dt_fs"], t["record_every"])
            self.host.append({"coords": r, "veloc": v, "forces": f,
                              "e_pot": e})

    def check(self, ref: Reference, seed: int):
        t = self.t
        r = generator.rng(seed, 3)
        k_max = len(self.host) - 1
        ks = sorted(r.choice(np.arange(1, k_max) if k_max > 1
                             else np.arange(k_max),
                             size=min(t["check_segments"], max(k_max - 1, 1)),
                             replace=False))
        return compare.md_numbers(self.host, ks, ref, self.sp, self.mask,
                                  self.masses, t["dt_fs"], t["record_every"])
