"""What the serving drivers share: the server, its warm-up, the kept
answers and their check.

The server is a ``MicroBatchScheduler`` over one ``QuantizedEngine``.
Requests are drawn from a pool of molecules made from the seed and
cycled; every answer is kept with the pool index of its molecule.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

from chipbench import compare, generator, ops
from chipbench.reference import make_params

POOL = 1000             # distinct molecules per run, cycled
CHECK_SAMPLE = 128      # answers compared with the reference per run
WAIT_S = 60.0           # how long past the window an answer may take


class ServeDriver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic
        self.model = ctx.model
        self.kept: List[Tuple[int, object]] = []   # (pool index, handle)

    # -- set-up --------------------------------------------------------------

    def setup(self):
        from repro.serving import QuantizedEngine, ServeConfig
        from repro.serving.bucketing import Graph
        ctx, t = self.ctx, self.t
        t0 = time.monotonic()
        self.requests = generator.request_pool(
            t["molecules"], t["geometry"], POOL, generator.rng(ctx.seed, 1))
        self.graphs = [Graph(sp, co) for _, sp, co in self.requests]
        params = make_params(ctx.seed, self.model, ctx.devices[0])
        serve = ServeConfig(mode=ctx.mode, bucket_sizes=tuple(t["buckets"]),
                            max_batch=t["max_batch"], path=t["path"])
        from repro.server import MicroBatchScheduler, SchedulerConfig
        engine = QuantizedEngine(ctx.model_cfg(), params, serve,
                                 device=ctx.devices[0])
        self.server = MicroBatchScheduler(
            engine, SchedulerConfig(max_batch=t["max_batch"],
                                    deadline_ms=t["deadline_ms"]))
        t1 = time.monotonic()
        # one pass of each molecule kind through the live server, so the
        # window meets no first call of anything
        first = {}
        for i, (name, _, _) in enumerate(self.requests):
            first.setdefault(name, i)
        hs = [self.server.submit(self.graphs[i]) for i in first.values()
              for _ in range(t["max_batch"])]
        for h in hs:
            h.result(timeout=600)
        self.warmup_report = list(engine.warmup_report)
        self.n_flush0 = len(self._flushes())
        t2 = time.monotonic()
        ctx.log(f"setup: pool, weights, engine and warmup {t1 - t0:.3f} s "
                f"({len(self.warmup_report)} programs, "
                f"{sum(r['seconds'] for r in self.warmup_report):.3f} s in "
                f"warmup), warm pass {t2 - t1:.3f} s")

    def _flushes(self):
        """The scheduler's FlushRecords so far."""
        return list(self.server._flushes)

    def submit(self, i: int):
        h = self.server.submit(self.graphs[i % POOL])
        self.kept.append((i % POOL, h))
        return h

    # -- after the window ----------------------------------------------------

    def finish(self, t_end: float) -> None:
        """Wait for every answer still out, up to ``WAIT_S`` past the
        window; then keep the window's flush records."""
        deadline = t_end + WAIT_S
        for _, h in self.kept:
            try:
                h.result(timeout=max(deadline - time.monotonic(), 0.0))
            except Exception:            # judged in collect()
                pass
        self.delivered = [i for i, h in self.kept if h.done()
                          and h.t_done <= t_end and h._error is None]
        self.flushes = self._flushes()[self.n_flush0:]

    def collect(self):
        """Answers to the host; the server and its engines go."""
        self.answers = []
        failed = 0
        for i, h in self.kept:
            res = None
            if h.done():
                try:
                    res = h.result(timeout=0)
                except Exception:
                    res = None
            if res is None:
                failed += 1
            self.answers.append((i, res))
        self.server.close()
        del self.server
        return {"attempted": len(self.kept), "failed": failed}

    def observations(self, pk):
        """Model work of the answers delivered in the window, and the
        scheduler's records of it."""
        m = self.model
        q = f = 0
        cache = {}
        for i in self.delivered:
            if i not in cache:
                _, sp, co = self.requests[i]
                e = compare.real_edges(co[None], np.ones((1, sp.size), bool),
                                       m["cutoff"])
                cache[i] = ops.energy_forces_ops(m, sp.size, e)
            q += cache[i][0]
            f += cache[i][1]
        return {"least_s": ops.least_seconds(q, f, pk),
                "flushes": self.flushes, "max_batch": self.t["max_batch"]}

    def control(self, ctrl, segments: int):
        """The control in the program's place: its answers for a sample of
        the seed's request pool drawn as a run's check draws it."""
        from types import SimpleNamespace
        self.requests = generator.request_pool(
            self.t["molecules"], self.t["geometry"], POOL,
            generator.rng(self.ctx.seed, 1))
        idx = generator.rng(self.ctx.seed, 5).choice(POOL, CHECK_SAMPLE,
                                                     replace=False)
        mols = [self.requests[i][1:] for i in idx]
        e, f = compare.reference_answers(mols, ctrl)
        self.answers = [(int(i), SimpleNamespace(energy=ei, forces=fi))
                        for i, ei, fi in zip(idx, e, f)]

    def check(self, ref, seed: int):
        """Gaps over a sample of the answers drawn from the seed, holding
        the largest molecule kind."""
        r = generator.rng(seed, 3)
        ok = [k for k, (_, res) in enumerate(self.answers) if res is not None]
        if not ok:
            return {"force_gap": float("inf"), "energy_gap": float("inf")}
        pick = list(r.choice(ok, size=min(CHECK_SAMPLE, len(ok)),
                             replace=False))
        largest = max(ok, key=lambda k: self.requests[self.answers[k][0]][1]
                      .size)
        if largest not in pick:
            pick.append(largest)
        answers = [(self.answers[k][1].energy, self.answers[k][1].forces)
                   for k in pick]
        mols = [self.requests[self.answers[k][0]][1:] for k in pick]
        return compare.serve_numbers(answers, mols, ref)
