"""Open loop: requests arrive on a fixed schedule whatever the server does.

Arrivals are the traffic's rate over the window (the same gaps for every
seed, in another order). Latency runs from each request's scheduled
arrival, so a late client or a stalled server cannot hide queueing; a
request that fails counts as having waited until the end of the wait
past the window.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import generator
from chipbench.drivers._serve import WAIT_S, ServeDriver


class Driver(ServeDriver):
    def window(self, seconds: float, tracer):
        arrivals = generator.arrival_times(
            self.t["rate_per_s"], seconds, generator.rng(self.ctx.seed, 4))
        arrivals = arrivals[arrivals < seconds]
        t0 = time.monotonic()
        t_end = t0 + seconds
        for i, t_arr in enumerate(arrivals):
            delay = t0 + t_arr - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            tracer.maybe_start(t_end - time.monotonic())
            with jax.profiler.TraceAnnotation("bench.client_submit"):
                self.submit(i)
        self.finish(t_end)
        lat = []
        for (_, h), t_arr in zip(self.kept, arrivals):
            ok = h.done() and h._error is None
            lat.append((h.t_done if ok else t_end + WAIT_S) - (t0 + t_arr))
        return {"p95_ms": float(np.percentile(lat, 95)) * 1e3}
