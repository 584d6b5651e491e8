"""Closed loop: one client keeps ``in_flight`` requests out at all times.

Whenever answers come back the client tops its window up again, so load
follows the server's pace. Judged on the molecules delivered in the
window per second.
"""
from __future__ import annotations

import time
from collections import deque

import jax

from chipbench.drivers._serve import ServeDriver


class Driver(ServeDriver):
    def window(self, seconds: float, tracer):
        from repro.server.scheduler import RequestTimeout
        out = deque()
        nxt = 0
        t0 = time.monotonic()
        t_end = t0 + seconds
        for _ in range(self.t["in_flight"]):
            out.append(self.submit(nxt))
            nxt += 1
        now = t0
        while now < t_end:
            tracer.maybe_start(t_end - now)
            try:
                out[0].result(timeout=t_end - now)
            except RequestTimeout:
                break
            except Exception:            # a failed answer: judged later
                pass
            with jax.profiler.TraceAnnotation("bench.client_top_up"):
                out = deque(h for h in out if not h.done())
                while len(out) < self.t["in_flight"]:
                    out.append(self.submit(nxt))
                    nxt += 1
            now = time.monotonic()
        self.finish(t_end)
        return {"mol_per_s": len(self.delivered) / seconds}
