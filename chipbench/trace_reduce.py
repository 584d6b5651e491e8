"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A trace holds planes (one per device, one for the host), their lines and
events with a start and a duration in nanoseconds. A device is busy while
any of its operations runs: the union of the op intervals, so overlapping
events count once. The idle share is 1 - busy / window. Kernel time is the
summed duration of the events whose name contains the kernel's name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]        # name, start_ns, duration_ns

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, non-overlapping [start, end] intervals covering the input."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: Sequence[Event]) -> float:
    """Length of the union of the events' intervals."""
    return sum(e - s for s, e in merge((s, s + d) for _, s, d in events))


def gaps(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """(start, end) of each idle interval between busy ones."""
    m = merge((s, s + d) for _, s, d in events)
    return [(a[1], b[0]) for a, b in zip(m, m[1:]) if b[0] > a[1]]


def short_name(name: str) -> str:
    """An XLA op event is named by its HLO text; keep the instruction."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


def self_time_by_name(events: Sequence[Event]) -> Dict[str, float]:
    """Time of each op not covered by ops nested inside it (a loop's
    event encloses its body's), summed by short name."""
    out: Dict[str, float] = {}
    stack: List[list] = []                  # [end, name, self time]

    def close(entry):
        out[entry[1]] = out.get(entry[1], 0.0) + entry[2]

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, short_name(name), d])
    for entry in stack:
        close(entry)
    return out


def kernel_ns(events: Sequence[Event], kernel: str) -> float:
    """Summed duration of the events whose name contains ``kernel``."""
    return sum(d for name, _, d in events if kernel in name)


def label_gap(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """Name of the host event that overlaps the gap most, or "host idle"."""
    best, name = 0.0, "host idle"
    for n, s, d in host:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, name = ov, n
    return name


@dataclasses.dataclass
class TraceSummary:
    """What one traced window says about the devices."""
    window_s: float
    busy_s: float                           # mean over the devices
    n_devices: int
    kernel_s: Dict[str, float]              # summed over the devices
    device_ops: List[Tuple[str, float]]     # most self time first, <= 10
    idle_gaps: List[Tuple[str, float]]      # longest first, <= 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(devices: Dict[str, List[Event]], host: List[Event],
              window_s: float, kernels: Sequence[str]) -> TraceSummary:
    """Reduce per-device op events and host events of one window."""
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(ev) for ev in devices.values()]
    ops: Dict[str, float] = {}
    for ev in devices.values():
        for k, v in self_time_by_name(ev).items():
            ops[k] = ops.get(k, 0.0) + v
    all_gaps = [g for ev in devices.values() for g in gaps(ev)]
    all_gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return TraceSummary(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) * 1e-9,
        n_devices=len(devices),
        kernel_s={k: sum(kernel_ns(ev, k) for ev in devices.values()) * 1e-9
                  for k in kernels},
        device_ops=[(k, v * 1e-9) for k, v in top_ops],
        idle_gaps=[(label_gap(g, host), (g[1] - g[0]) * 1e-9)
                   for g in all_gaps[:10]])


def read_xplane(path: str, device_prefix: str = DEVICE_PREFIX,
                host_plane: str = HOST_PLANE
                ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device plane -> op events, host events) from an ``.xplane.pb``.
    A device plane's ops are its "XLA Ops" line where it has one, else
    all its lines; host events of zero length are dropped."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in prof.planes:
        if plane.name.startswith(device_prefix):
            lines = list(plane.lines)
            picked = [ln for ln in lines if ln.name == OPS_LINE] or lines
            evs = [(e.name, e.start_ns, e.duration_ns)
                   for ln in picked for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name == host_plane:
            host += [(e.name, e.start_ns, e.duration_ns)
                     for ln in plane.lines for e in ln.events
                     if e.duration_ns > 0]
    return devices, host


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


class Tracer:
    """Profiles the last ``trace_s`` seconds of a window, or nothing.

    A driver calls :meth:`maybe_start` at the points where its work can be
    cut (between MD segments, between client steps) with the seconds left
    in the window; the first call with ``trace_s`` or less left starts the
    profiler. :meth:`stop` ends it and returns the summary of the traced
    span. The Python tracer is off: it would slow the host it measures.
    """

    def __init__(self, enabled: bool, trace_s: float, log_dir: str,
                 kernels: Sequence[str] = ()):
        self.enabled = enabled
        self.trace_s = trace_s
        self.log_dir = log_dir
        self.kernels = tuple(kernels)
        self.active = False
        self._t0 = 0.0

    def maybe_start(self, remaining_s: float) -> None:
        if self.enabled and not self.active and remaining_s <= self.trace_s:
            import time
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.active = True
            self._t0 = time.monotonic()

    def stop(self):
        """Stop profiling; the span's TraceSummary, or None if it never
        started."""
        if not self.active:
            return None
        import time
        import jax
        window_s = time.monotonic() - self._t0
        jax.profiler.stop_trace()
        self.active = False
        devices, host = read_xplane(find_xplane(self.log_dir))
        return summarize(devices, host, window_s, self.kernels)
