"""Stage spans, runtime counters and named scopes of the serving hot path.

* the serving worker's seven stage spans land on the profiler's host
  plane flat and in order (``sched.wait`` -> ``engine.prep`` ->
  ``engine.dispatch`` -> ``engine.sync`` -> ``engine.unpack`` ->
  ``engine.guard`` -> ``sched.resolve``), read back with the benchmark's
  own ``.xplane.pb`` reader;
* every ``FlushRecord`` holds its breakdown's invariants, through the
  single-engine scheduler and through a one-replica ``ClusterPool``;
* the compile and GC counters count what happens between flushes;
* the served forwards name every stage scope in their op metadata, and
  the scopes change nothing but metadata.
"""
import dataclasses
import gc
import re
import time
from contextlib import nullcontext

import jax
import numpy as np
import pytest

from chipbench import trace_reduce
from repro.cluster import ClusterConfig, ClusterPool
from repro.models import so3krates as so3
from repro.obs import REGISTRY, RUNTIME
from repro.obs import trace as obs_trace
from repro.server.scheduler import MicroBatchScheduler, SchedulerConfig
from repro.serving import Graph, QuantizedEngine, ServeConfig
from repro.serving.bucketing import build_edge_list
from repro.serving.forward import (batched_energy_and_forces,
                                   sparse_energy_and_forces)
from repro.serving.qparams import quantize_so3_params

CFG = so3.So3kratesConfig(feat=16, vec_feat=4, n_layers=1, n_rbf=4,
                          dir_bits=6, cutoff=3.0)
SERVE = ServeConfig(mode="w4a8", bucket_sizes=(16, 32), max_batch=4,
                    path="dense")
STAGES = ("sched.wait", "engine.prep", "engine.dispatch", "engine.sync",
          "engine.unpack", "engine.guard", "sched.resolve")
WAIT_S = 600


def _graph(n=10, seed=0):
    rng = np.random.default_rng(seed)
    side = (n / 0.1) ** (1.0 / 3.0)
    return Graph(species=rng.integers(0, CFG.n_species, n).astype(np.int32),
                 coords=rng.uniform(0, side, size=(n, 3)).astype(np.float32))


@pytest.fixture(scope="module")
def qparams():
    params = so3.init_params(jax.random.PRNGKey(0), CFG)
    return quantize_so3_params(params, "w4a8")


def _engine(qparams):
    return QuantizedEngine.from_quantized(CFG, qparams, SERVE)


def _flushes(submit, n_flushes, n=10, pause_s=0.0):
    """Drive ``n_flushes`` full flushes of 4 molecules, one at a time,
    pausing between them so the worker waits in ``sched.wait``."""
    for k in range(n_flushes):
        hs = [submit(_graph(n, seed=4 * k + j)) for j in range(4)]
        for h in hs:
            h.result(timeout=WAIT_S)
        time.sleep(pause_s)


def _check_records(recs):
    assert recs
    for i, f in enumerate(recs):
        work = f.prep_s + f.dispatch_s + f.sync_s + f.unpack_s + f.guard_s
        assert work <= f.service_s
        assert min(f.prep_s, f.dispatch_s, f.sync_s, f.unpack_s,
                   f.guard_s) > 0.0
        assert 0.0 <= f.idle_s <= f.gap_s
        assert f.compiles >= 0 and f.gc_s >= 0.0
        if i == 0:
            assert f.gap_s == 0.0 and f.idle_s == 0.0
        else:
            assert f.gap_s >= f.prep_s + f.dispatch_s


def test_stage_spans_on_profiler_clock(qparams, tmp_path):
    with MicroBatchScheduler(_engine(qparams),
                             SchedulerConfig(max_batch=4,
                                             deadline_ms=5.0)) as s:
        _flushes(s.submit, 1)                 # off the trace: first run
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _flushes(s.submit, 3, pause_s=0.02)
            gc.collect()
            time.sleep(0.02)
        finally:
            jax.profiler.stop_trace()
    _, host = trace_reduce.read_xplane(trace_reduce.find_xplane(
        str(tmp_path)))
    spans = sorted((e for e in host if e[0] in STAGES),
                   key=lambda e: e[1])
    names = [n for n, _, _ in spans]
    assert set(names) == set(STAGES)
    # flat and in order: each span ends before the next starts, and the
    # names cycle through the worker loop's seven stages
    for (a, sa, da), (b, sb, _) in zip(spans, spans[1:]):
        assert sa + da <= sb, (a, b)
        assert STAGES.index(b) == (STAGES.index(a) + 1) % len(STAGES), \
            (a, b)
    assert names.count("engine.dispatch") == 3
    assert any(n == "python.gc" for n, _, _ in host)


def test_flush_records_scheduler(qparams):
    with MicroBatchScheduler(_engine(qparams),
                             SchedulerConfig(max_batch=4,
                                             deadline_ms=5.0)) as s:
        _flushes(s.submit, 4, pause_s=0.02)
        recs = list(s._flushes)
    _check_records(recs)
    # the pauses between flushes are worker idle time inside the gap
    assert all(f.idle_s > 0.005 for f in recs[1:])


def test_flush_records_one_replica_pool(qparams):
    pool = ClusterPool([_engine(qparams)],
                       ClusterConfig(n_replicas=1, max_batch=4,
                                     deadline_ms=5.0))
    with pool:
        _flushes(pool.submit, 4, pause_s=0.02)
        recs = pool.flush_records()
    _check_records(recs)
    assert all(f.idle_s > 0.005 for f in recs[1:])


def test_compiles_counted_per_flush(qparams):
    """A fresh shape compiles inside its flush; warm flushes count 0."""
    before = REGISTRY.counter("jax_compiles_total", source="compile").value
    with MicroBatchScheduler(_engine(qparams),
                             SchedulerConfig(max_batch=4, deadline_ms=5.0,
                                             warmup=False)) as s:
        _flushes(s.submit, 2, n=10)           # bucket 16: compiles, warm
        _flushes(s.submit, 1, n=20)           # bucket 32: a fresh shape
        recs = list(s._flushes)
    assert [f.capacity for f in recs] == [16, 16, 32]
    assert recs[0].compiles >= 1
    assert recs[1].compiles == 0
    assert recs[2].compiles >= 1
    after = REGISTRY.counter("jax_compiles_total", source="compile").value
    assert after - before >= recs[0].compiles + recs[2].compiles


def test_cache_load_counted_apart():
    """A backend compile preceded by a cache hit on the same thread is a
    load from the persistent cache."""
    from jax import monitoring
    RUNTIME.install()
    c = {s: REGISTRY.counter("jax_compiles_total", source=s)
         for s in ("compile", "cache")}
    v0 = {s: c[s].value for s in c}
    n0 = RUNTIME.compiles
    monitoring.record_event(obs_trace.CACHE_HIT_EVENT)
    monitoring.record_event_duration_secs(obs_trace.BACKEND_COMPILE_EVENT,
                                          0.01, fun_name="f")
    monitoring.record_event_duration_secs(obs_trace.BACKEND_COMPILE_EVENT,
                                          0.01, fun_name="f")
    assert RUNTIME.compiles - n0 == 2
    assert c["cache"].value - v0["cache"] == 1
    assert c["compile"].value - v0["compile"] == 1


def test_gc_pause_lands_in_flush_and_registry(qparams):
    def registry_total():
        return sum(REGISTRY.counter("python_gc_seconds_total",
                                    generation=str(g)).value
                   for g in range(3))

    with MicroBatchScheduler(_engine(qparams),
                             SchedulerConfig(max_batch=4,
                                             deadline_ms=5.0)) as s:
        _flushes(s.submit, 2)
        total0, reg0 = RUNTIME.gc_s, registry_total()
        gc.collect()
        paused = RUNTIME.gc_s - total0
        in_registry = registry_total() - reg0
        _flushes(s.submit, 1)
        recs = list(s._flushes)
    assert paused > 0.0
    assert in_registry == pytest.approx(paused)
    assert recs[-1].gc_s >= paused


def test_stage_accumulates_and_annotates():
    acc = {}
    with obs_trace.stage("engine.prep", acc):
        pass
    with obs_trace.stage("engine.prep", acc) as st:
        time.sleep(0.002)
    with obs_trace.stage("sched.wait", acc, "idle_s", flush=3):
        pass
    assert set(acc) == {"prep_s", "idle_s"}
    assert acc["prep_s"] >= st.t1 - st.t0 >= 0.002
    with obs_trace.stage("sched.resolve") as st:   # annotation only
        pass
    assert st.t1 >= st.t0


# -- named scopes in the served forward -------------------------------------

LAYER_SCOPES = ("trunk", "radial", "attention", "messages", "update",
                "mddq_snap", "vnorm_feedback")
CFG2 = dataclasses.replace(CFG, n_layers=2)


def _forward_hlo(path):
    """Compiled text of the served forward at a tiny size, both layers."""
    params = quantize_so3_params(so3.init_params(jax.random.PRNGKey(1),
                                                 CFG2), "w4a8")
    B, n = 2, 16
    rng = np.random.default_rng(0)
    sp = rng.integers(0, CFG2.n_species, (B, n)).astype(np.int32)
    co = rng.uniform(0, 5, (B, n, 3)).astype(np.float32)
    mask = np.ones((B, n), bool)
    if path == "dense":
        fn = jax.jit(lambda s, c, m: batched_energy_and_forces(
            params, CFG2, s, c, m))
        args = (sp, co, mask)
    else:
        el = build_edge_list(co, mask, CFG2.cutoff, n * (n - 1))
        fn = jax.jit(lambda s, c, m, a, b, e: sparse_energy_and_forces(
            params, CFG2, s, c, m, a, b, e))
        args = (sp, co, mask, el.senders, el.receivers, el.edge_mask)
    return fn.lower(*args).compile().as_text()


def _strip_metadata(text):
    head, rest = text.split("\n", 1)
    body = rest[re.search(r"^(%|ENTRY)", rest, re.M).start():]
    meta = re.compile(r',? ?metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
    return meta.sub("", head + "\n" + body)


@pytest.mark.parametrize("path", ["dense", "sparse"])
def test_forward_names_every_scope(path):
    names = set(re.findall(r'op_name="([^"]*)"', _forward_hlo(path)))
    joined = "\n".join(names)
    for top in ("geometry", "readout"):
        assert re.search(rf"(^|/|\(){top}(\)|/)", joined, re.M), top
    for i in range(CFG2.n_layers):
        for scope in LAYER_SCOPES:
            assert re.search(rf"layer{i}\)?/{scope}/", joined), \
                (i, scope)
    # the force pass inherits the names through the name stack
    assert re.search(r"transpose\(jvp\(layer0\)\)/", joined)


@pytest.mark.parametrize("path", ["dense", "sparse"])
def test_scopes_change_only_metadata(path, monkeypatch):
    scoped = _strip_metadata(_forward_hlo(path))
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    plain = _strip_metadata(_forward_hlo(path))
    assert scoped == plain
