"""Trace reduction, analytic counts, peaks and the per-layer readers, on
hand-built events and on a small trace recorded on the CPU."""
import importlib.util
from types import SimpleNamespace

import pytest

from chipbench import ops, spec, trace_reduce as tr

PAPER = {"feat": 64, "vec_feat": 16, "n_rbf": 16, "n_layers": 3}


def reader(name):
    s = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), spec.metric_reader(name))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def test_union_of_overlapping_intervals():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 20, 5), ("d", 21, 1),
          ("e", 40, 0)]
    assert tr.busy_ns(ev) == 20
    assert tr.gaps(ev) == [(15, 20), (25, 40)]
    assert tr.merge([(3, 4), (0, 1), (1, 2)]) == [[0, 2], [3, 4]]


def test_kernel_time_by_name():
    ev = [("fusion.1", 0, 5), ("_w8a8_kernel", 5, 3),
          ("custom-call _w8a8_kernel.2", 9, 4), ("_w4a8_kernel", 20, 2)]
    assert tr.kernel_ns(ev, "_w8a8_kernel") == 7
    assert tr.kernel_ns(ev, "_edge_softmax_kernel") == 0
    nested = [("%while.1 = (s32[]) while(...)", 0, 10),
              ("%fusion.2 = f32[8] fusion(...)", 1, 3),
              ("%fusion.3 = f32[8] fusion(...)", 5, 2), ("%copy.4 = f32[8]", 12, 1)]
    assert tr.self_time_by_name(nested) == {"%while.1": 5, "%fusion.2": 3,
                                            "%fusion.3": 2, "%copy.4": 1}


def test_summary_and_breakdown():
    dev = {"/device:TPU:0": [("fusion", 0, 4e8), ("_w4a8_kernel", 6e8, 1e8),
                             ("fusion", 1e8, 1e8)],
           "/device:TPU:1": [("fusion", 0, 2e8)]}
    host = [("bench.md_segment", 3e8, 5e8), ("other", 4e8, 1e7)]
    s = tr.summarize(dev, host, 1.0, ["_w4a8_kernel"])
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((0.5 + 0.2) / 2)
    assert s.idle_share == pytest.approx(0.65)
    assert s.kernel_s == {"_w4a8_kernel": pytest.approx(0.1)}
    assert s.device_ops[0] == ("fusion", pytest.approx(0.6))
    assert s.device_ops[1] == ("_w4a8_kernel", pytest.approx(0.1))
    assert s.idle_gaps == [("bench.md_segment", pytest.approx(0.2))]
    with pytest.raises(ValueError):
        tr.summarize({}, host, 1.0, [])


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    t = tr.Tracer(True, 10.0, str(tmp_path))
    t.maybe_start(5.0)
    assert t.active
    with jax.profiler.TraceAnnotation("bench.step"):
        for _ in range(3):
            f(x).block_until_ready()
    t.active = False
    jax.profiler.stop_trace()
    devices, host = tr.read_xplane(tr.find_xplane(str(tmp_path)),
                                   device_prefix="/host:CPU",
                                   host_plane="none")
    events = [e for ev in devices.values() for e in ev]
    names = {n for n, _, _ in events}
    assert any("sin" in n for n in names)
    assert any("bench.step" in n for n in names)
    span = max(s + d for _, s, d in events) - min(s for _, s, _ in events)
    assert 0 < tr.busy_ns(events) <= span


def test_layer_ops_by_hand():
    lay = ops.layer_ops(PAPER, 21, 420)
    assert lay == {"trunk": 2 * 21 * 64 * 224, "update": 4 * 21 * 64 * 64,
                   "vnorm": 2 * 21 * 16 * 64,
                   "radial": 2 * 420 * 16 * 97, "attention": 2 * 420 * 64,
                   "messages": 2 * 420 * 112}
    q, f = ops.forward_ops(PAPER, 21, 420)
    assert q == 3 * (602112 + 344064 + 43008) + 2 * 21 * 80 * 64
    assert f == 3 * (1303680 + 53760 + 94080) + 2 * 21 * 64
    q2, f2 = ops.energy_forces_ops(PAPER, 21, 420)
    assert (q2, f2) == (q, f + 2 * (q + f))


def test_qmatmul_launches_and_cost():
    w4 = ops.qmatmul_launches(PAPER, "w4a8", 672)
    assert len(w4) == 16 and w4[0] == (672, 64, 192, 8)
    assert w4[1] == (672, 64, 32, 4) and w4[-1] == (672, 80, 64, 8)
    assert len(ops.qmatmul_launches(PAPER, "w8a8", 672)) == 13
    assert ops.qmatmul_cost(672, 64, 192, 8) == (16515072, 574848)
    assert ops.qmatmul_cost(672, 64, 32, 4) == (2752512, 132864)
    o, b = ops.edge_softmax_cost(PAPER, 672, 13440)
    assert o == 2 * 13440 * 65 + 2 * 13440 * 112
    assert b == 4 * (672 * 65 + 13440 * 65 + 13440 + 13440 * 112 + 672 * 112)


def test_peaks_table():
    pk = ops.peaks("TPU v5 lite")
    assert (pk["bf16_flops_per_s"], pk["int8_ops_per_s"],
            pk["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    assert "Google Cloud" in pk["source"]
    with pytest.raises(KeyError):
        ops.peaks("cpu")
    assert ops.least_seconds(393e12, 197e12, pk) == pytest.approx(2.0)
    assert ops.roofline_seconds(197e12, 1.0, 197e12, pk) == 1.0
    assert ops.roofline_seconds(1.0, 819e9, 197e12, pk) == 1.0


def test_readers():
    summ = tr.TraceSummary(window_s=2.0, busy_s=1.5, n_devices=1,
                           kernel_s={"w8a8_matmul": 0.1, "w4a8_matmul": 0.1,
                                     "_edge_softmax_kernel": 0.0},
                           device_ops=[], idle_gaps=[])
    fl = [SimpleNamespace(prep_s=0.001, dispatch_s=0.002, n_requests=8,
                          wait_s=0.004),
          SimpleNamespace(prep_s=0.003, dispatch_s=0.002, n_requests=16,
                          wait_s=0.002)]
    obs = {"trace": summ, "window_s": 10.0, "chips": 4, "least_s": 0.2,
           "qmatmul_least_s": 0.05, "edge_softmax_least_s": 0.01,
           "flushes": fl, "max_batch": 16}
    assert reader("device_idle.md")(obs) == pytest.approx(25.0)
    assert reader("mfu.tput")(obs) == pytest.approx(0.5)
    assert reader("qmatmul_roofline.md")(obs) == pytest.approx(25.0)
    assert reader("edge_softmax_roofline.md")(obs) is None
    assert reader("engine_host_ms.tput")(obs) == pytest.approx(4.0)
    assert reader("flush_fill.tput")(obs) == pytest.approx(75.0)
    assert reader("flush_wait_ms.lat")(obs) == pytest.approx(3.0)
    assert reader("device_idle.lat")(dict(obs, trace=None)) is None
