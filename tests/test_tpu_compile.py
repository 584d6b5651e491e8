"""AOT compiles of the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs the kernels on CPU but checks none of what the TPU
compiler checks: block tiling, memory layouts, fast-memory limits. Each
test here compiles one kernel at the paper's widths (F=64, Fv=16) for
one chip of a described ``v5e:2x2`` topology, with no chip attached,
and asserts that the kernel is in the program as a compiled TPU custom
call. Nothing runs, so nothing here says anything about results or
speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.edge_softmax import edge_softmax_kernel
from repro.kernels.quant_matmul import w8a8_matmul
from repro.serving.bucketing import default_edge_capacity


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ops wrappers take interpret mode from the process's backend,
    which is the CPU here: steer them to the compiled kernels."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile_for_chip(fn, sharding, *specs) -> None:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# q/k carry F+1 = 65 columns and the values F + 3Fv = 112: both pad to 128
@pytest.mark.parametrize("cap", [16, 32, 64, 128])
def test_edge_softmax_kernel(one_chip, cap):
    b, ec = 2, default_edge_capacity(cap)
    _compile_for_chip(
        lambda q, k, r, v: edge_softmax_kernel(q, k, r, v, cap=cap),
        one_chip, ((b * cap, 128), jnp.float32), ((b * ec, 128), jnp.float32),
        ((b * ec, 1), jnp.int32), ((b * ec, 128), jnp.float32))


def test_edge_softmax_unaligned_capacity(one_chip, compiled_kernels):
    """MD replica batches keep the molecule's atom count (here 21) as
    their capacity; the wrapper pads node rows to the 8-row tile."""
    cap, b, ec, f, w = 21, 4, 512, 64, 112
    _compile_for_chip(
        lambda q, k, bias, v, s, r, m: ops.edge_softmax(
            q, k, bias, v, s, r, m, cap=cap, use_kernel=True),
        one_chip, ((b * cap, f), jnp.float32), ((b * cap, f), jnp.float32),
        ((b * ec,), jnp.float32), ((b * ec, w), jnp.float32),
        ((b * ec,), jnp.int32), ((b * ec,), jnp.int32), ((b * ec,), bool))


def test_w8a8_matmul(one_chip):
    """K=128, N=256: the fused q|k|msg trunk (3F = 192 columns, padded)."""
    m, k, n = 256, 128, 256
    _compile_for_chip(
        lambda a, a_s, w, w_s: w8a8_matmul(a, a_s, w, w_s),
        one_chip, ((m, k), jnp.int8), ((m, 1), jnp.float32),
        ((k, n), jnp.int8), ((1, n), jnp.float32))


@pytest.mark.parametrize("n", [128, 256])
def test_w4a8_matmul(one_chip, compiled_kernels, n):
    """Through the ops wrapper, which picks a lane-aligned packed block:
    N=128 covers the paper's wa|wb (2Fv = 32 columns, padded), N=256 a
    W4 weight wider than one block."""
    m, k = 200, 64
    _compile_for_chip(ops.matmul_w4a8, one_chip, ((m, k), jnp.float32),
                      ((k, n // 2), jnp.uint8), ((1, n), jnp.float32))
