"""Kernels of the main path compiled for the v5e at the widths the benchmark
runs them at, without a chip: the TPU's compiler is installed here and
compiles for a chip that is described, not attached.  This catches what
interpreter mode cannot — a block shape, a layout or a VMEM budget that
Mosaic refuses — at no chip time.  Nothing runs; results are tested elsewhere
(``tests/test_hybrid_lm.py``).

The topology is described inside a fixture, never while a module is imported:
only one process at a time may load the TPU's library, and every xdist worker
imports every test file.  All such tests stay in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparknet_tpu.ops import pallas_delta_rule


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# qwen3next-train-8k: 2 sequences of 8,192 tokens, 32 heads of 128, chunks of
# 64, bfloat16; and its check's rule in float32 (one sequence)
@pytest.mark.parametrize("batch, dtype", [(2, "bfloat16"), (1, "float32")])
@pytest.mark.parametrize("backward", [False, True])
def test_delta_rule_kernels_compile_for_the_v5e(
        one_chip, monkeypatch, batch, dtype, backward):
    monkeypatch.setattr(pallas_delta_rule, "lowerable", lambda: True)
    t, h, d, chunk, cd = 8192, 32, 128, 64, jnp.dtype(dtype)
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    inputs = (shape(batch, t, h, d),) * 3 + (shape(batch, t, h),) * 2

    def forward(*xs):
        return pallas_delta_rule.within_chunks(*xs, chunk, cd)

    def gradients(*xs):
        outs, vjp = jax.vjp(forward, *xs)
        return vjp(outs)

    compiled = jax.jit(gradients if backward else forward).lower(
        *inputs).compile()
    name = "delta_rule_within_chunks" + ("_backward" if backward else "")
    assert name in compiled.as_text()
