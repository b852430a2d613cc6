"""Ablation-based perf probe for the fused AlexNet step.

Times solver.step_repeat under config variants to attribute cost: a layer
timed in isolation is not additive to the fused total.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from sparknet_tpu import models
from sparknet_tpu.config import replace_data_layers
from sparknet_tpu.solver import Solver

BATCH = 256
ITERS = 20


def build(mutate=None, dtype="bfloat16"):
    netp = replace_data_layers(
        models.load_model("alexnet"),
        [(BATCH, 3, 227, 227), (BATCH,)],
        [(BATCH, 3, 227, 227), (BATCH,)],
    )
    if mutate:
        mutate(netp)
    return Solver(models.load_model_solver("alexnet"), net_param=netp,
                  compute_dtype=None if dtype == "f32" else dtype)


from tools.deep_probe import drop_layers  # shared ablation helper


def timeit(name, solver):
    state = solver.init_state(seed=0)
    rng = np.random.RandomState(0)
    batch = {
        "data": rng.randn(BATCH, 3, 227, 227).astype(np.float32),
        "label": rng.randint(0, 1000, BATCH).astype(np.float32),
    }
    dev = jax.device_put(batch)
    state, losses = solver.step_repeat(state, dev, tau=ITERS)
    jax.block_until_ready(losses)
    t0 = time.perf_counter()
    state, losses = solver.step_repeat(state, dev, tau=ITERS)
    jax.block_until_ready(losses)
    dt = time.perf_counter() - t0
    print("%-28s %7.1f img/s   %6.2f ms/iter" % (name, BATCH * ITERS / dt, dt / ITERS * 1e3))
    return dt


def ungroup(netp):
    for lp in netp.layer:
        if lp.type == "Convolution":
            lp.convolution_param.group = 1


if __name__ == "__main__":
    timeit("baseline bf16", build())
    timeit("f32", build(dtype="f32"))
    timeit("no LRN", build(lambda p: drop_layers(p, {"LRN"})))
    timeit("no Dropout", build(lambda p: drop_layers(p, {"Dropout"})))
    timeit("no LRN+Dropout", build(lambda p: drop_layers(p, {"LRN", "Dropout"})))
    timeit("group=1 convs", build(ungroup))
