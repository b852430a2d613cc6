"""What the device trace reads from the program (PR 24): the round program
names its phases and layers (``jax.named_scope`` in ``solver.py``, ``net.py``,
``parallel/trainers.py``), and ``obs.span`` opens a ``TraceAnnotation`` so
that the program's spans sit in the profiler's ``.xplane.pb`` on its clock.
ARCHITECTURE.md "Telemetry reference" lists the names pinned here."""

import functools
import glob
import os
import re

import jax
import numpy as np
import pytest

from sparknet_tpu import config, obs
from sparknet_tpu.data import transforms
from sparknet_tpu.net import layer_scope
from sparknet_tpu.obs import trace as obs_trace
from sparknet_tpu.parallel import (
    ParameterAveragingTrainer,
    make_mesh,
    shard_leading,
)
from sparknet_tpu.solver import Solver
from sparknet_tpu.utils.rngs import default_train_key

STORED, CROP, BATCH, TAU, WORKERS = 14, 12, 4, 2, 2

HEAD = f"""
name: "scoped"
layer {{ name: "data" type: "HostData" top: "data" top: "label"
  java_data_param {{ shape {{ dim: {BATCH} dim: 3 dim: {CROP} dim: {CROP} }}
                    shape {{ dim: {BATCH} }} }} }}
"""
TAIL = """
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label" top: "loss" }
"""
NETS = {
    # conv + ReLU + LRN + pool + inner product, one name with a '/' in it
    "lrn": HEAD + """
layer { name: "stem/conv1" type: "Convolution" bottom: "data" top: "c1"
  convolution_param { num_output: 8 kernel_size: 3 stride: 1
    weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "c1" top: "c1" }
layer { name: "norm1" type: "LRN" bottom: "c1" top: "n1"
  lrn_param { local_size: 3 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool1" type: "Pooling" bottom: "n1" top: "p1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "fc" type: "InnerProduct" bottom: "p1" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
""" + TAIL,
    # conv + BatchNorm + Scale + ReLU + inner product
    "batchnorm": HEAD + """
layer { name: "conv1" type: "Convolution" bottom: "data" top: "c1"
  convolution_param { num_output: 8 kernel_size: 3 stride: 2 bias_term: false
    weight_filler { type: "xavier" } } }
layer { name: "bn1" type: "BatchNorm" bottom: "c1" top: "c1" }
layer { name: "scale1" type: "Scale" bottom: "c1" top: "c1"
  scale_param { bias_term: true } }
layer { name: "relu1" type: "ReLU" bottom: "c1" top: "c1" }
layer { name: "fc" type: "InnerProduct" bottom: "c1" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
""" + TAIL,
}


def _trainer(net):
    solver_param = config.parse_solver_prototxt(
        'base_lr: 0.01 lr_policy: "fixed" momentum: 0.9 weight_decay: 0.0005'
    )
    mean = np.full((3, STORED, STORED), 120.0, np.float32)
    solver = Solver(
        solver_param,
        net_param=config.parse_net_prototxt(NETS[net]),
        train_transform=transforms.train_transform(mean, CROP, mirror=True),
    )
    mesh = make_mesh({"dp": WORKERS}, devices=jax.devices()[:WORKERS])
    return ParameterAveragingTrainer(solver, mesh), mesh


def _batch(mesh):
    rng = np.random.RandomState(0)
    return shard_leading({
        "data": rng.randint(
            0, 256, (WORKERS, TAU, BATCH, 3, STORED, STORED)).astype(np.uint8),
        "label": rng.randint(0, 5, (WORKERS, TAU, BATCH)).astype(np.float32),
    }, mesh)


@functools.lru_cache(maxsize=None)
def _compiled_round(net):
    """(net's layers, op_names in the HLO text of its compiled round)."""
    trainer, mesh = _trainer(net)
    state = trainer.init_state(seed=0)
    live = trainer._place_live(np.ones((WORKERS,), np.float32))
    text = trainer._round.lower(
        state, _batch(mesh), default_train_key(0), live
    ).compile().as_text()
    layers = [
        l.lp for l in trainer.solver.net.layers if l.lp.type != "HostData"
    ]
    return layers, set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(params=sorted(NETS))
def compiled_round(request):
    return _compiled_round(request.param)


def _under(op_names, scope):
    """Operations whose name stack holds ``scope`` as one whole component."""
    return [n for n in op_names if scope in n.split("/")]


@pytest.mark.parametrize("scope", ["transform", "update", "average"])
def test_compiled_round_names_its_phases(compiled_round, scope):
    _, op_names = compiled_round
    assert _under(op_names, scope), scope


def test_compiled_round_names_every_layer_forward_and_backward(compiled_round):
    layers, op_names = compiled_round
    assert len(layers) >= 6
    for lp in layers:
        scope = layer_scope(lp)
        assert scope == f"{lp.type}:{lp.name.replace('/', '.')}"
        # forward: jvp(<Type>:<name>); backward: autodiff's own wrapper
        assert _under(op_names, f"jvp({scope})"), scope
        assert _under(op_names, f"transpose(jvp({scope}))"), scope


def test_phases_sit_inside_or_outside_the_step_loop(compiled_round):
    """``transform`` and ``update`` run once a local step (inside the scan
    over tau), ``average`` once a round (after it)."""
    _, op_names = compiled_round
    assert all("while" in n.split("/") for n in _under(op_names, "transform"))
    assert all("while" in n.split("/") for n in _under(op_names, "update"))
    assert not any(
        "while" in n.split("average")[0].split("/")
        for n in _under(op_names, "average")
    )


def test_a_slash_in_a_layer_name_is_read_back_whole():
    layers, op_names = _compiled_round("lrn")
    (lp,) = [lp for lp in layers if "/" in lp.name]
    assert layer_scope(lp) == "Convolution:stem.conv1"
    # one component of the stack: type and name come back without guessing
    assert _under(op_names, "jvp(Convolution:stem.conv1)")
    assert not any("Convolution:stem/" in n for n in op_names)


# -- obs.span on the profiler's clock ------------------------------------------
def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, dict(ev.stats), line.name))
    return out


def test_span_lands_in_the_profilers_trace(tmp_path):
    tracer = obs.install_tracer(obs.Tracer())
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with obs.span("assemble", round=3):
            jax.block_until_ready(jax.numpy.ones((8,)) + 1)
    finally:
        jax.profiler.stop_trace()
        obs.uninstall_tracer()
    mine = [e for e in _host_events(str(tmp_path)) if e[0] == "assemble"]
    assert len(mine) == 1
    assert str(mine[0][1].get("round")) == "3"
    # and the tracer still has it, with the same argument
    (span,) = [e for e in tracer.events() if e.get("ph") == "X"]
    assert span["name"] == "assemble" and span["args"] == {"round": 3}


def test_span_off_path_is_the_shared_null_span(monkeypatch):
    made = []
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda *a, **k: made.append(a) or pytest.fail("annotation made"),
    )
    assert obs_trace.get_tracer() is None
    assert obs.span("assemble", round=3) is obs_trace._NULL_SPAN
    with obs.span("average", round=0) as s:
        assert s is obs_trace._NULL_SPAN
    assert made == []


def test_every_sink_still_gets_the_span_beside_the_annotation():
    seen = []
    obs_trace.set_span_observer(lambda *a: seen.append(a))
    try:
        with obs.span("h2d", round=7):
            pass
    finally:
        obs_trace.set_span_observer(None)
    (got,) = seen
    assert got[0] == "h2d" and got[-1] == {"round": 7}


def test_round_average_span_carries_the_round():
    trainer, mesh = _trainer("lrn")
    state = trainer.init_state(seed=0)
    tracer = obs.install_tracer(obs.Tracer())
    try:
        state, _ = trainer.round(state, _batch(mesh), round_index=5)
        state, _ = trainer.round(state, _batch(mesh))
    finally:
        obs.uninstall_tracer()
    jax.block_until_ready(state)
    spans = [e for e in tracer.events() if e.get("ph") == "X"]
    rounds = [e["args"]["round"] for e in spans if e["name"] == "average"]
    assert rounds == [5, 6]
    assert [e["name"] for e in spans].count("execute") == 2
