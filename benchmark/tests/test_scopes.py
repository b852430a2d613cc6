"""Device time by scope (PR 24): the reader on made-up module and operation
events, and on a trace recorded on the chip with the ``op_name``s kept
(``data/caffenet-train.scoped.xplane.pb.gz``, cut by
``data/make_trimmed_scoped_trace.py``)."""

import gzip
import os

import numpy as np
import pytest
from jax.profiler import ProfileData

from benchmark import evidence, files, flops, run, scopes
from benchmark import xplane as X

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MARKS = ["feed.next_round", "trainer.round", "wait.losses", "drain"]
BODY = "jit(round_body)/while/body/closed_call/"
NEW = ["transform_device_ms", "forward_device_ms", "backward_device_ms",
       "update_device_ms", "average_device_ms", "matmul_device_ms",
       "norm_device_ms", "unscoped_device_ms"]


# -- the name stack of one operation ------------------------------------------
@pytest.mark.parametrize("op_name,expected", [
    (BODY + "transform/vmap()/gather", ("transform", None, None)),
    (BODY + "jvp(Convolution:conv1)/conv_general_dilated",
     ("forward", "Convolution", "conv1")),
    (BODY + "transpose(jvp(Convolution:conv1))/conv_general_dilated",
     ("backward", "Convolution", "conv1")),
    (BODY + "transpose(jvp(LRN:norm1))/jit(_pad)/pad", ("backward", "LRN", "norm1")),
    # GoogLeNet's inception_3a/1x1: the '/' is written '.', type and name whole
    (BODY + "jvp(Convolution:inception_3a.1x1)/add",
     ("forward", "Convolution", "inception_3a.1x1")),
    # not under autodiff (a test net's forward pass)
    ("jit(forward)/BatchNorm:bn_conv1/rsqrt", ("forward", "BatchNorm", "bn_conv1")),
    (BODY + "update/mul;while/body", ("update", None, None)),
    ("jit(round_body)/average/psum_invariant", ("average", None, None)),
    ("average/add", ("average", None, None)),
    # the compiler's own: the loop, its copies, a function that is not a scope
    ("jit(round_body)/while", ("unscoped", None, None)),
    ("jit(round_body)/jit(_threefry_fold_in)/shift_left", ("unscoped", None, None)),
    ("jit(transform)/add", ("unscoped", None, None)),
    ("", ("unscoped", None, None)),
])
def test_a_name_stack_gives_phase_type_and_name(op_name, expected):
    assert scopes.classify(op_name) == expected


# -- made-up events -----------------------------------------------------------
OPS = {  # event name -> op_name, as the file's metadata would give them
    "%while.1 = () while(...)": "jit(round_body)/while",
    "%crop = f32[4] fusion(...)": BODY + "transform/dynamic_slice",
    "%conv = bf16[4] fusion(...)": BODY + "jvp(Convolution:conv1)/conv_general_dilated",
    "%lrn = bf16[4] fusion(...)": BODY + "jvp(LRN:norm1)/mul",
    "%dconv = f32[4] fusion(...)":
        BODY + "transpose(jvp(Convolution:conv1))/conv_general_dilated",
    "%dfc = f32[4] fusion(...)": BODY + "transpose(jvp(InnerProduct:fc))/dot_general",
    "%sgd = f32[4] fusion(...)": BODY + "update/sub",
    "%mean = f32[4] all-reduce(...)": "jit(round_body)/average/psum_invariant",
    "%copy.1 = f32[4] copy(...)": "jit(round_body)/while",
}


def one_round(t, step=(("%crop", 10), ("%conv", 30), ("%lrn", 5), ("%dconv", 40),
                       ("%dfc", 10), ("%sgd", 4), ("%copy.1", 1)),
              tau=2, hole=0):
    """Operation events of one execution that starts at ``t``: a ``while``
    that holds tau steps back to back, then the all-reduce; ``hole`` ns in
    which no event is recorded are left before the all-reduce.  Returns
    (events, end)."""
    full = {name.split(" ")[0]: name for name in OPS}
    events, at = [], t
    for _ in range(tau):
        for short, ns in step:
            events.append((full[short], at, at + ns))
            at += ns
    events.insert(0, (full["%while.1"], t, at + 2))  # 2 ns of its own
    at += 2 + hole
    events.append((full["%mean"], at, at + 20))
    return events, at + 20


def made_up(holes=(0, 0, 0), tau=2, scoped=True):
    """Three executions of ``jit_round_body`` with a ``jit_take`` before
    each, inside a window that also cuts a fourth in two."""
    ops, mods, t = [], [], 1000.0
    for hole in (*holes, 0):
        ops.append(("%take = u8[4] fusion(...)", t, t + 8))
        mods.append(("jit_take(1)", t, t + 8))
        events, end = one_round(t + 10, tau=tau, hole=hole)
        ops.extend(events)
        mods.append(("jit_round_body(2)", t + 10, end))
        t = end + 5
    window = (990.0, mods[-1][2] - 50)  # the last execution is not whole
    trace = type("Trace", (), {})()
    trace.devices = {0: {"ops": X.Events(*zip(*ops)),
                         "modules": X.Events(*zip(*mods)),
                         "async": X.Events()}}
    trace.host = {}
    ev = {"trace": trace, "window_ns": window, "devices": [0], "tau": tau,
          "spans": {}, "marks": {}}
    return ev, ({0: OPS} if scoped else {0: {}})


@pytest.fixture
def reading(monkeypatch):
    """``read(ev, stacks, metric)``: the metric as ``run.py`` reads it, the
    ``op_name``s handed over in place of a file's."""
    def read(ev, stacks, metric):
        monkeypatch.setattr(scopes, "op_names", lambda path: stacks)
        monkeypatch.setattr(scopes, "trace_path", lambda ev: "made-up")
        return run.read_layer_metric(metric, ev)

    scopes._cache.clear()
    yield read
    scopes._cache.clear()


def test_self_time_is_counted_once_and_divided_by_tau(reading, capsys):
    ev, stacks = made_up()
    ms = lambda ns: ns / 1e6
    assert reading(ev, stacks, "transform_device_ms") == pytest.approx(ms(10))
    assert reading(ev, stacks, "forward_device_ms") == pytest.approx(ms(35))
    assert reading(ev, stacks, "backward_device_ms") == pytest.approx(ms(50))
    assert reading(ev, stacks, "update_device_ms") == pytest.approx(ms(4))
    # per round, not per step
    assert reading(ev, stacks, "average_device_ms") == pytest.approx(ms(20))
    # the while's own 2 ns a round and the copy's 1 ns a step: the time its
    # body runs is the body's, not the loop's a second time
    assert reading(ev, stacks, "unscoped_device_ms") == pytest.approx(ms(2 / 2 + 1))
    out = capsys.readouterr().out
    assert "chip 0: 3 of 3" in out  # the fourth is cut by the window's end
    assert out.count("device ms a step by phase") == 1  # printed once a run
    whole = sum(reading(ev, stacks, m) for m in NEW[:4]) + reading(
        ev, stacks, "unscoped_device_ms") + reading(ev, stacks, "average_device_ms") / 2
    assert whole == pytest.approx(ms((2 * 100 + 2 + 20) / 2))


def test_type_lists_come_from_the_metrics_own_file(reading):
    ev, stacks = made_up()
    # Convolution + InnerProduct, forward and backward: 30 + 40 + 10
    assert files.load_json("benchmark", "layer_metrics", "matmul_device_ms.json")[
        "args"]["types"] == ["Convolution", "InnerProduct"]
    assert reading(ev, stacks, "matmul_device_ms") == pytest.approx(80 / 1e6)
    assert files.load_json("benchmark", "layer_metrics", "norm_device_ms.json")[
        "args"]["types"] == ["LRN", "BatchNorm", "Scale"]
    assert reading(ev, stacks, "norm_device_ms") == pytest.approx(5 / 1e6)
    from benchmark.reducers import device_ms_by_scope

    assert device_ms_by_scope.reduce(
        ev, phases=["backward"], types=["InnerProduct"]) == pytest.approx(10 / 1e6)


def test_an_execution_with_a_hole_is_dropped_and_counted(reading, capsys):
    # 222 ns of events and a hole of 30: 88% covered
    ev, stacks = made_up(holes=(0, 30, 0))
    assert reading(ev, stacks, "backward_device_ms") == pytest.approx(50 / 1e6)
    out = capsys.readouterr().out
    assert "chip 0: 2 of 3, the rest cover [0.881]" in out
    # every execution holed: nothing to read, and the line says which it was
    scopes._cache.clear()
    ev, stacks = made_up(holes=(30, 30, 30))
    assert all(reading(ev, stacks, m) is None for m in NEW)
    assert "no whole execution of the round program" in capsys.readouterr().out


def test_an_executable_without_scopes_gives_none_and_says_so(reading, capsys):
    ev, stacks = made_up(scoped=False)
    assert all(reading(ev, stacks, m) is None for m in NEW)
    out = capsys.readouterr().out
    assert "no operation of the round program carries a scope" in out
    assert out.count("nothing read") == 1  # one line, not one a metric


def test_the_median_ignores_one_slow_execution(reading):
    ev, stacks = made_up()
    ops = ev["trace"].devices[0]["ops"]
    # one execution's first crop runs 4 ns long, into the operation after it:
    # the median over the executions does not move
    first = ops.names.index("%crop = f32[4] fusion(...)")
    ops.end[first] += 4
    assert reading(ev, stacks, "transform_device_ms") == pytest.approx(10 / 1e6)


def test_executions_report_interval_coverage_and_the_gap_before():
    ev, _ = made_up(holes=(0, 30, 0))
    rows = scopes.executions(ev["trace"], 0, ev["window_ns"])
    assert [round(r["coverage"], 3) for r in rows] == [1.0, 0.881, 1.0]
    assert [r["gap_before"] for r in rows] == [2.0, 2.0, 2.0]  # after jit_take
    assert rows[0]["end"] - rows[0]["start"] == 222.0


def test_a_reader_never_raises(reading, capsys):
    ev, stacks = made_up()
    del ev["trace"].devices[0]["modules"]  # a trace of another make
    assert reading(ev, stacks, "forward_device_ms") is None
    assert "nothing read, KeyError" in capsys.readouterr().out


# -- the wire format ------------------------------------------------------------
def test_op_names_are_read_from_the_files_event_metadata(tmp_path):
    text = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } }
  event_metadata { key: 1 value { id: 1 name: "%conv = bf16[4] fusion(...)"
    stats { metadata_id: 7 str_value: "jit(f)/jvp(Convolution:conv1)/conv:" }
    stats { metadata_id: 8 uint64_value: 42 } } }
  event_metadata { key: 2 value { id: 2 name: "%viaref = f32[] add(...)"
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%bare = f32[] add(...)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(f)/update/sub:" } }
}
planes { id: 2 name: "/host:CPU" event_metadata { key: 1 value { id: 1 name: "x"
    stats { metadata_id: 7 str_value: "not a device" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } } }
planes { id: 3 name: "/device:TPU:1"
  event_metadata { key: 1 value { id: 1 name: "%conv = bf16[4] fusion(...)"
    stats { metadata_id: 1 str_value: "jit(f)/average/psum:Collective ops" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }
"""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert scopes.op_names(str(path)) == {
        0: {"%conv = bf16[4] fusion(...)": "jit(f)/jvp(Convolution:conv1)/conv",
            "%viaref = f32[] add(...)": "jit(f)/update/sub"},
        1: {"%conv = bf16[4] fusion(...)": "jit(f)/average/psum"},
    }


# -- on a trace recorded on the chip ------------------------------------------
RECORDED = os.path.join(DATA, "caffenet-train.scoped.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Three rounds of ``caffenet-train`` on the v5e, compiled from an empty
    cache so that the executable carries this PR's scopes, cut from this PR's
    traced run by ``data/make_trimmed_scoped_trace.py``."""
    path = tmp_path_factory.mktemp("trace") / "caffenet-train.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    config = files.load_json("benchmark", "configs", "caffenet.json")
    window = {"workers": 1, "tau": 10, "flops_per_round_and_worker":
              10 * 256 * flops.train_flops_per_image(config)}
    ev = evidence.collect(str(path), MARKS, window, files.peaks("TPU v5 lite"))
    ev["xplane_path"] = str(path)
    scopes._cache.clear()
    return ev


def read(ev, metric):
    return run.read_layer_metric(metric, ev)


def test_recorded_trace_holds_two_whole_executions(recorded):
    assert recorded["window_ns"] == (1985852649.0, 2471009546.0)
    rows = scopes.executions(recorded["trace"], 0, recorded["window_ns"])
    assert [(r["start"], r["end"]) for r in rows] == [
        (2147521513.0, 2307633284.0), (2309178193.0, 2469311273.0)]
    # the events cover all but 16 millionths of each; before each ran the
    # window copy jit_take, 3.3 us earlier
    assert [round(r["coverage"], 6) for r in rows] == [0.999984, 0.999984]
    assert [r["gap_before"] for r in rows] == [3286.0, 3351.0]
    assert len(scopes.op_names(recorded["xplane_path"])[0]) == 632


def test_recorded_trace_by_phase_closes_on_the_round_program(recorded):
    phases = {m: read(recorded, m) for m in NEW}
    assert phases == {
        "transform_device_ms": pytest.approx(2.8376371, rel=1e-6),
        "forward_device_ms": pytest.approx(3.7271304, rel=1e-6),
        "backward_device_ms": pytest.approx(8.5976542, rel=1e-6),
        # XLA fuses the SGD update into the weight gradients (backward)
        "update_device_ms": pytest.approx(0.0360208, rel=1e-5),
        # one worker: no all-reduce, and the mean's division is fused into
        # the `x[None]` after the scope, so it is read as unscoped
        "average_device_ms": pytest.approx(0.0054125, rel=1e-5),
        "matmul_device_ms": pytest.approx(9.8724790, rel=1e-6),
        "norm_device_ms": pytest.approx(1.0097025, rel=1e-6),
        "unscoped_device_ms": pytest.approx(0.8130085, rel=1e-6),
    }
    whole = sum(phases[m] for m in NEW[:4]) + phases["unscoped_device_ms"] \
        + phases["average_device_ms"] / 10
    rows = scopes.executions(recorded["trace"], 0, recorded["window_ns"])
    program = np.median([r["end"] - r["start"] for r in rows]) / 10 / 1e6
    assert whole == pytest.approx(program, rel=1e-4)
    # step_device_ms also counts the window copy (jit_take, 0.9%)
    assert read(recorded, "step_device_ms") == pytest.approx(16.1657235, rel=1e-6)
    assert whole == pytest.approx(read(recorded, "step_device_ms"), rel=0.01)


def test_recorded_trace_puts_the_named_operations_in_their_scopes(recorded):
    stacks = scopes.op_names(recorded["xplane_path"])[0]
    scope = {X.short_name(hlo): scopes.classify(op) for hlo, op in stacks.items()}
    assert scope["fusion.483 f32[96,3,11,11]"] == ("backward", "Convolution", "conv1")
    assert scope["fusion.497 f32[4096,9216]"] == ("backward", "InnerProduct", "fc6")
    assert scope["bitcast_dynamic-update-slice_fusion.2 f32[256,3,227,227]"] == (
        "transform", None, None)
    assert scope["copy.219 f32[256,3,227,227]"] == ("transform", None, None)
    assert scope["select-and-scatter.17 bf16[256,96,55,55]"] == (
        "backward", "Pooling", "pool1")
    # conv + bias + ReLU in one fusion: the convolution's
    assert scope["broadcast_maximum_fusion.5 bf16[256,96,55,55]"] == (
        "forward", "Convolution", "conv1")
    # the mirror's select fused with conv1's cast to bf16: read as conv1's
    assert scope["select_convert_fusion.2 bf16[256,3,227,227]"] == (
        "forward", "Convolution", "conv1")
    # the mean's division fused with the `x[None]` after the scope
    assert scope["divide_bitcast_fusion f32[1,4096,9216]"] == ("unscoped", None, None)


def test_recorded_trace_has_the_programs_spans_on_the_host_plane(recorded):
    host = recorded["trace"].host
    assert len(host["average"]) == len(host["execute"]) == 5
    # execute nests in average, average in the harness's trainer.round mark
    for outer, inner in (("trainer.round", "average"), ("average", "execute")):
        assert np.all(host[outer].start <= host[inner].start)
        assert np.all(host[inner].end <= host[outer].end)
