"""Every program the trainer builds keeps its own account
(``sparknet_tpu/obs/program.py``): one record per program and signature,
kept with or without a sink; spans, memory marks and gauges where someone
listens; and nothing at all on a round nobody observes."""

import json
import os
import subprocess
import sys
import urllib.request

import jax
import numpy as np
import pytest

from sparknet_tpu import config, obs
from sparknet_tpu.analysis import registry
from sparknet_tpu.obs import program as obs_program, trace as obs_trace
from sparknet_tpu.parallel import (
    AllReduceTrainer,
    ParameterAveragingTrainer,
    make_mesh,
)
from sparknet_tpu.solver import Solver

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TAU, BATCH = 2, 4
RECORD_FIELDS = {
    "program", "trace_lower_s", "compile_s", "cache", "temp_bytes",
    "argument_bytes", "output_bytes", "alias_bytes", "code_bytes", "shapes",
}
MARK_FIELDS = {"at", "t_s", *obs_program.MEMORY_KINDS}

NET = """
name: "tiny"
layer { name: "data" type: "HostData" top: "data" top: "label"
  java_data_param { shape { dim: 4 dim: 6 } shape { dim: 4 } } }
layer { name: "fc1" type: "InnerProduct" bottom: "data" top: "h"
  inner_product_param { num_output: 8 weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "h" top: "h" }
layer { name: "fc2" type: "InnerProduct" bottom: "h" top: "logits"
  inner_product_param { num_output: 3 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label"
  top: "loss" }
"""


def _solver():
    return Solver(
        config.parse_solver_prototxt(
            'base_lr: 0.01 lr_policy: "fixed" momentum: 0.9'),
        net_param=config.parse_net_prototxt(NET),
    )


def _trainer(workers=2):
    mesh = make_mesh({"dp": workers}, devices=jax.devices()[:workers])
    return ParameterAveragingTrainer(_solver(), mesh)


def _batch(workers=2, tau=TAU, batch=BATCH):
    rng = np.random.RandomState(0)
    return {
        "data": rng.randn(workers, tau, batch, 6).astype(np.float32),
        "label": rng.randint(0, 3, (workers, tau, batch)).astype(np.float32),
    }


@pytest.fixture(autouse=True)
def fresh_account():
    obs._reset_training_metrics_for_tests()
    obs_program._reset_for_tests()
    yield
    obs.uninstall_tracer()
    obs._reset_training_metrics_for_tests()


def _named(name):
    return [r for r in obs.programs() if r["program"] == name]


# -- the record --------------------------------------------------------


def test_one_record_per_program_and_signature_with_every_field():
    trainer = _trainer()
    state = trainer.init_state(seed=0)
    assert obs.programs() == []  # several workers stack on the host
    state, _ = trainer.round(state, _batch())
    (record,) = obs.programs()
    assert set(record) == RECORD_FIELDS and record["program"] == "round"
    assert record["trace_lower_s"] > 0 and record["compile_s"] > 0
    assert record["cache"] in ("hit", "miss", "off")
    for kind in obs_program.BYTE_KINDS:
        assert isinstance(record[kind + "_bytes"], int)
    assert record["argument_bytes"] > 0
    # the signature a call is keyed on: the batches, with where they lay
    assert record["shapes"] == (
        "float32[2,2,4,6]@host", "float32[2,2,4]@host")
    state, _ = trainer.round(state, _batch())
    assert len(obs.programs()) == 1


def test_one_worker_stacks_its_state_through_a_program():
    trainer = _trainer(workers=1)
    state = trainer.init_state(seed=0)
    (record,) = obs.programs()
    assert record["program"] == "stack_state"
    assert record["alias_bytes"] == record["argument_bytes"] > 0  # donated
    assert jax.tree_util.tree_leaves(state.params)[0].shape[0] == 1


def test_changed_batch_shape_is_a_second_round_record_and_counts():
    tm = obs.enable_training_metrics()
    trainer = _trainer()
    state = trainer.init_state(seed=0)
    state, _ = trainer.round(state, _batch())
    state, _ = trainer.round(state, _batch(tau=3))
    first, second = _named("round")
    assert first["shapes"] != second["shapes"]
    assert second["shapes"][0] == "float32[2,3,4,6]@host"
    # (where an earlier test file of this process turned the persistent cache
    # on, one build can be a hit and the other a miss: count over both)
    builds = lambda: sum(  # noqa: E731
        tm.program_builds.labels("round", cache).value
        for cache in {first["cache"], second["cache"]})
    assert builds() == 2
    state, _ = trainer.round(state, _batch(tau=3))
    state, _ = trainer.round(state, _batch())
    assert builds() == 2
    assert len(obs.programs()) == 2


def test_the_round_compiles_once_over_build_and_first_call():
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _, fun_name="", **kw: compiled.append(fun_name)
        if event == _COMPILE_EVENT else None
    )
    trainer = _trainer()
    state = trainer.init_state(seed=0)
    batch = _batch()
    trainer.compile_round(state, batch)
    assert len(_named("round")) == 1
    state, losses = trainer.round(state, batch)
    jax.block_until_ready(losses)
    assert compiled.count("jit(round_body)") == 1
    assert trainer._round._cache_size() == 1
    assert len(_named("round")) == 1  # the call found it built


def test_the_solvers_own_programs_keep_records_too():
    solver = _solver()
    state = solver.init_state(0)
    one = {k: v[0] for k, v in _batch().items()}
    state, _ = solver.step(state, one)
    state, _ = solver.step_repeat(
        state, {k: v[0] for k, v in one.items()}, 3)
    solver.test_and_store_result(state, one)
    assert [r["program"] for r in obs.programs()] == [
        "step", "step_repeat", "forward_test"]
    # a static argument is part of the signature, as itself
    assert _named("step_repeat")[0]["shapes"][-1] == "3"


def test_the_second_trainer_builds_through_the_same_step():
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    trainer = AllReduceTrainer(_solver(), mesh)
    state = trainer.init_state(seed=0)
    batch = {k: v.reshape((TAU, 2 * BATCH) + v.shape[3:])
             for k, v in _batch().items()}
    state, _ = trainer.step(state, batch)
    state, _ = trainer.step(state, batch)
    (record,) = obs.programs()
    assert record["program"] == "sync_round"
    ats = [m["at"] for m in obs.memory_marks()]
    assert ats == ["init_state:enter", "init_state", "built:sync_round"]


# -- the cache verdict ---------------------------------------------------


def test_cache_reads_off_miss_then_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    def built(seed):
        # a fresh jit of the same computation: the in-process caches miss,
        # the persistent one (once on) serves it
        prog = obs.Program("probe", jax.jit(lambda x: x * 2.0 + seed))
        prog(np.ones((4,), np.float32))
        return obs.programs()[-1]["cache"]

    before = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
        jax.config.jax_persistent_cache_min_entry_size_bytes,
    )
    try:
        # whatever an earlier test of this process left: no directory, off
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
        assert built(1.0) == "off"
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        assert built(2.0) == "miss"
        assert built(2.0) == "hit"
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", before[1])
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", before[2])
        compilation_cache.reset_cache()


# -- the hot path nobody observes ----------------------------------------


def test_an_unobserved_round_reads_no_memory_and_feeds_no_sink(monkeypatch):
    trainer = _trainer()
    state = trainer.init_state(seed=0)
    batch = _batch()
    trainer.compile_round(state, batch)
    before = obs.programs(), obs.memory_marks()
    calls = {"memory_stats": 0, "instant": 0, "span": 0}

    def counting(name):
        def bump(*a, **k):
            calls[name] += 1
            return {}
        return bump

    monkeypatch.setattr(obs_program, "_stats", counting("memory_stats"))
    monkeypatch.setattr(obs_trace.Tracer, "instant", counting("instant"))
    monkeypatch.setattr(obs_trace._Span, "__init__", counting("span"))
    assert obs.span("average", round=0) is obs_trace._NULL_SPAN
    for _ in range(2):
        state, losses = trainer.round(state, batch)
    jax.block_until_ready(losses)
    assert calls == {"memory_stats": 0, "instant": 0, "span": 0}
    assert (obs.programs(), obs.memory_marks()) == before


# -- spans and marks, where a sink is installed ---------------------------


def _traced_run(workers=1):
    tracer = obs.install_tracer(obs.Tracer())
    try:
        trainer = _trainer(workers)
        state = trainer.init_state(seed=0)
        for _ in range(2):
            state, losses = trainer.round(state, _batch(workers))
        jax.block_until_ready(losses)
    finally:
        obs.uninstall_tracer()
    events = tracer.events()
    spans = [e for e in events if e.get("ph") == "X"]
    marks = [e for e in events if e.get("ph") == "i" and e["name"] == "memory"]
    return spans, marks


def test_build_spans_carry_the_program_and_what_it_holds():
    spans, _ = _traced_run()
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert all(e["cat"] == "build" for n in
               ("build", "trace_lower", "compile", "init_state")
               for e in by_name[n])
    builds = {e["args"]["program"]: e for e in by_name["build"]}
    assert set(builds) == {"stack_state", "round"}
    args = builds["round"]["args"]
    assert set(args) == {"program", "cache"} | {
        k + "_bytes" for k in obs_program.BYTE_KINDS}
    record = next(r for r in obs.programs() if r["program"] == "round")
    assert args["temp_bytes"] == record["temp_bytes"]
    # the children are the build's of the same program on the same thread
    for child in ("trace_lower", "compile"):
        (mine,) = [e for e in by_name[child]
                   if e["args"] == {"program": "round"}]
        parent = builds["round"]
        assert mine["tid"] == parent["tid"]
        assert parent["ts"] <= mine["ts"]
        assert mine["ts"] + mine["dur"] <= parent["ts"] + parent["dur"] + 1
    (init,) = by_name["init_state"]
    assert init["args"] == {"workers": 1}
    assert len(by_name["average"]) == 2


def test_memory_marks_say_where_and_a_round_marks_only_when_observed():
    _, marks = _traced_run()
    assert all(e["cat"] == "memory" and set(e["args"]) == MARK_FIELDS - {"t_s"}
               for e in marks)
    assert [e["args"]["at"] for e in marks] == [
        "init_state:enter", "built:stack_state", "init_state",
        "built:round", "round", "round",
    ]
    # kept beside the records: the build-time marks only, with their time
    kept = obs.memory_marks()
    assert [m["at"] for m in kept] == [
        "init_state:enter", "built:stack_state", "init_state", "built:round"]
    assert all(set(m) == MARK_FIELDS for m in kept)
    assert kept[0]["t_s"] <= kept[2]["t_s"]
    # the CPU reports nothing: nulls, and no further branch
    assert all(m[k] is None for m in kept for k in obs_program.MEMORY_KINDS)


def test_a_mark_reads_the_fullest_device(monkeypatch):
    fake = {
        0: {"bytes_in_use": 5, "peak_bytes_in_use": 9,
            "bytes_reserved": 1, "peak_bytes_reserved": 2, "bytes_limit": 99},
        1: {"bytes_in_use": 7, "peak_bytes_in_use": 8,
            "bytes_reserved": 3, "peak_bytes_reserved": 6, "bytes_limit": 99},
    }
    monkeypatch.setattr(obs_program, "_stats", lambda d: fake[d.id])
    devices = jax.devices()[:2]
    assert obs_program.device_memory(devices) == {
        "in_use": 7, "peak_in_use": 8, "reserved": 3, "peak_reserved": 6,
        "limit": 99,
    }
    assert obs_program.device_memory(devices[:1])["peak_in_use"] == 9
    obs_program.mark_memory("init_state", devices)
    (mark,) = obs.memory_marks()
    assert mark["at"] == "init_state" and mark["in_use"] == 7


def _host_events(logdir):
    from jax.profiler import ProfileData

    (path,) = [
        os.path.join(root, f)
        for root, _, files in os.walk(logdir) for f in files
        if f.endswith(".xplane.pb")
    ]
    return [
        (ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for ev in line.events
    ]


def test_build_spans_are_host_events_of_the_profilers_trace(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _traced_run()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    for name in ("build", "trace_lower", "compile"):
        programs = sorted(
            str(stats.get("program")) for n, stats in events if n == name)
        assert programs == ["round", "stack_state"], (name, programs)
    (init,) = [stats for n, stats in events if n == "init_state"]
    assert str(init.get("workers")) == "1"


# -- counters and gauges ---------------------------------------------------


def test_the_new_families_render_on_metrics(monkeypatch):
    monkeypatch.setattr(obs_program, "_stats", lambda d: {
        "bytes_in_use": 1024, "peak_bytes_in_use": 4096,
        "bytes_reserved": 512, "peak_bytes_reserved": 2048,
        "bytes_limit": 1 << 20,
    })
    run = obs.start(metrics=True, port=0, echo=None)
    try:
        trainer = _trainer()
        state = trainer.init_state(seed=0)
        trainer.round(state, _batch())
        host, port = run.address
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics").read().decode()
    finally:
        run.close()
    (record,) = obs.programs()
    cache = record["cache"]
    assert (f'sparknet_program_builds_total{{program="round",cache="{cache}"}}'
            " 1") in body
    for stage in ("trace_lower", "compile"):
        assert ('sparknet_program_build_seconds{program="round",'
                f'stage="{stage}"}} ') in body
    for kind in obs_program.BYTE_KINDS:
        assert ('sparknet_program_bytes{program="round",'
                f'kind="{kind}"}} {record[kind + "_bytes"]}') in body
    assert 'sparknet_device_memory_bytes{kind="in_use"} 1024' in body
    assert 'sparknet_device_memory_bytes{kind="peak_in_use"} 4096' in body
    assert 'sparknet_device_memory_bytes{kind="reserved"} 512' in body
    assert 'sparknet_device_memory_bytes{kind="peak_reserved"} 2048' in body
    assert 'sparknet_device_memory_bytes{kind="limit"} 1048576' in body


def test_the_device_memory_gauge_reads_zero_where_nothing_reports():
    tm = obs.enable_training_metrics()
    body = tm.registry.render()
    for kind in obs_program.MEMORY_KINDS:
        assert f'sparknet_device_memory_bytes{{kind="{kind}"}} 0' in body


@pytest.mark.parametrize(
    "gone", ["sparknet_jit_cache_size", "sparknet_device_bytes"])
def test_the_gauges_this_replaces_are_gone(gone):
    assert gone not in registry.CANONICAL_METRICS
    assert gone not in obs.enable_training_metrics().registry.render()
    assert not hasattr(obs, "track_jit") and not hasattr(obs, "_device_bytes")
    for family, labels in (
        ("sparknet_program_builds_total", ("program", "cache")),
        ("sparknet_program_build_seconds", ("program", "stage")),
        ("sparknet_program_bytes", ("program", "kind")),
        ("sparknet_device_memory_bytes", ("kind",)),
    ):
        assert registry.CANONICAL_METRICS[family] == labels
    assert registry.CANONICAL_SPANS["build"] == {
        "build", "trace_lower", "compile", "init_state"}


# -- the benchmark's readers, rehearsed ---------------------------------------

_REHEARSAL = """
import json, sys
sys.path.insert(0, {repo!r})
from sparknet_tpu.obs import program
# the CPU reports no memory: a runtime that does, and grows at first
asked = []
def stats(device):
    asked.append(device)
    n = min(len(asked), 4) << 20
    return {{"bytes_in_use": n, "peak_bytes_in_use": 2 * n,
             "bytes_reserved": n // 2, "peak_bytes_reserved": n,
             "bytes_limit": 1 << 40}}
program._stats = stats
from benchmark import files, run
rc = run.main(["--workload", {cell!r}, "--seed", "2147489301",
               "--seconds", "1", "--trace", "0", "--rehearse"])
names = [m["name"] for m in files.metrics_of({cell!r}, "per_layer")
         if files.load_json("benchmark", "layer_metrics",
                            m["name"] + ".json")["reducer"] == "program_record"]
print("READ " + json.dumps(
    {{"rc": rc, "values": {{n: run.read_layer_metric(n, {{}}) for n in names}}}}))
"""


@pytest.mark.parametrize("cell", ["caffenet-train", "lfm2moe-train-8k"])
def test_every_new_reader_finds_a_number_in_a_rehearsed_cell(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the rehearsal sizes its own virtual devices
    proc = subprocess.run(
        [sys.executable, "-c", _REHEARSAL.format(repo=_REPO, cell=cell)],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    (line,) = [ln for ln in proc.stdout.splitlines() if ln.startswith("READ ")]
    read = json.loads(line[len("READ "):])
    assert read["rc"] == 0
    values = read["values"]
    assert set(values) == {
        "round_trace_lower_s", "round_compile_s", "build_cache_hit_share",
        "init_state_s", "state_gib", "round_temp_gib", "round_code_mib",
        "live_peak_gib", "reserved_peak_gib",
    }
    assert all(isinstance(v, float) for v in values.values()), values
    assert values["round_trace_lower_s"] > 0 and values["round_compile_s"] > 0
    assert values["init_state_s"] > 0 and values["state_gib"] > 0
    assert 0 <= values["build_cache_hit_share"] <= 1
    # the two addends of peak_hbm_gib, as the fake runtime grew them
    assert values["live_peak_gib"] == 2 * values["reserved_peak_gib"]
