"""The benchmark's LFM2 cell (``lfm2moe-train-8k``) beside its rehearsal
(``tests/test_benchmark_cells.py``): every planted fault through the cell's
own comparisons at the rehearsal's size, its operation count against a walk
of the program's parameter shapes, its files against ``BENCHMARK.json``, and
its reader on a trace without scopes.  Reads ``benchmark/``, edits nothing."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import files, lfm2_checks, lfm2_flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2moe-train-8k"
MFU = {"shortconv_mfu": ["ShortConv"], "lfm2_attention_mfu": ["Attention"],
       "dense_mlp_mfu": ["DenseMLP"]}
DEVICE_MS = {
    "shortconv_device_ms": ["ShortConv"],
    "lfm2_attention_device_ms": ["Attention"],
    "dense_mlp_device_ms": ["DenseMLP"],
    "lfm2_moe_route_device_ms": ["MoERouter"],
    "lfm2_moe_experts_device_ms": ["MoEExperts"],
    "lfm2_head_device_ms": ["Embedding", "LMHead"]}

# each group of planted faults, the comparisons it is limited to, and the
# verdicts that have to come out False; every other verdict stays True
PLANTED = {
    "state_unchanged:step": {"step_stated_dtype"},
    "half_batch:step": {"step_stated_dtype"},
    "bfloat16_update:step": {"step_stated_dtype"},
    "float8_reference": {"forward_stated_dtype", "step_stated_dtype",
                         "step_exact"},
    "bfloat16_router:float32": {"router_in_float32"},
    "biased_weights:float32": {"router_in_float32"},
    "bfloat16_conv:float32": {"short_conv_exact"},
    "dropped_tap:float32": {"short_conv_exact", "short_conv_in_band"},
    "swapped_gates:float32": {"short_conv_exact", "short_conv_in_band"},
}


@pytest.fixture(scope="module")
def planted():
    """``python -m benchmark.lfm2_checks --rehearse``: the cell's
    comparisons alone, unplanted and then once a group, in one process."""
    command = [sys.executable, "-m", "benchmark.lfm2_checks", "--workload",
               CELL, "--rehearse", "--seed", "3", "--plant", ""]
    for group in PLANTED:
        command += ["--plant", group]
    # the rehearsal sizes its own virtual devices; its programs are compile
    # time at these sizes, which LLVM's lowest level halves with the same
    # verdicts
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_backend_optimization_level=0"}
    proc = subprocess.run(command, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return dict(zip(["", *PLANTED], lines)), proc.stdout


def test_unplanted_comparisons_agree_with_the_plain_reference(planted):
    results, stdout = planted
    assert results[""]["planted"] == []
    assert set(results[""]["verdict"]) == {
        "forward_stated_dtype", "step_stated_dtype", "step_exact",
        "router_in_float32", "short_conv_exact", "short_conv_in_band"}
    assert results[""]["correct"] is True, stdout[-3000:]


@pytest.mark.parametrize("held, ok", [
    (32.0, True),  # the expectation: 8 of 64 experts, 256 of 2,048 a layer
    (17.0, True), (15.0, False),  # half of it is the band's lower edge
    (63.0, True), (65.0, False),  # twice it the rows of the grouped path
    (0.0, False),  # a router that walked away from its held experts
])
def test_the_windows_verdict_on_the_held_experts_load(held, ok):
    """``held_load_in_window`` on a state whose last step sent each held
    expert ``held`` of a layer's 2,048 assignments (512 tokens, top-4)."""
    import types

    import numpy as np

    _, config, _ = files.cell(CELL)
    load = np.zeros((1, 64), np.float32)
    load[0, :8] = held
    load[0, 8:] = (2048 - 8 * held) / 56
    even = np.full((1, 64), 32.0, np.float32)
    routers = ("l1_router", "l2_router")
    cell = types.SimpleNamespace(
        config=config, log=lambda message: None,
        model=types.SimpleNamespace(biased_routers=routers),
        state=types.SimpleNamespace(stats={
            "l1_router": [np.zeros((1, 64)), even],
            "l2_router": [np.zeros((1, 64)), load]}))
    assert lfm2_checks.held_load_in_window(cell) == {"held_load_in_window": ok}


@pytest.mark.parametrize("group", PLANTED)
def test_a_planted_fault_comes_out_as_not_correct(planted, group):
    """A state left as it was, a dropped sequence, an update or a router in
    a lower precision, weights gathered from the biased scores, a
    convolution in a lower precision, without a tap or with its gates
    swapped, and the reference in the precision below the stated one: not
    correct, by the comparison that is there for it and by no other."""
    results, stdout = planted
    result = results[group]
    assert result["planted"] == sorted(group.partition(":")[0].split(","))
    assert result["correct"] is False
    failed = {k for k, ok in result["verdict"].items() if not ok}
    assert failed == PLANTED[group], stdout[-3000:]


def test_an_unknown_plant_is_refused(monkeypatch):
    monkeypatch.setenv("LM_CHECK_PLANT", "bfloat16_state")  # Qwen3-Next's
    with pytest.raises(SystemExit, match="unknown"):
        lfm2_checks.planted(None)


def test_cell_and_its_files_are_in_the_table():
    work, config, traffic = files.cell(CELL)
    assert work["chips"] == 1
    assert work["traffic"] == "lm-resident-tau4-8k-lfm2"
    assert traffic["kind"] == "lm-train-resident-lfm2"
    assert (traffic["seq_len"], traffic["sequences_per_step"], traffic["tau"],
            traffic["partition_sequences"], traffic["zipf_exponent"]) == (
                8192, 2, 4, 2048, 1.0)
    entry = next(c for c in files.table()["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "experts_held", "vocab_size"]
    # every published width as published
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "conv_L_cache": 3, "conv_bias": False,
        "intermediate_size": 11776, "num_experts": 64,
        "num_experts_per_tok": 4, "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "routed_scaling_factor": 1,
        "use_expert_bias": True, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe",
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["layer_types"], config["experts_held"],
            config["vocab_size"]) == (
                5, 1, ["conv", "full_attention", "conv", "conv", "conv"],
                [0, 8], 8192)
    was = config["published"]
    assert (was["num_hidden_layers"], was["num_dense_layers"],
            was["num_experts"], was["vocab_size"]) == (40, 2, 64, 65536)
    assert len(was["layer_types"]) == 40
    assert was["layer_types"].count("full_attention") == 10
    # the cut is the model's layer 0 and its layers 2..5
    assert config["layer_types"] == [was["layer_types"][i]
                                     for i in (0, 2, 3, 4, 5)]
    assumed = " ".join(config["assumed"])
    for said in ("tie_word_embeddings", "1e-6", "expert_bias is no parameter",
                 "expert_bias_update_rate 0.001", "rate fixed at 3e-6"):
        assert said in assumed
    assert config["expert_bias_update_rate"] == 0.001
    assert config["solver"]["base_lr"] == 3e-6
    per_layer = {m["name"]: m for m in files.table()["per_layer"]}
    for name in [*MFU, *DEVICE_MS]:
        # the dense MLP's reader reads the Laguna cell's leading layer too
        assert per_layer[name]["workloads"] == [CELL, *(
            ["laguna-train-8k"] if name == "dense_mlp_device_ms" else [])]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
    reported = {m["name"] for m in files.metrics_of(CELL, "per_layer")}
    assert set(MFU) | set(DEVICE_MS) <= reported
    # Qwen3-Next's metrics are not this cell's
    assert not {"gdn_mfu", "attention_device_ms", "head_device_ms"} & reported


@pytest.mark.parametrize("name, types", [*MFU.items(), *DEVICE_MS.items()])
def test_a_metrics_file_names_its_reader_and_types(name, types):
    spec = files.load_json("benchmark", "layer_metrics", name + ".json")
    assert spec["args"]["types"] == types
    if name in MFU:
        work, _, _ = files.cell(CELL)
        assert spec["reducer"] == "lfm2_mfu_by_scope"
        assert (spec["args"]["config"], spec["args"]["traffic"]) == (
            work["config"], work["traffic"])
    else:
        assert spec["reducer"] == "device_ms_by_scope"
        assert spec["args"]["phases"] == ["forward", "backward"]
        assert spec["args"]["per"] == "step"


def test_operation_count_against_a_walk_of_the_programs_shapes():
    """Every matrix the program holds is a projection a token passes once
    (2 operations a weight), the held experts at the expected share of
    tokens, the tied embedding once as the head; the attention scores, which
    have no weights, are added from their formula."""
    from sparknet_tpu.models.hybrid_lm import MIXERS, HybridMoELM

    _, config, traffic = files.cell(CELL)
    t = traffic["seq_len"]
    model = HybridMoELM(config)
    share = config["num_experts_per_tok"] / config["num_experts"]
    by_type = dict.fromkeys(lfm2_flops.TYPES, 0.0)
    for group, shapes in model._group_blobs:
        layer = group.split("_")[-1]
        for shape in shapes:
            weights = 1
            for n in shape:
                weights *= n
            if len(shape) < 2:
                continue  # vectors scale or shift: no MXU work
            if group == "embed":  # gathered once, multiplied once as the head
                by_type["LMHead"] += 2 * weights
            elif layer == "experts":
                by_type["MoEExperts"] += 2 * weights * share
            elif layer == "router":
                by_type["MoERouter"] += 2 * weights
            elif layer == "mlp":
                by_type["DenseMLP"] += 2 * weights
            else:
                i = int(group[1:].split("_")[0])
                by_type[MIXERS[model.config["mixers"][i]]] += 2 * weights
    by_type["Attention"] += config["layer_types"].count("full_attention") * (
        4 * config["num_attention_heads"] * config["head_dim"] * (t + 1) / 2)
    want = lfm2_flops.forward_flops_per_token_by_type(config, t)
    assert set(want) == set(by_type)
    for kind in want:
        assert by_type[kind] == pytest.approx(want[kind], rel=1e-12), kind
    # ISSUE 31's arithmetic: 405.9 MFLOP a token forward, 9.97 TFLOP a
    # sequence trained, and its shares by layer type
    total = sum(want.values())
    assert total == pytest.approx(405.85e6, rel=1e-4)
    assert lfm2_flops.train_flops_per_sequence(config, t) == pytest.approx(
        9.974e12, rel=1e-4)
    shares = {k: round(100 * v / total) for k, v in want.items() if v}
    assert shares == {"ShortConv": 33, "Attention": 13, "DenseMLP": 36,
                      "MoERouter": 0, "MoEExperts": 9, "LMHead": 8}


def test_by_type_reader_finds_nothing_without_scopes():
    """On a trace with no scoped execution (here: no trace at all) the new
    reader returns None and raises nothing, as a parent commit that lacks the
    model's scopes makes it."""
    from benchmark.reducers import lfm2_mfu_by_scope

    ev = {"xplane_path": os.path.join(ROOT, "no-such-file.xplane.pb"),
          "window_ns": (0.0, 1.0), "devices": [], "tau": 4,
          "peaks": {"bf16_flops_per_s": 1.97e14}}
    for name in MFU:
        spec = files.load_json("benchmark", "layer_metrics", name + ".json")
        assert lfm2_mfu_by_scope.reduce(ev, **spec["args"]) is None
