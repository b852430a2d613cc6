"""The sequence cell (``qwen3next-train-8k``): its rehearsal end to end at a
tiny size, every planted fault through the cell's own comparisons, its
operation count against a walk of the program's parameter shapes, its files
against ``BENCHMARK.json``, and the by-type reader."""

import ast
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, lm_flops
from benchmark.tests.conftest import ROOT, rehearse

CELL = "qwen3next-train-8k"
NEW_METRICS = {"gdn_device_ms", "attention_device_ms", "moe_route_device_ms",
               "moe_experts_device_ms", "head_device_ms", "gdn_mfu",
               "moe_experts_mfu"}


@pytest.fixture(scope="module")
def run():
    return rehearse(CELL)


def test_rehearsal_runs_to_its_result_line(run):
    assert run.rc == 0, run.stderr[-2000:]
    assert set(run.result) == {"correct", "attempted", "failed", "metrics", "device"}
    declared = {m["name"]: m["unit"] for m in files.metrics_of(CELL, "end_to_end")}
    assert {k: v["unit"] for k, v in run.result["metrics"].items()} == declared
    assert set(declared) == {"images_per_s", "mfu", "peak_hbm_gib", "setup_s"}
    assert all(v["value"] is None for v in run.result["metrics"].values())
    assert run.result["attempted"] >= 1 and run.result["failed"] == 0
    assert run.result["device"]["platform"] == "cpu"


def test_rehearsal_agrees_with_the_plain_reference(run):
    verdict = ast.literal_eval(run.note("correct: "))
    assert set(verdict) == {
        "forward_stated_dtype", "step_stated_dtype", "step_exact",
        "router_in_float32", "delta_rule_exact", "delta_rule_in_band",
        "first_loss_in_band", "no_compile_in_window", "nothing_failed"}
    # at the rehearsal's size the same bounds hold: only summation order
    # differs in float32, and bf16 is a rounding or two through four layers
    assert all(verdict.values()), verdict
    assert run.result["correct"] is True
    assert "tokens a second" in "\n".join(run.notes)
    before, after = [n for n in run.notes if "routing of one step's " in n]
    assert "before the first round" in before and "after round" in after
    assert "held_assignments_per_token" in before and "held_load_skew" in after


# each group of planted faults (``lm_checks.PLANTS``), the comparisons it is
# limited to, and the verdicts that have to come out False; every other
# verdict of the group stays True
PLANTED = {
    "state_unchanged:step": {"step_stated_dtype"},
    "half_batch:step": {"step_stated_dtype"},
    "bfloat16_update:step": {"step_stated_dtype"},
    "float8_reference": {"forward_stated_dtype", "step_stated_dtype",
                         "step_exact"},
    "bfloat16_router:float32": {"router_in_float32"},
    "bfloat16_state:float32": {"delta_rule_exact"},
}


@pytest.fixture(scope="module")
def planted():
    """``python -m benchmark.lm_checks --rehearse``: the cell's comparisons
    alone, once a group, in one process."""
    command = [sys.executable, "-m", "benchmark.lm_checks", "--workload", CELL,
               "--rehearse", "--seed", "3"]
    for group in PLANTED:
        command += ["--plant", group]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(command, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return dict(zip(PLANTED, lines)), proc.stdout


@pytest.mark.parametrize("group", PLANTED)
def test_a_planted_fault_comes_out_as_not_correct(planted, group):
    """What a training state left as it was, a dropped sequence, an update
    or a router or a decay or a state in a lower precision, and the
    reference in the precision below the stated one read: not correct, by
    the comparison that is there for it and by no other."""
    results, stdout = planted
    result = results[group]
    assert result["planted"] == sorted(group.partition(":")[0].split(","))
    assert result["correct"] is False
    failed = {k for k, ok in result["verdict"].items() if not ok}
    assert failed == PLANTED[group], stdout[-3000:]


def test_cell_and_its_files_are_in_the_table():
    work, config, traffic = files.cell(CELL)
    assert work["chips"] == 1 and work["traffic"] == "lm-resident-tau4-8k"
    assert traffic["kind"] == "lm-train-resident"
    assert (traffic["seq_len"], traffic["sequences_per_step"], traffic["tau"],
            traffic["partition_sequences"], traffic["zipf_exponent"]) == (
                8192, 2, 4, 2048, 1.0)
    entry = next(c for c in files.table()["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
    # every published width as published
    published = {
        "hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
        "num_key_value_heads": 2, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "num_experts": 512, "num_experts_per_tok": 10,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "rms_norm_eps": 1e-06, "full_attention_interval": 4}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["experts_held"],
            config["vocab_size"]) == (4, [0, 32], 18992)
    assert config["published"]["num_hidden_layers"] == 48
    per_layer = {m["name"]: m for m in files.table()["per_layer"]}
    assert NEW_METRICS <= set(per_layer)
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
    assert NEW_METRICS <= {m["name"] for m in files.metrics_of(CELL, "per_layer")}


def test_operation_count_against_a_walk_of_the_programs_shapes():
    """Every matrix the program holds is a projection a token passes once
    (2 operations a weight), the held experts at the expected share of
    tokens; what has no weights (the recurrence, the attention scores) and
    the width-4 convolution are added from their formulas."""
    from sparknet_tpu.models.hybrid_lm import HybridMoELM

    _, config, traffic = files.cell(CELL)
    t = traffic["seq_len"]
    model = HybridMoELM(config)
    held = config["experts_held"][1]
    share = config["num_experts_per_tok"] / config["num_experts"]
    by_type = dict.fromkeys(lm_flops.TYPES, 0.0)
    for group, shapes in model._group_blobs:
        layer = group.split("_")[-1]
        for shape in shapes:
            weights = 1
            for n in shape:
                weights *= n
            if len(shape) < 2 or group == "embed":
                continue  # vectors scale, the embedding gathers: no MXU work
            if layer == "experts":
                by_type["MoEExperts"] += 2 * weights * share
            elif layer == "router":
                by_type["MoERouter"] += 2 * weights
            elif layer == "shared":
                by_type["MoEShared"] += 2 * weights
            elif group == "head":
                by_type["LMHead"] += 2 * weights
            else:
                i = int(group[1:].split("_")[0])
                kind = ("GatedAttention" if model.is_attention_layer(i)
                        else "GatedDeltaNet")
                by_type[kind] += 2 * weights
    layers = config["num_hidden_layers"]
    attention_layers = sum(model.is_attention_layer(i) for i in range(layers))
    by_type["GatedDeltaNet"] += (layers - attention_layers) * (
        6 * config["linear_key_head_dim"] * config["linear_value_head_dim"]
        * config["linear_num_value_heads"])
    by_type["GatedAttention"] += attention_layers * (
        4 * config["num_attention_heads"] * config["head_dim"] * (t + 1) / 2)
    want = lm_flops.forward_flops_per_token_by_type(config, t)
    assert held == 32 and set(want) == set(by_type)
    for kind in want:
        assert by_type[kind] == pytest.approx(want[kind], rel=1e-12), kind
    # ISSUE 27's arithmetic: 460 MFLOP a token forward, 11.3 TFLOP a sequence
    assert sum(want.values()) == pytest.approx(460.48e6, rel=1e-4)
    assert lm_flops.train_flops_per_sequence(config, t) == pytest.approx(
        11.317e12, rel=1e-4)


def test_by_type_reader_finds_nothing_without_scopes():
    """On a trace with no scoped execution (here: no trace at all) the new
    reader returns None and raises nothing, as a parent commit that lacks the
    model's scopes makes it."""
    from benchmark.reducers import mfu_by_scope

    ev = {"xplane_path": os.path.join(ROOT, "no-such-file.xplane.pb"),
          "window_ns": (0.0, 1.0), "devices": [], "tau": 4,
          "peaks": {"bf16_flops_per_s": 1.97e14}}
    work, _, _ = files.cell(CELL)
    for name in ("gdn_mfu", "moe_experts_mfu"):
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json")))
        # the metric names the configuration and the traffic its count is
        # of (a cell is that pair), not a cell
        assert spec["reducer"] == "mfu_by_scope"
        assert (spec["args"]["config"], spec["args"]["traffic"]) == (
            work["config"], work["traffic"])
        assert mfu_by_scope.reduce(ev, **spec["args"]) is None
