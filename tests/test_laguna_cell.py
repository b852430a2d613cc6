"""The benchmark's Laguna cell (``laguna-train-8k``) beside its rehearsal
(``tests/test_benchmark_cells.py``): every planted fault through the cell's
own comparisons at the rehearsal's size, its operation count against a walk
of the program's parameter shapes, its files against ``BENCHMARK.json``, and
its reader on a trace without scopes.  Reads ``benchmark/``, edits
nothing."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import files, laguna_checks, laguna_flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "laguna-train-8k"
MFU = {"swa_attention_mfu": ["WindowAttention"]}
DEVICE_MS = {"swa_attention_device_ms": ["WindowAttention"]}
# the accepted readers of the scopes this model shares, each given the cell
SHARED = {
    "attention_device_ms": ["GatedAttention"],
    "moe_route_device_ms": ["MoERouter"],
    "moe_experts_device_ms": ["MoEExperts", "MoEShared"],
    "head_device_ms": ["Embedding", "LMHead"],
    "dense_mlp_device_ms": ["DenseMLP"]}
VERDICTS = {"forward_stated_dtype", "step_stated_dtype", "step_exact",
            "router_in_float32", "window_kernels_exact",
            "window_kernels_in_band"}
STEP = {"step_stated_dtype", "step_exact"}
WINDOW = {"window_kernels_exact", "window_kernels_in_band"}

# each group of planted faults, the comparisons it is limited to, and the
# verdicts that have to come out False; every other verdict stays True.  At
# the rehearsal's widths (hidden 32, a window of 7 in 24 or 40 tokens) the
# seeded attention is near flat, so a fault in it stays inside bfloat16's
# band (the forward comparison passes; on the chip, at the published widths,
# PERF.md section 6 has what each reads) and shows in the float32
# comparisons: ``step_exact`` and the windowed kernels' own
PLANTED = {
    "state_unchanged:step": {"step_stated_dtype"},
    "half_batch:step": {"step_stated_dtype"},
    "bfloat16_update:step": {"step_stated_dtype"},
    "float8_reference:forward,step": {"forward_stated_dtype", *STEP},
    "bfloat16_router:float32": {"router_in_float32"},
    "biased_weights:float32": {"router_in_float32"},
    "window_511:step,window": {"step_exact", *WINDOW},
    "window_513:step,window": {"step_exact", *WINDOW},
    "full_causal_sliding:step,window": {*STEP, *WINDOW},
    "plain_rotary_full:step,window": STEP,
    "whole_head_rotary_full:step": STEP,
    "per_head_scalar_gate:step": {"step_exact"},
    "full_heads_sliding:step": STEP,
    "no_routed_scaling:step,float32": {*STEP, "router_in_float32"},
    "bfloat16_reference:step,window": {"step_exact", "window_kernels_exact"},
}


@pytest.fixture(scope="module")
def planted():
    """``python -m benchmark.laguna_checks --rehearse``: the cell's
    comparisons alone, unplanted and then once a group, in one process."""
    command = [sys.executable, "-m", "benchmark.laguna_checks", "--workload",
               CELL, "--rehearse", "--seed", "3", "--plant", ""]
    for group in PLANTED:
        command += ["--plant", group]
    # the rehearsal sizes its own virtual devices; its programs are compile
    # time at these sizes, which LLVM's lowest level halves with the same
    # verdicts
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_backend_optimization_level=0"}
    proc = subprocess.run(command, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return dict(zip(["", *PLANTED], lines)), proc.stdout


def test_unplanted_comparisons_agree_with_the_plain_reference(planted):
    results, stdout = planted
    assert results[""]["planted"] == []
    assert set(results[""]["verdict"]) == VERDICTS
    assert results[""]["correct"] is True, stdout[-3000:]


@pytest.mark.parametrize("group", PLANTED)
def test_a_planted_fault_comes_out_as_not_correct(planted, group):
    """A state left as it was, half the batch, an update or a router in a
    lower precision, the reference in the precision below the stated one,
    and the faults of the window, the positions, the gate, the heads and
    this family's router: not correct, by the comparisons that are there
    for it and by no other."""
    results, stdout = planted
    result = results[group]
    assert result["planted"] == sorted(group.partition(":")[0].split(","))
    assert result["correct"] is False
    failed = {k for k, ok in result["verdict"].items() if not ok}
    assert failed == PLANTED[group], stdout[-3000:]


def test_every_plant_is_a_group_and_an_unknown_one_is_refused(monkeypatch):
    assert {g.partition(":")[0] for g in PLANTED} == set(
        laguna_checks.ALL_PLANTS)
    monkeypatch.setenv("LM_CHECK_PLANT", "dropped_tap")  # no convolution here
    with pytest.raises(SystemExit, match="unknown"):
        laguna_checks.planted(None)


def test_cell_and_its_files_are_in_the_table():
    work, config, traffic = files.cell(CELL)
    assert work["chips"] == 1
    assert work["traffic"] == "lm-resident-tau4-8k-laguna"
    assert traffic["kind"] == "lm-train-resident-laguna"
    # the same tokens a step, Zipf and tau as the three other 8k cells
    for other in ("lm-resident-tau4-8k", "lm-resident-tau4-8k-lfm2",
                  "lm-resident-tau4-8k-kanana"):
        theirs = files.load_json("benchmark", "traffic", other + ".json")
        assert {k for k in traffic if traffic[k] != theirs.get(k)} == {
            "kind", "what"}
    entry = next(c for c in files.table()["configs"]
                 if c["name"] == "laguna-xs.2")
    assert entry["source"] == config["source"]
    was = config["published"]
    assert (was["num_hidden_layers"], was["num_experts"],
            was["vocab_size"]) == (40, 256, 100352)
    assert config["experts_held"][1] * 16 == config["num_experts"]
    assumed = " ".join(config["assumed"])
    for said in ("rate fixed at", "expert_bias_update_rate 0.001",
                 "the embedding normal(0, 1)", "multi-token-prediction",
                 "1e-20", "i - 512 < j <= i", "low = 5, high = 16",
                 "elementwise sigmoid output gate", "RMSNorm over each head"):
        assert said in assumed, said
    check = config["check"]
    assert check["seq_len"] == traffic["seq_len"]
    # every comparison meets both edges of the band: a query block of the
    # kernels' 512 meets the window's lower edge and the diagonal
    assert min(check["seq_len"], check["step_seq_len"]) >= 2 * config[
        "sliding_window"]
    for key in check:
        if key.startswith("why_"):
            assert len(check[key]) > 100, key
    per_layer = {m["name"]: m for m in files.table()["per_layer"]}
    for name in [*MFU, *DEVICE_MS]:
        assert per_layer[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
    for name in SHARED:
        assert per_layer[name]["workloads"][-1] == CELL
    reported = {m["name"] for m in files.metrics_of(CELL, "per_layer")}
    assert set(MFU) | set(DEVICE_MS) | set(SHARED) <= reported
    assert not {"gdn_mfu", "mla_attention_mfu", "dsa_attention_mfu"} & reported
    # one cell more, on one chip: the four-chip cells stay inside the quarter
    cells = files.table()["workloads"]
    assert cells[-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("name, types", [
    *MFU.items(), *DEVICE_MS.items(), *SHARED.items()])
def test_a_metrics_file_names_its_reader_and_types(name, types):
    spec = files.load_json("benchmark", "layer_metrics", name + ".json")
    assert spec["args"]["types"] == types
    if name in MFU:
        work, _, _ = files.cell(CELL)
        assert spec["reducer"] == "laguna_mfu_by_scope"
        assert (spec["args"]["config"], spec["args"]["traffic"]) == (
            work["config"], work["traffic"])
    else:
        assert spec["reducer"] == "device_ms_by_scope"
        assert spec["args"]["phases"] == ["forward", "backward"]
        assert spec["args"]["per"] == "step"


def test_operation_count_against_a_walk_of_the_programs_shapes():
    """Every matrix the program holds is a projection a token passes once (2
    operations a weight), the held experts at the expected share of tokens;
    the attention's two products, which have no weights, are added from
    their formula over the keys a query sees: ``(T + 1) / 2`` on a full
    layer, ``min(t + 1, 512)`` for token ``t`` on a sliding one."""
    from sparknet_tpu.models.hybrid_lm import MIXERS, HybridMoELM

    _, config, traffic = files.cell(CELL)
    t = traffic["seq_len"]
    model = HybridMoELM(config)
    share = config["num_experts_per_tok"] / config["num_experts"]
    by_type = dict.fromkeys(laguna_flops.TYPES, 0.0)
    kinds = {"head": "LMHead", "router": "MoERouter", "mlp": "DenseMLP",
             "shared": "MoEShared"}
    for group, shapes in model._group_blobs:
        layer, _, part = group.partition("_")
        for shape in shapes:
            weights = 1
            for n in shape:
                weights *= n
            if len(shape) < 2 or group == "embed":
                continue  # vectors scale, the embedding is gathered
            if part == "experts":
                by_type["MoEExperts"] += 2 * weights * share
            elif part == "mixer":
                by_type[MIXERS[model.config["mixers"][int(layer[1:])]]] += (
                    2 * weights)
            else:
                by_type[kinds[group if group == "head" else part]] += (
                    2 * weights)
    for i, kind in enumerate(config["layer_types"]):
        heads = config["num_attention_heads_per_layer"][i]
        keys = (sum(min(s + 1, 512) for s in range(t)) / t
                if kind == "sliding_attention" else (t + 1) / 2)
        by_type[laguna_flops.SCOPE[kind]] += 2 * 2 * heads * 128 * keys
    want = laguna_flops.forward_flops_per_token_by_type(config, t)
    assert set(want) == set(by_type)
    for kind in want:
        assert by_type[kind] == pytest.approx(want[kind], rel=1e-12), kind
    # the arithmetic: a sliding mixer's projections 109 and window
    # 16 MFLOP a token, a full one's 84 + 101; the sliding mixers about 40%
    # of the counted work, the full ones about 39%, the dense MLP 11%, the
    # head 5.5%, the experts with router and shared expert 4.5%
    total = sum(want.values())
    assert want["WindowAttention"] / 3 == pytest.approx(
        109.05e6 + 16.25e6, rel=1e-3)
    assert want["GatedAttention"] / 2 == pytest.approx(
        83.89e6 + 100.68e6, rel=1e-3)
    assert want["WindowAttention"] / total == pytest.approx(0.400, abs=2e-3)
    assert want["GatedAttention"] / total == pytest.approx(0.393, abs=2e-3)
    assert want["DenseMLP"] / total == pytest.approx(0.107, abs=2e-3)
    assert want["LMHead"] / total == pytest.approx(0.055, abs=2e-3)
    assert (want["MoEExperts"] + want["MoEShared"] + want["MoERouter"]) / (
        total) == pytest.approx(0.045, abs=2e-3)
    # a held expert sees 512 of a step's tokens, a sixteenth of its deployed
    per_expert = 2 * t * config["num_experts_per_tok"] / config["num_experts"]
    assert per_expert == 512 and per_expert * 16 == 8192


def test_the_kinds_parts_are_the_configurations():
    from benchmark.kinds import lm_train_resident_laguna as kind
    from sparknet_tpu.models.hybrid_lm import HybridMoELM

    _, config, _ = files.cell(CELL)
    parts = kind.parameters_by_part(HybridMoELM(config))
    held = config["held_here"]["by_part"]
    assert parts == {
        "embedding_head_final_norm": held["embedding_head_final_norm"],
        "norms": 5 * held["two_norms_a_layer"],
        "gated_attention": 2 * held["full_attention_mixer"],
        "window_attention": 3 * held["sliding_attention_mixer"],
        "mlp": held["dense_mlp"], "routed_block": 4 * held["routed_block"]}
    assert sum(parts.values()) == config["held_here"]["parameters"]


def test_by_type_reader_finds_nothing_without_scopes():
    """On a trace with no scoped execution (here: no trace at all) the new
    reader returns None and raises nothing, as a parent commit that lacks the
    model's scopes makes it."""
    from benchmark.reducers import laguna_mfu_by_scope

    ev = {"xplane_path": os.path.join(ROOT, "no-such-file.xplane.pb"),
          "window_ns": (0.0, 1.0), "devices": [], "tau": 4,
          "peaks": {"bf16_flops_per_s": 1.97e14}}
    for name in MFU:
        spec = files.load_json("benchmark", "layer_metrics", name + ".json")
        assert laguna_mfu_by_scope.reduce(ev, **spec["args"]) is None
