"""The benchmark's Kanana cell (``kanana2-train-8k``) and the one-chip cell
that came with it (``caffenet-train-tau50``) beside their rehearsals
(``tests/test_benchmark_cells.py``): every planted fault through the cell's
own comparisons at the rehearsal's size, its operation count against a walk
of the program's parameter shapes, its files against ``BENCHMARK.json`` and
the catalog's keys, and its reader on a trace without scopes.  Reads
``benchmark/``, edits nothing."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import files, kanana_checks, kanana_flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kanana2-train-8k"
MFU = {"mla_latent_mfu": ["MLALatent"], "mla_attention_mfu": ["MLAAttention"]}
DEVICE_MS = {
    "mla_latent_device_ms": ["MLALatent"],
    "mla_attention_device_ms": ["MLAAttention"],
    "kanana_dense_mlp_device_ms": ["DenseMLP"],
    "kanana_moe_route_device_ms": ["MoERouter"],
    "kanana_moe_experts_device_ms": ["MoEExperts", "MoEShared"],
    "kanana_head_device_ms": ["Embedding", "LMHead"]}
VERDICTS = {"forward_stated_dtype", "step_stated_dtype", "step_exact",
            "router_in_float32"}
STEP = {"step_stated_dtype", "step_exact"}

# each group of planted faults, the comparisons it is limited to, and the
# verdicts that have to come out False; every other verdict stays True.  At
# the rehearsal's widths (hidden 32) the seeded scores are near zero and the
# attention near flat, so a fault in it moves the logits by 6e-6 to 3e-3:
# under bfloat16's band (the forward comparison passes; on the chip, at the
# published widths, PERF.md section 6 has what each reads), over the float32
# comparison's (``step_exact``, which reads 2e-8 unplanted), and, but for
# the scale, enough in some leaf's gradient for ``step_stated_dtype``
PLANTED = {
    "state_unchanged:step": {"step_stated_dtype"},
    "half_batch:step": {"step_stated_dtype"},
    "bfloat16_update:step": {"step_stated_dtype"},
    "float8_reference:forward,step": {"forward_stated_dtype", *STEP},
    "bfloat16_router:float32": {"router_in_float32"},
    "biased_weights:float32": {"router_in_float32"},
    "scale_128:step": {"step_exact"},
    "rotary_on_nope:step": STEP,
    "rotate_half_keys:step": STEP,
    "rope_key_per_head:step": STEP,
    "no_latent_norm:step": STEP,
    "no_routed_scaling:step,float32": {*STEP, "router_in_float32"},
    "gated_shared_expert:step": STEP,
}


@pytest.fixture(scope="module")
def planted():
    """``python -m benchmark.kanana_checks --rehearse``: the cell's
    comparisons alone, unplanted and then once a group, in one process."""
    command = [sys.executable, "-m", "benchmark.kanana_checks", "--workload",
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
    and the seven faults of the latent attention and this family's
    feed-forward: not correct, by the comparisons that are there for it and
    by no other."""
    results, stdout = planted
    result = results[group]
    assert result["planted"] == sorted(group.partition(":")[0].split(","))
    assert result["correct"] is False
    failed = {k for k, ok in result["verdict"].items() if not ok}
    assert failed == PLANTED[group], stdout[-3000:]


def test_an_unknown_plant_is_refused(monkeypatch):
    monkeypatch.setenv("LM_CHECK_PLANT", "dropped_tap")  # no convolution here
    with pytest.raises(SystemExit, match="unknown"):
        kanana_checks.planted(None)
    assert set(kanana_checks.PLANTS) < set(kanana_checks.ALL_PLANTS)


def test_cell_and_its_files_are_in_the_table():
    work, config, traffic = files.cell(CELL)
    assert work["chips"] == 1
    assert work["traffic"] == "lm-resident-tau4-8k-kanana"
    assert traffic["kind"] == "lm-train-resident-kanana"
    # the same tokens a step, Zipf and tau as the two other 8k cells
    for other in ("lm-resident-tau4-8k", "lm-resident-tau4-8k-lfm2"):
        theirs = files.load_json("benchmark", "traffic", other + ".json")
        assert {k for k in traffic if traffic[k] != theirs.get(k)} == {
            "kind", "what"}
    assert (traffic["seq_len"], traffic["sequences_per_step"], traffic["tau"],
            traffic["partition_sequences"], traffic["zipf_exponent"],
            traffic["workers"]) == (8192, 2, 4, 2048, 1.0, 1)
    entry = next(c for c in files.table()["configs"]
                 if c["name"] == "kanana-2-30b-a3b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
    was = config["published"]
    assert (was["num_hidden_layers"], was["n_routed_experts"],
            was["vocab_size"]) == (48, 128, 128256)
    assert config["vocab_size"] * 8 == was["vocab_size"]
    assert config["experts_held"][1] * 8 == config["n_routed_experts"]
    assumed = " ".join(config["assumed"])
    for said in ("rate fixed at", "expert_bias_update_rate 0.001",
                 "the embedding normal(0, 1)", "multi-token-prediction",
                 "1e-20", "kv_b_proj head-major"):
        assert said in assumed, said
    assert config["solver"]["base_lr"] == 3e-6
    check = config["check"]
    assert check["held_load_band"] == [0.5, 2.0]
    assert check["seq_len"] == traffic["seq_len"]
    for key in ("first_loss", *check):
        why = (config["first_loss"]["why"] if key == "first_loss"
               else check[key] if key.startswith("why_") else None)
        assert why is None or len(why) > 100, key
    per_layer = {m["name"]: m for m in files.table()["per_layer"]}
    for name in [*MFU, *DEVICE_MS]:
        assert per_layer[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
    reported = {m["name"] for m in files.metrics_of(CELL, "per_layer")}
    assert set(MFU) | set(DEVICE_MS) <= reported
    assert not {"gdn_mfu", "lfm2_attention_mfu", "dsa_attention_mfu"} & reported


def test_the_tau50_cell_is_the_tau10_one_at_the_apps_tau():
    work, config, traffic = files.cell("caffenet-train-tau50")
    _, same, ten = files.cell("caffenet-train")
    assert work["chips"] == 1 and work["config"] == "caffenet"
    assert config == same
    assert traffic["tau"] == 50 and ten["tau"] == 10
    assert {k for k in traffic if traffic[k] != ten.get(k)} == {"tau", "what"}
    # two cells on four chips: inside the quarter
    cells = files.table()["workloads"]
    assert [w["name"] for w in cells if w["chips"] == 4] == [
        "caffenet-dp4", "caffenet-dp4-tau1"]
    assert len(cells) // 4 >= 2
    names = [w["name"] for w in cells]
    assert names.index("caffenet-train-tau50") == names.index(CELL) + 1
    # it reads the accepted metrics without a list, and none of its own
    reported = {m["name"] for m in files.metrics_of(
        "caffenet-train-tau50", "per_layer")}
    assert {"step_device_ms", "round_dispatch_ms", "state_gib"} <= reported
    assert not {"mla_attention_mfu", "feed_h2d_ms", "collective_ms"} & reported


@pytest.mark.parametrize("name, types", [*MFU.items(), *DEVICE_MS.items()])
def test_a_metrics_file_names_its_reader_and_types(name, types):
    spec = files.load_json("benchmark", "layer_metrics", name + ".json")
    assert spec["args"]["types"] == types
    if name in MFU:
        work, _, _ = files.cell(CELL)
        assert spec["reducer"] == "kanana_mfu_by_scope"
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
    their formula at the PUBLISHED widths: 192 for a score, 128 for a
    value."""
    from sparknet_tpu.models.hybrid_lm import MLA_SCOPES, HybridMoELM

    _, config, traffic = files.cell(CELL)
    t = traffic["seq_len"]
    model = HybridMoELM(config)
    share = config["num_experts_per_tok"] / config["n_routed_experts"]
    by_type = dict.fromkeys(kanana_flops.TYPES, 0.0)
    assert set(MLA_SCOPES) <= set(by_type)
    kinds = {"head": "LMHead", "router": "MoERouter", "mlp": "DenseMLP",
             "shared": "MoEShared"}
    for group, shapes in model._group_blobs:
        layer = group.split("_")[-1]
        for index, shape in enumerate(shapes):
            weights = 1
            for n in shape:
                weights *= n
            if len(shape) < 2 or group == "embed":
                continue  # vectors scale, the embedding is gathered
            if layer == "experts":
                by_type["MoEExperts"] += 2 * weights * share
            elif layer == "mixer":  # o_proj is the attention's, the rest
                by_type["MLAAttention" if index == 4 else "MLALatent"] += (
                    2 * weights)
            else:
                by_type[kinds[layer]] += 2 * weights
    layers = config["num_hidden_layers"]
    by_type["MLAAttention"] += layers * 2 * config["num_attention_heads"] * (
        192 + 128) * (t + 1) / 2
    want = kanana_flops.forward_flops_per_token_by_type(config, t)
    assert set(want) == set(by_type)
    for kind in want:
        assert by_type[kind] == pytest.approx(want[kind], rel=1e-12), kind
    # ISSUE 37's arithmetic: 930 MFLOP a token forward, 22.9 TFLOP a
    # sequence trained; a layer's latent projections 35.9 (the issue's 52.69
    # had o_proj with them) and its attention 100.7, of which scores and
    # values 83.90; 73% of it all in the latent attention
    total = sum(want.values())
    assert total == pytest.approx(930.3e6, rel=1e-3)
    assert kanana_flops.train_flops_per_sequence(config, t) == pytest.approx(
        22.86e12, rel=1e-3)
    assert want["MLALatent"] / layers == pytest.approx(35.91e6, rel=1e-3)
    assert want["MLAAttention"] / layers == pytest.approx(
        83.90e6 + 16.78e6, rel=1e-3)
    assert (want["MLALatent"] + want["MLAAttention"]) / total == pytest.approx(
        0.734, abs=2e-3)
    assert want["DenseMLP"] == pytest.approx(75.50e6, rel=1e-3)
    assert want["MoEShared"] / 4 == pytest.approx(18.87e6, rel=1e-3)
    assert want["MoEExperts"] / 4 == pytest.approx(7.08e6, rel=1e-3)
    assert want["LMHead"] == pytest.approx(65.67e6, rel=1e-3)
    # a held expert sees 768 of a step's tokens, an eighth of its deployed
    per_expert = 2 * t * config["num_experts_per_tok"] / config["n_routed_experts"]
    assert per_expert == 768 and per_expert * 8 == 6144


def test_by_type_reader_finds_nothing_without_scopes():
    """On a trace with no scoped execution (here: no trace at all) the new
    reader returns None and raises nothing, as a parent commit that lacks the
    model's scopes makes it."""
    from benchmark.reducers import kanana_mfu_by_scope

    ev = {"xplane_path": os.path.join(ROOT, "no-such-file.xplane.pb"),
          "window_ns": (0.0, 1.0), "devices": [], "tau": 4,
          "peaks": {"bf16_flops_per_s": 1.97e14}}
    for name in MFU:
        spec = files.load_json("benchmark", "layer_metrics", name + ".json")
        assert kanana_mfu_by_scope.reduce(ev, **spec["args"]) is None
