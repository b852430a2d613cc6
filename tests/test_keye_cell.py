"""The benchmark's Keye cell (``keye2-train-16k``) and the four-chip cell
that came with it (``caffenet-dp4-tau1``) beside their rehearsals
(``tests/test_benchmark_cells.py``): every planted fault through the cell's
own comparisons at the rehearsal's size, its operation count against a walk
of the program's parameter shapes, its files against ``BENCHMARK.json`` and
the catalog's keys, the reference that carries two losses into
``lm_checks``' step, and its reader on a trace without scopes.  Reads
``benchmark/``, edits nothing."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import files, keye_checks, keye_flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "keye2-train-16k"
MFU = {"dsa_indexer_mfu": ["DSAIndexer", "DSAIndexerLoss"],
       "dsa_attention_mfu": ["DSAAttention"]}
DEVICE_MS = {
    "dsa_indexer_device_ms": ["DSAIndexer"],
    "dsa_select_device_ms": ["DSASelect"],
    "dsa_attention_device_ms": ["DSAAttention"],
    "dsa_align_device_ms": ["DSAIndexerLoss"],
    "keye_moe_route_device_ms": ["MoERouter"],
    "keye_moe_experts_device_ms": ["MoEExperts"],
    "keye_head_device_ms": ["Embedding", "LMHead"]}
VERDICTS = {"forward_stated_dtype", "step_stated_dtype", "step_exact",
            "router_in_float32", "selection_against_reference",
            "sparse_attention_exact", "indexer_learns"}

# each group of planted faults, the comparisons it is limited to, and the
# verdicts that have to come out False; every other verdict stays True
PLANTED = {
    "state_unchanged:step": {"step_stated_dtype"},
    "bfloat16_update:step": {"step_stated_dtype"},
    "float8_reference:forward,step": {
        "forward_stated_dtype", "step_stated_dtype", "step_exact"},
    "bfloat16_router:float32": {"router_in_float32"},
    "dense_attention:selection": {"sparse_attention_exact"},
    "half_topk:selection": {"selection_against_reference"},
    "unweighted_heads:selection": {"selection_against_reference"},
    "no_relu:selection": {"selection_against_reference"},
    "lm_loss_reaches_indexer:indexer": {"indexer_learns"},
    "no_alignment_loss:indexer": {"indexer_learns"},
}


@pytest.fixture(scope="module")
def planted():
    """``python -m benchmark.keye_checks --rehearse``: the cell's
    comparisons alone, unplanted and then once a group, in one process."""
    command = [sys.executable, "-m", "benchmark.keye_checks", "--workload",
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
    """A state left as it was, an update or a router in a lower precision,
    the reference in the precision below the stated one, an attention that
    ignores the selection, half the keys, index heads summed unweighted or
    without their ReLU, a language-model loss that reaches the indexer and a
    step without the alignment loss: not correct, by the comparison that is
    there for it and by no other."""
    results, stdout = planted
    result = results[group]
    assert result["planted"] == sorted(group.partition(":")[0].split(","))
    assert result["correct"] is False
    failed = {k for k, ok in result["verdict"].items() if not ok}
    assert failed == PLANTED[group], stdout[-3000:]


@pytest.mark.parametrize("per_token, ok", [
    ([1.0, 1.0, 1.0, 1.0], True),  # the expectation: 8 x 16 / 128
    ([0.74, 1.27, 0.89, 1.38], True),  # what seeded weights read, by layer
    ([0.5, 2.0, 1.0, 1.0], True), ([0.49, 1.0, 1.0, 1.0], False),
    ([1.0, 1.0, 2.01, 1.0], False),  # past the grouped rows: token chunks
    ([1.04, 0.46, 0.65, 0.21], False),  # EVERY layer, not their mean
    ([0.0, 0.0, 0.0, 0.0], False),  # a router that walked away
    ([], False),
])
def test_the_verdict_on_the_held_experts_load(per_token, ok):
    import types

    _, config, _ = files.cell(CELL)
    cell = types.SimpleNamespace(config=config, log=lambda message: None)
    gauges = {"held_assignments_per_token": per_token}
    assert keye_checks.held_load(cell, gauges) == {"held_load_in_window": ok}


def test_an_unknown_plant_is_refused(monkeypatch):
    monkeypatch.setenv("LM_CHECK_PLANT", "half_batch")  # a step is one sequence
    with pytest.raises(SystemExit, match="unknown"):
        keye_checks.planted(None)


def test_the_step_reference_carries_both_losses():
    """``jax.grad`` of ``lm_checks.cross_entropy`` of ``keye_vl2_step``'s
    logits is ``jax.grad`` of ``keye_vl2.loss``, every leaf, and its logits
    are ``keye_vl2``'s."""
    import jax
    import numpy as np

    from benchmark import lm_checks
    from benchmark.reference import keye_vl2, keye_vl2_step
    from sparknet_tpu.models.hybrid_lm import HybridMoELM

    _, config, _ = files.cell(CELL, rehearse=True)
    params, _ = HybridMoELM(config).init(0)
    ids = jax.random.randint(jax.random.key(0), (1, 25), 0, config["vocab_size"])
    tokens, targets = ids[:, :-1], ids[:, 1:]
    got = jax.jit(jax.grad(lambda p: lm_checks.cross_entropy(
        keye_vl2_step.logits(p, tokens, config, remat=True), targets)))(params)
    want = jax.jit(jax.grad(lambda p: keye_vl2.loss(
        p, tokens, targets, config)))(params)
    for group in want:
        for g, w in zip(got[group], want[group]):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7)
    assert np.asarray(got["l0_mixer"][10]).any()  # the indexer's, from L_I
    np.testing.assert_array_equal(
        keye_vl2_step.logits(params, tokens, config),
        keye_vl2.logits(params, tokens, config))


def test_cell_and_its_files_are_in_the_table():
    work, config, traffic = files.cell(CELL)
    assert work["chips"] == 1
    assert work["traffic"] == "lm-resident-tau4-16k-keye"
    assert traffic["kind"] == "lm-train-resident-keye"
    assert (traffic["seq_len"], traffic["sequences_per_step"], traffic["tau"],
            traffic["partition_sequences"], traffic["zipf_exponent"],
            traffic["warm_rounds"]) == (16384, 1, 4, 1024, 1.0, 2)
    entry = next(c for c in files.table()["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
    # every number of the catalog's config under its key, as published but
    # for the depth and the vocabulary
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["experts_held"],
            config["vocab_size"]) == (4, [0, 16], 18992)
    was = config["published"]
    assert (was["num_hidden_layers"], was["num_experts"],
            was["vocab_size"]) == (48, 128, 151936)
    assert config["vocab_size"] * 8 == was["vocab_size"]
    assert config["held_here"]["parameters"] == 465_391_104
    assumed = " ".join(config["assumed"])
    for said in ("M-RoPE IS plain rotary", "RMSNorm over each head of q and k",
                 "DeepSeek-V3.2-Exp", "q_chunk_size", "at weight 1",
                 "No router auxiliary loss", "rate fixed at 3e-6",
                 "the embedding normal(0, 1)"):
        assert said in assumed, said
    assert config["solver"]["base_lr"] == 3e-6
    # the grouped rows are ops/moe.ROWS_SLACK's, as in every sequence cell,
    # and every layer's held load is held to half to twice the expectation
    assert "expert_rows_slack" not in config
    assert config["check"]["held_load_band"] == [0.5, 2.0]
    check = config["check"]
    # every comparison that covers the mixer runs at T >= 2 x topk
    assert min(check["seq_len"], check["step_seq_len"]) >= 2 * 2048
    assert check["seq_len"] == traffic["seq_len"]
    for key in check:
        if key.startswith("why_"):
            assert len(check[key]) > 100, key
    per_layer = {m["name"]: m for m in files.table()["per_layer"]}
    for name in [*MFU, *DEVICE_MS]:
        assert per_layer[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
    reported = {m["name"] for m in files.metrics_of(CELL, "per_layer")}
    assert set(MFU) | set(DEVICE_MS) <= reported
    # the other sequence cells' metrics are not this cell's
    assert not {"gdn_mfu", "attention_device_ms", "lfm2_head_device_ms"} & reported


def test_the_four_chip_cell_is_the_tau10_one_at_tau_1():
    work, config, traffic = files.cell("caffenet-dp4-tau1")
    _, same, ten = files.cell("caffenet-dp4")
    assert work["chips"] == 4 and work["config"] == "caffenet"
    assert config == same
    assert traffic["tau"] == 1 and ten["tau"] == 10
    differ = {k for k in traffic if traffic[k] != ten.get(k)}
    assert differ == {"tau", "what"}
    # two cells on four chips, of eight when this one came: the quarter
    cells = files.table()["workloads"]
    assert [w["name"] for w in cells if w["chips"] == 4] == [
        "caffenet-dp4", "caffenet-dp4-tau1"]
    assert len(cells) // 4 >= 2
    # it reads the accepted metrics without a list, and none of its own
    reported = {m["name"] for m in files.metrics_of(
        "caffenet-dp4-tau1", "per_layer")}
    assert {"average_device_ms", "step_device_ms", "round_dispatch_ms"} <= reported
    assert not {"collective_ms", "collective_exposed_ms"} & reported


@pytest.mark.parametrize("name, types", [*MFU.items(), *DEVICE_MS.items()])
def test_a_metrics_file_names_its_reader_and_types(name, types):
    spec = files.load_json("benchmark", "layer_metrics", name + ".json")
    assert spec["args"]["types"] == types
    if name in MFU:
        work, _, _ = files.cell(CELL)
        assert spec["reducer"] == "keye_mfu_by_scope"
        assert (spec["args"]["config"], spec["args"]["traffic"]) == (
            work["config"], work["traffic"])
    else:
        assert spec["reducer"] == "device_ms_by_scope"
        assert spec["args"]["phases"] == ["forward", "backward"]
        assert spec["args"]["per"] == "step"


def test_operation_count_against_a_walk_of_the_programs_shapes():
    """Every matrix the program holds is a projection a token passes once (2
    operations a weight), the held experts at the expected share of tokens;
    the index scores and the attention's two products, which have no
    weights, are added from their formulas: all causal pairs for the one,
    the selected pairs for the other."""
    from sparknet_tpu.models.hybrid_lm import DSA_SCOPES, HybridMoELM

    _, config, traffic = files.cell(CELL)
    t = traffic["seq_len"]
    model = HybridMoELM(config)
    share = config["num_experts_per_tok"] / config["num_experts"]
    by_type = dict.fromkeys(keye_flops.TYPES, 0.0)
    assert set(DSA_SCOPES) <= set(by_type)
    for group, shapes in model._group_blobs:
        layer = group.split("_")[-1]
        for index, shape in enumerate(shapes):
            weights = 1
            for n in shape:
                weights *= n
            if len(shape) < 2 or group == "embed":
                continue  # vectors scale or shift, the embedding is gathered
            if group == "head":
                by_type["LMHead"] += 2 * weights
            elif layer == "experts":
                by_type["MoEExperts"] += 2 * weights * share
            elif layer == "router":
                by_type["MoERouter"] += 2 * weights
            else:
                assert layer == "mixer"
                by_type["DSAIndexer" if index >= 6 else "DSAAttention"] += (
                    2 * weights)
    layers, sa = config["num_hidden_layers"], config["sa_config"]
    selected = sum(min(row + 1, sa["topk"]) for row in range(t)) / t
    assert selected == keye_flops.selected_pairs_per_token(t, sa["topk"])
    assert selected == pytest.approx(1920.06, abs=0.01)
    by_type["DSAIndexer"] += layers * (
        2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * (t + 1) / 2)
    by_type["DSAAttention"] += layers * (
        4 * config["num_attention_heads"] * config["head_dim"] * selected)
    want = keye_flops.forward_flops_per_token_by_type(config, t)
    assert set(want) == set(by_type)
    for kind in want:
        assert by_type[kind] == pytest.approx(want[kind], rel=1e-12), kind
    # ISSUE 33's arithmetic: ~480 MFLOP a token forward, ~23.6 TFLOP a
    # sequence trained; a layer 100.5 MFLOP of which the selected attention
    # 31.46 and the index scores 16.78
    total = sum(want.values())
    assert total == pytest.approx(479.67e6, rel=1e-4)
    assert keye_flops.train_flops_per_sequence(config, t) == pytest.approx(
        23.577e12, rel=1e-4)
    assert (total - want["LMHead"]) / layers == pytest.approx(100.47e6, rel=1e-4)
    assert want["DSASelect"] == want["DSAIndexerLoss"] == 0.0
    shares = {k: round(100 * v / total) for k, v in want.items() if v}
    assert shares == {"DSAIndexer": 18, "DSAAttention": 58, "MoERouter": 0,
                      "MoEExperts": 8, "LMHead": 16}
    # short of topk nothing is dropped: the count is dense causal attention's
    assert keye_flops.selected_pairs_per_token(1024, 2048) == (1024 + 1) / 2


def test_by_type_reader_finds_nothing_without_scopes():
    """On a trace with no scoped execution (here: no trace at all) the new
    reader returns None and raises nothing, as a parent commit that lacks the
    model's scopes makes it."""
    from benchmark.reducers import keye_mfu_by_scope

    ev = {"xplane_path": os.path.join(ROOT, "no-such-file.xplane.pb"),
          "window_ns": (0.0, 1.0), "devices": [], "tau": 4,
          "peaks": {"bf16_flops_per_s": 1.97e14}}
    for name in MFU:
        spec = files.load_json("benchmark", "layer_metrics", name + ".json")
        assert keye_mfu_by_scope.reduce(ev, **spec["args"]) is None
