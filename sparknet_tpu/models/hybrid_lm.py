"""Decoder-only LM whose layer pattern, mixers, feed-forwards, norms,
positions, head counts, router and head are VALUES read from a published
``config.json``: one block (``_layer``), one class, four families so far.

- ``qwen3_next`` (Qwen3-Next): linear-attention (Gated DeltaNet) layers with
  one gated softmax-attention layer every ``full_attention_interval``, a
  sparse mixture of experts with a shared expert in every layer (softmax
  router), zero-centred RMSNorm, rotary positions on part of each head, an
  untied head.
- ``lfm2_moe`` (LFM2): gated short-convolution layers beside plain grouped
  attention as ``layer_types`` lists them (no output gate, rotary on the
  whole head, plain RMSNorm on q and k), a dense gated MLP in the first
  ``num_dense_layers`` and routed experts without a shared expert after
  (sigmoid scores, top-k on ``scores + expert_bias``, weights from the
  unbiased scores; the bias moves by its balancing rule, below), plain
  RMSNorm, the head tied to the embedding.
- ``KeyeVL2`` (Keye-VL-2.0's language model): every layer plain grouped
  attention (RMSNorm on q and k, rotary on the whole head) over the
  ``sa_config.topk`` keys a learned indexer selects for each query
  (``ops/sparse_attention.py``: index scores from ``indexer_num_heads`` small
  heads against ONE shared key, a per-row threshold, a masked pass), with the
  indexer's alignment loss beside the language model's; routed experts
  without a shared expert (softmax router), plain RMSNorm, an untied head;
  its seeded weights start at ``init_std`` (the embedding normal(0, 1), the
  matrices that write to the residual stream 0.02 / sqrt(2 x layers)): at
  0.02 throughout, the flat attention of an all-attention stack hands every
  router the same input.

- ``deepseek_v3`` (as kanana-2-30b-a3b publishes it): every layer multi-head
  LATENT attention without a query rank (``ops/attention.causal_mla_
  attention``: the keys and values of all heads are made from ONE normed
  latent of ``kv_lora_rank``; a head's score is ``qk_nope_head_dim +
  qk_rope_head_dim`` wide, 128 + 64, the second part against ONE rotary key
  every head shares, rotary over adjacent pairs; its value ``v_head_dim``,
  128), no norm on the heads; a dense gated MLP in the first
  ``first_k_dense_replace`` layers and after them routed experts (sigmoid
  scores, ``noaux_tc``: top-k on ``scores + bias`` as LFM2's, one group,
  renormalised with 1e-20, times ``routed_scaling_factor``) beside shared
  experts, one gated MLP of ``n_shared_experts x moe_intermediate_size``
  WITHOUT Qwen3-Next's output gate; plain RMSNorm, an untied head; seeded
  weights start as KeyeVL2's, an all-attention stack too.

- ``laguna`` (Laguna-XS.2): every layer gated grouped attention (the
  ``gated_attention`` layout and gate, RMSNorm on q and k), full causal at
  the ``full_attention`` entries of ``layer_types`` and over the last
  ``sliding_window`` keys at the ``sliding_attention`` ones
  (``window_attention``); the query heads (``num_attention_heads_per_layer``)
  and the rotary positions (``rope_parameters`` a layer type: rotate-half on
  ``partial_rotary_factor`` of each head, at plain frequencies or YaRN's,
  ``yarn_inv_freq``) are the layer's own; a dense gated MLP where
  ``mlp_layer_types`` says ``dense`` and routed experts elsewhere (sigmoid
  scores, top-k on ``scores + bias`` as DeepSeek-V3's, renormalised with
  1e-20, times ``moe_routed_scaling_factor``) beside one shared expert WITHOUT
  an output gate; plain RMSNorm, an untied head; seeded weights start as
  KeyeVL2's, an all-attention stack too.

``describe`` turns a file's keys into one description: a mixer kind a
layer (``MIXERS``: ``gated_delta_net``, ``gated_attention``, ``short_conv``,
``attention``, ``dsa_attention``, ``mla_attention``, ``window_attention``),
a feed-forward kind a layer (``dense`` or ``moe``), an attention layer's
query heads, rotary positions and window (``heads``, ``rope``, ``window``:
a value a layer; a family with one of each fills them from its scalars),
and the values the equations take.  Nothing below it asks which
family it builds.
Beside the published keys, three of this system's own:

- ``experts_held: [lo, n]`` — the contiguous range of routed experts this
  chip holds.  The router keeps its published width; only the held experts'
  terms are added (``ops/moe.py``).  Default: all of them.
- ``compute_dtype`` — matrix products take their operands in it and
  accumulate in float32 over float32 master weights; norms, softmaxes, the
  router, the decay ``g``, the delta-rule state, the short convolution and
  the loss stay float32.  ``Solver(net=..., compute_dtype=...)`` sets it
  (``set_compute_dtype``).
- ``expert_bias_update_rate`` — the rate of the selection bias's balancing
  rule (``ops/moe.balance``); without the key the bias stays where it is.

The block is written once (``_layer``), for training.  The generation seams
of ``TransformerLM`` (``prefill_with_kv`` / ``decode_step_with_kv``) are not
supported: a linear-attention layer decodes from a recurrent state, a short
convolution from its last ``width - 1`` inputs, beside the paged K/V, and
``serve/`` has neither (ROADMAP R5).

Solver protocol as ``TransformerLM``: ``init`` / ``loss_fn`` /
``param_multipliers`` / ``feed_blobs`` and the checkpoint interface
(``layers`` + ``_blob_refs``).  A router's selection bias is no parameter:
no gradient reaches it and no optimizer holds state for it.  It lives in
the ``stats`` collection (where a net's BatchNorm statistics live: carried
from step to step by the solver, averaged over the workers with the
parameters, checkpointed with its router's group), as ``stats["l<i>_router"]
= [expert_bias, expert_load]``, both ``(num_experts,)`` float32: a training
step leaves the assignments each expert received in ``expert_load`` and
moves the bias one step of ``ops/moe.balance`` towards an even load.  A
forward pass given no ``stats`` selects on the unbiased scores.  A tied head
is the embedding's transpose: there is no ``head`` group.  Every sublayer
runs under one ``jax.named_scope("<Type>:<name>")`` (ARCHITECTURE.md
"Telemetry reference") with a ``jax.checkpoint`` INSIDE the scope: between
sublayers only the residual stream and the normed input are kept (and, of
an attention layer, what the flash kernels name: ``MIXER_KEEPS``), each
sublayer's forward is recomputed in its backward, and autodiff names both
``transpose(jvp(<Type>:<name>))``, i.e. backward.  An ``mla_attention`` layer
opens two (``_mla_mixer``: what making q, k and v costs apart from the
kernels and the output projection).  A ``dsa_attention`` layer
opens four scopes where the others open one (``_dsa_mixer``) and hands an
auxiliary scalar, its alignment loss, up to ``loss_fn`` as a routed layer
hands up its counts; what it keeps between forward and backward is stated
there.  The loss is one operation
(``ops/lm_loss.nll_sum``, under ``LMHead:head`` with the final norm and
recomputed like a sublayer: ``HEAD_KEEPS``): where its kernels take the
shapes it walks the vocabulary in blocks with its own backward, reads a tied
head where the embedding lies and keeps a float32 ``lse`` a row; elsewhere
``log_softmax`` over float32 logits, which are not kept.

Layouts.  Gated DeltaNet: ``in_proj_qkvz`` / ``in_proj_ba`` columns ``[q |
k | v | z]`` and ``[b | a]``, heads contiguous inside each part.  Gated
attention: ``q_proj`` head-major, each head ``[q | gate]``.  Short
convolution: ``in_proj`` columns ``[B | C | u]``.  Selected-key attention:
the plain attention's six blobs, then the indexer's ``index_q`` (heads
contiguous), ``index_k``, its LayerNorm's weight and bias, ``index_w``.
Windowed attention: the gated attention's.
Latent attention: ``q_proj`` ``(E, H (nope + rope))`` head-major, each head
``[nope | rope]``; ``kv_a_proj`` ``(E, kv_lora_rank + rope)``, columns
``[latent | the one rope key]``; the latent's RMSNorm weight
``(kv_lora_rank,)``; ``kv_b_proj`` ``(kv_lora_rank, H (nope + v))``
head-major, each head ``[k_nope | v]``; ``o_proj`` ``(H v, E)``.
"""

from __future__ import annotations

import json
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.models.transformer_lm import _Group, _Ref
from sparknet_tpu.ops import lm_loss, moe, sparse_attention
from sparknet_tpu.ops.attention import (
    causal_gqa_attention,
    causal_mla_attention,
)
from sparknet_tpu.ops.delta_rule import gated_delta_rule
from sparknet_tpu.ops.pallas_attention import SAVED as FLASH_SAVED
from sparknet_tpu.ops.short_conv import causal_depthwise_conv, gated_short_conv

F32 = jnp.float32
# a mixer's recomputation keeps what the flash kernels name, their output and
# its row log-sum-exp (269 MB an attention layer at 2 x 8,192 tokens), and so
# does not run the forward kernel a second time (the selected-key attention's
# blocks name theirs likewise); nothing else is kept
MIXER_KEEPS = jax.checkpoint_policies.save_only_these_names(
    *FLASH_SAVED, *sparse_attention.SAVED)
# the head's recomputation is the final norm's: the loss kernels' row
# log-sum-exp is kept (64 KB), the XLA path's logits are not
HEAD_KEEPS = jax.checkpoint_policies.save_only_these_names(*lm_loss.SAVED)

# the keys of a qwen3_next config.json that decide a shape or an equation
CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "full_attention_interval",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "rms_norm_eps",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size",
)
# and of an lfm2_moe one (``head_dim``, ``tie_word_embeddings``,
# ``use_expert_bias``, ``routed_scaling_factor`` at the family's defaults)
LFM2_MOE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
    "num_dense_layers", "num_attention_heads", "num_key_value_heads",
    "rope_parameters", "norm_eps", "conv_L_cache", "intermediate_size",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
)
# and of a KeyeVL2 one (``sa_config``: ``SA_KEYS``)
KEYE_VL2_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "sa_config", "num_experts", "num_experts_per_tok", "moe_intermediate_size",
)
SA_KEYS = ("indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads",
           "topk", "q_chunk_size")
# and of a deepseek_v3 one
DEEPSEEK_V3_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rope_theta", "rms_norm_eps", "first_k_dense_replace",
    "intermediate_size", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "moe_intermediate_size", "routed_scaling_factor",
)
# what a deepseek_v3 file may say of the mechanisms this class has not: a
# key, the one value taken (``None``: the key absent counts too), the
# mechanism a different value asks for
DEEPSEEK_V3_ONLY = (
    ("q_lora_rank", None, "a query rank (a latent for q too)"),
    ("rope_scaling", None, "scaled rotary positions"),
    ("n_group", 1, "group-limited routing"),
    ("topk_group", 1, "group-limited routing"),
    ("moe_layer_freq", 1, "dense layers between the routed ones"),
    ("attention_bias", False, "biases on the attention's projections"),
    ("norm_topk_prob", True, "top-k weights that are not renormalised"),
    ("scoring_func", "sigmoid", "softmax scores under noaux_tc"),
    ("topk_method", "noaux_tc", "a top-k without the selection bias"),
    ("rope_interleave", True, "rotate-half on the rope part"),
)
# the renormalisation of DeepSeek-V3's top-k weights: ``w / (sum(w) + 1e-20)``
DEEPSEEK_V3_TOPK_EPS = 1e-20
# and of a laguna one (``rope_parameters``: ``ROPE_KEYS`` a layer type, and
# ``YARN_KEYS`` beside them where its ``rope_type`` is ``yarn``)
LAGUNA_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
    "num_attention_heads_per_layer", "num_key_value_heads", "head_dim",
    "rope_parameters", "partial_rotary_factor", "sliding_window",
    "rms_norm_eps", "mlp_layer_types", "intermediate_size", "num_experts",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "moe_routed_scaling_factor",
)
ROPE_KEYS = ("rope_type", "rope_theta")
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")
# what a laguna file may say of the mechanisms this class has not (as
# ``DEEPSEEK_V3_ONLY``)
LAGUNA_ONLY = (
    ("gating", True, "attention without the elementwise output gate"),
    ("moe_apply_router_weight_on_input", False,
     "the router's weights on the experts' input"),
    ("attention_bias", False, "biases on the attention's projections"),
    ("tie_word_embeddings", False, "a head tied to the embedding"),
    ("moe_router_logit_softcapping", 0, "a soft cap on the router's logits"),
)
LAGUNA_LAYERS = {"full_attention": "gated_attention",
                 "sliding_attention": "window_attention"}
# a mixer kind's scope type; its blobs and its function are the model's
# ``_mixer_shapes`` and ``_<kind>`` (``dsa_attention``: ``_dsa_mixer``, which
# opens the scopes ``DSA_SCOPES`` beside it)
MIXERS = {
    "gated_delta_net": "GatedDeltaNet", "gated_attention": "GatedAttention",
    "short_conv": "ShortConv", "attention": "Attention",
    "dsa_attention": "DSAAttention", "mla_attention": "MLAAttention",
    "window_attention": "WindowAttention",
}
DSA_SCOPES = ("DSAIndexer", "DSASelect", "DSAAttention", "DSAIndexerLoss")
# a latent-attention layer's (``_mla_mixer``)
MLA_SCOPES = ("MLALatent", "MLAAttention")
# the indexer's LayerNorm (DeepSeek-V3.2-Exp's public implementation)
INDEX_NORM_EPS = 1e-6
# the renormalisation of LFM2's top-k weights: ``w / (sum(w) + 1e-6)``
LFM2_TOPK_EPS = 1e-6


def load_config(path: str) -> Dict:
    """A configuration file as ``benchmark/configs/`` keeps them: the
    published keys at the top level beside this system's own."""
    with open(path) as f:
        return json.load(f)


def _take(config: Dict, keys, name: str) -> Dict:
    missing = [k for k in keys if k not in config]
    if missing:
        raise ValueError(f"{name}: configuration lacks {missing}")
    return {k: config[k] for k in keys}


def _describe_qwen3_next(config: Dict, name: str) -> Dict:
    c = _take(config, CONFIG_KEYS, name)
    depth = c["num_hidden_layers"]
    return dict(
        c,
        mixers=tuple(
            "gated_attention" if (i + 1) % c["full_attention_interval"] == 0
            else "gated_delta_net" for i in range(depth)),
        ffns=("moe",) * depth,
        eps=c["rms_norm_eps"], zero_centred_norm=True,
        rotary_dim=int(c["head_dim"] * c["partial_rotary_factor"]),
        shared_expert_gate=True,
        router_scores="softmax", expert_bias=False, routed_scaling_factor=1.0,
        topk_eps=0.0, expert_bias_update_rate=0.0,
        tied=bool(config.get("tie_word_embeddings", False)),
    )


def _describe_lfm2_moe(config: Dict, name: str) -> Dict:
    c = _take(config, LFM2_MOE_KEYS, name)
    depth = c["num_hidden_layers"]
    kinds = {"conv": "short_conv", "full_attention": "attention"}
    types = list(c["layer_types"])
    if len(types) != depth or set(types) - set(kinds):
        raise ValueError(
            f"{name}: layer_types must list {depth} of {sorted(kinds)}")
    if config.get("conv_bias", False):
        raise ValueError(f"{name}: conv_bias=true is not supported")
    head_dim = config.get(
        "head_dim", c["hidden_size"] // c["num_attention_heads"])
    return dict(
        c,
        mixers=tuple(kinds[t] for t in types),
        ffns=tuple("dense" if i < c["num_dense_layers"] else "moe"
                   for i in range(depth)),
        head_dim=head_dim, rotary_dim=head_dim,
        rope_theta=c["rope_parameters"]["rope_theta"],
        eps=c["norm_eps"], zero_centred_norm=False,
        shared_expert_intermediate_size=0,
        router_scores="sigmoid",
        expert_bias=bool(config.get("use_expert_bias", True)),
        routed_scaling_factor=float(config.get("routed_scaling_factor", 1.0)),
        topk_eps=LFM2_TOPK_EPS,
        expert_bias_update_rate=float(
            config.get("expert_bias_update_rate", 0.0)),
        tied=bool(config.get("tie_word_embeddings", True)),
    )


def _describe_keye_vl2(config: Dict, name: str) -> Dict:
    c = _take(config, KEYE_VL2_KEYS, name)
    sa = _take(c["sa_config"], SA_KEYS, name + " sa_config")
    depth = c["num_hidden_layers"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError(f"{name}: the indexer's heads share ONE key head")
    if config.get("mlp_only_layers") or config.get("decoder_sparse_step", 1) != 1:
        raise ValueError(f"{name}: every layer routes (mlp_only_layers empty, "
                         "decoder_sparse_step 1)")
    if config.get("use_sliding_window", False):
        raise ValueError(f"{name}: use_sliding_window=true is not supported")
    return dict(
        c,
        mixers=("dsa_attention",) * depth, ffns=("moe",) * depth,
        eps=c["rms_norm_eps"], zero_centred_norm=False,
        rotary_dim=c["head_dim"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        # the published tiling of the indexer's computation is the query
        # block of every pass over the scores; it changes no equation
        index_block=sa["q_chunk_size"],
        # where the seeded weights start (``init``): at 0.02 throughout, an
        # all-attention stack on a 0.9-long embedding routes every token
        # alike (the flat attention's output, the values' mean, is 6 long)
        init_std={"embed": 1.0, "out": 0.02 * (2 * depth) ** -0.5},
        shared_expert_intermediate_size=0,
        router_scores="softmax", expert_bias=False, routed_scaling_factor=1.0,
        topk_eps=0.0, expert_bias_update_rate=0.0,
        tied=bool(config.get("tie_word_embeddings", False)),
    )


def _describe_deepseek_v3(config: Dict, name: str) -> Dict:
    c = _take(config, DEEPSEEK_V3_KEYS, name)
    for key, taken, mechanism in DEEPSEEK_V3_ONLY:
        if config.get(key, taken) != taken:
            raise ValueError(
                f"{name}: {key}={config[key]!r} is not supported "
                f"({mechanism}); only {key}={taken!r}")
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    if config.get("qk_head_dim", nope + rope) != nope + rope:
        raise ValueError(f"{name}: qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if c["v_head_dim"] != nope:
        raise ValueError(f"{name}: v_head_dim={c['v_head_dim']} beside "
                         f"qk_nope_head_dim={nope} is not supported (the "
                         "kernels' value block is the nope part's)")
    if rope % 2:
        raise ValueError(f"{name}: qk_rope_head_dim must be even")
    depth = c["num_hidden_layers"]
    return dict(
        c,
        mixers=("mla_attention",) * depth,
        ffns=tuple("dense" if i < c["first_k_dense_replace"] else "moe"
                   for i in range(depth)),
        eps=c["rms_norm_eps"], zero_centred_norm=False,
        # a key head a query head; ``head_dim`` (the file's is the rope
        # part's) and ``rotary_dim`` are the other attentions' and unused
        num_key_value_heads=c["num_attention_heads"],
        head_dim=nope, rotary_dim=rope,
        num_experts=c["n_routed_experts"],
        # the shared experts are ONE gated MLP of their widths side by side,
        # added whole, with no gate on its output (no ``w_s`` blob)
        shared_expert_intermediate_size=(
            c["n_shared_experts"] * c["moe_intermediate_size"]),
        shared_expert_gate=False,
        # an all-attention stack: as ``_describe_keye_vl2``'s, and why
        init_std={"embed": 1.0, "out": 0.02 * (2 * depth) ** -0.5},
        router_scores="sigmoid", expert_bias=True,
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        topk_eps=DEEPSEEK_V3_TOPK_EPS,
        expert_bias_update_rate=float(
            config.get("expert_bias_update_rate", 0.0)),
        tied=bool(config.get("tie_word_embeddings", False)),
    )


def _rope_of(params: Dict, head_dim: int, partial: float, name: str):
    """A layer type's rotary positions from its ``rope_parameters`` entry:
    ``{"theta", "dim", "yarn"}``, ``yarn`` None at plain frequencies."""
    p = _take(params, ROPE_KEYS, name)
    if p["rope_type"] not in ("default", "yarn"):
        raise ValueError(f"{name}: rope_type={p['rope_type']!r} is not "
                         "supported; only 'default' or 'yarn'")
    dim = int(head_dim * params.get("partial_rotary_factor", partial))
    if dim % 2 or not 0 < dim <= head_dim:
        raise ValueError(f"{name}: a rotary part of {dim} of a head of "
                         f"{head_dim} is not supported")
    yarn = None
    if p["rope_type"] == "yarn":
        yarn = {k: float(v) for k, v in _take(params, YARN_KEYS, name).items()}
    return {"theta": float(p["rope_theta"]), "dim": dim, "yarn": yarn}


def _describe_laguna(config: Dict, name: str) -> Dict:
    c = _take(config, LAGUNA_KEYS, name)
    for key, taken, mechanism in LAGUNA_ONLY:
        if config.get(key, taken) != taken:
            raise ValueError(
                f"{name}: {key}={config[key]!r} is not supported "
                f"({mechanism}); only {key}={taken!r}")
    depth = c["num_hidden_layers"]
    types, ffns = list(c["layer_types"]), list(c["mlp_layer_types"])
    heads = tuple(int(h) for h in c["num_attention_heads_per_layer"])
    if len(types) != depth or set(types) - set(LAGUNA_LAYERS):
        raise ValueError(f"{name}: layer_types must list {depth} of "
                         f"{sorted(LAGUNA_LAYERS)}")
    if len(ffns) != depth or set(ffns) - {"dense", "sparse"}:
        raise ValueError(f"{name}: mlp_layer_types must list {depth} of "
                         "['dense', 'sparse']")
    if len(heads) != depth or any(h % c["num_key_value_heads"] for h in heads):
        raise ValueError(
            f"{name}: num_attention_heads_per_layer={list(heads)} must list "
            f"{depth} head counts that divide by num_key_value_heads="
            f"{c['num_key_value_heads']}")
    rope = {t: _rope_of(c["rope_parameters"].get(t, {}), c["head_dim"],
                        c["partial_rotary_factor"], f"{name} rope_parameters"
                        f"[{t!r}]") for t in set(types)}
    return dict(
        c,
        mixers=tuple(LAGUNA_LAYERS[t] for t in types),
        ffns=tuple("dense" if f == "dense" else "moe" for f in ffns),
        heads=heads, rope=tuple(rope[t] for t in types),
        window=tuple(c["sliding_window"] if t == "sliding_attention" else None
                     for t in types),
        # the kernels hand their output over in the compute dtype
        # (``causal_gqa_attention``'s ``out_dtype``)
        attention_out_dtype=None,
        eps=c["rms_norm_eps"], zero_centred_norm=False,
        shared_expert_gate=False,
        # an all-attention stack: as ``_describe_keye_vl2``'s, and why
        init_std={"embed": 1.0, "out": 0.02 * (2 * depth) ** -0.5},
        router_scores="sigmoid", expert_bias=True,
        routed_scaling_factor=float(c["moe_routed_scaling_factor"]),
        topk_eps=DEEPSEEK_V3_TOPK_EPS,
        expert_bias_update_rate=float(
            config.get("expert_bias_update_rate", 0.0)),
        tied=False,
    )


DESCRIBERS = {"qwen3_next": _describe_qwen3_next, "lfm2_moe": _describe_lfm2_moe,
              "KeyeVL2": _describe_keye_vl2,
              "deepseek_v3": _describe_deepseek_v3, "laguna": _describe_laguna}


def describe(config: Dict, name: str = "HybridMoELM") -> Dict:
    """The description ``HybridMoELM`` builds from, by the file's
    ``model_type`` (``qwen3_next`` where it has none).  A family whose
    attention layers are all alike gets its ``heads``, ``rope`` and
    ``window`` a layer from its scalars here."""
    family = config.get("model_type", "qwen3_next")
    if family not in DESCRIBERS:
        raise ValueError(
            f"{name}: model_type {family!r} is none of {sorted(DESCRIBERS)}")
    c = DESCRIBERS[family](config, name)
    if "heads" not in c:
        depth = c["num_hidden_layers"]
        c.update(heads=(c["num_attention_heads"],) * depth,
                 rope=({"theta": c["rope_theta"], "dim": c["rotary_dim"],
                        "yarn": None},) * depth,
                 window=(None,) * depth, attention_out_dtype=F32)
    return c


def rms_norm(x, w, eps, zero_centred: bool):
    """``x * rsqrt(mean(x^2) + eps) * w`` in float32; ``(1 + w)`` in place
    of ``w`` where the weight is zero-centred."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        (1.0 + w) if zero_centred else w)


def yarn_inv_freq(theta: float, dim: int, factor: float,
                  original_max_position_embeddings: float, beta_fast: float,
                  beta_slow: float, **_) -> np.ndarray:
    """YaRN's frequencies of a rotary part ``dim`` wide (arXiv:2309.00071,
    as the public ``_compute_yarn_parameters`` computes them), float64:
    ``f_e = theta^(-2i / dim)``, ``f_i = f_e / factor``; the pairs that turn
    fewer than ``beta_slow`` times over the original positions interpolate,
    those that turn more than ``beta_fast`` times keep ``f_e``, and a linear
    ramp over the pair index joins them between ``low = floor(d(beta_fast))``
    and ``high = ceil(d(beta_slow))``, ``d(r) = dim ln(original / (2 pi r))
    / (2 ln theta)``."""
    f_e = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair(rotations):
        return dim * np.log(original_max_position_embeddings / (
            rotations * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(pair(beta_fast)), 0)
    high = min(np.ceil(pair(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f_e / factor * ramp + f_e * (1 - ramp)


def rotary(x, theta: float, rotary_dim: int, yarn=None):
    """Rotate-half on the first ``rotary_dim`` of each head of ``(B, T, H,
    D)``; the rest passes through.  ``yarn``: ``YARN_KEYS`` of a layer's
    ``rope_parameters``, for YaRN's frequencies and its ``attention_factor``
    on cos and sin."""
    half = rotary_dim // 2
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary_dim)
    else:
        inv_freq = jnp.asarray(
            yarn_inv_freq(theta, rotary_dim, **yarn), F32)
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    if yarn is not None:
        cos, sin = (a * yarn["attention_factor"] for a in (cos, sin))
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1)


def rotary_pairs(x, theta: float):
    """Rotary over ADJACENT pairs of the whole last axis of ``(B, T, H,
    D)``: ``(x[2i], x[2i + 1])`` turns by ``t theta^(-2i / D)`` and stays
    where it lies (``rope_interleave``; a score is the same as under the
    public implementation, which moves the pairs' halves apart first, on
    queries and keys alike)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(d // 2, dtype=F32) * 2.0 / d)
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


class HybridMoELM:
    """See the module docstring.  The forward takes any ``T``: there are no
    learned positions."""

    def __init__(self, config: Dict, name: str = "HybridMoELM"):
        c = self.config = describe(config, name)
        if not config.get("norm_topk_prob", True):
            raise ValueError(f"{name}: norm_topk_prob=false is not supported")
        self.experts_held = tuple(
            config.get("experts_held") or (0, c["num_experts"]))
        lo, n = self.experts_held
        if not (0 <= lo and 0 < n and lo + n <= c["num_experts"]):
            raise ValueError(
                f"experts_held={list(self.experts_held)} is not a range of "
                f"the {c['num_experts']} experts")
        if any(h % c["num_key_value_heads"] for h in c["heads"]) or (
                "gated_delta_net" in c["mixers"]
                and c["linear_num_value_heads"] % c["linear_num_key_heads"]):
            raise ValueError("query / value heads must divide by K/V / key heads")
        self.set_compute_dtype(config.get("compute_dtype"))
        self.name = name
        self.depth = c["num_hidden_layers"]
        self.routed_layers = tuple(
            i for i in range(self.depth) if c["ffns"][i] == "moe")
        self.feed_blobs = ("tokens", "targets")
        plan = self._blob_plan()
        self._group_blobs = [(g, [s for s, _ in blobs]) for g, blobs in plan]
        self._blob_inits = {g: [how for _, how in blobs] for g, blobs in plan}
        self.layers = [_Group(k) for k, _ in self._group_blobs]
        self._blob_refs = {
            k: [_Ref(k, i) for i in range(len(shapes))]
            for k, shapes in self._group_blobs
        }
        # a selection bias and its load are checkpointed with their router,
        # from ``stats``
        self.biased_routers = tuple(
            f"l{i}_router" for i in self.routed_layers if c["expert_bias"])
        for group in self.biased_routers:
            for index in range(2):
                ref = _Ref(group, index)
                ref.collection = "stats"
                self._blob_refs[group].append(ref)

    def set_compute_dtype(self, dtype) -> None:
        """``None`` is float32 throughout."""
        self.compute_dtype = None if dtype is None else jnp.dtype(dtype)

    def is_attention_layer(self, i: int) -> bool:
        """Whether layer ``i`` mixes by softmax attention, gated or plain."""
        return self.config["mixers"][i] in (
            "gated_attention", "attention", "dsa_attention", "mla_attention",
            "window_attention")

    # ------------------------------------------------------------------
    def _mixer_shapes(self, i: int):
        """Layer ``i``'s mixer's blobs as ``(shape, how it is
        initialised)``."""
        c = self.config
        kind = c["mixers"][i]
        e = c["hidden_size"]
        hq, hkv, d = c["heads"][i], c["num_key_value_heads"], c["head_dim"]
        # ``out``: a matrix that writes to the residual stream
        w, norm, out = "matrix", "norm", "out"
        if kind in ("gated_attention", "attention", "dsa_attention",
                    "window_attention"):
            gated = kind in ("gated_attention", "window_attention")
            q_width = (2 if gated else 1) * hq * d
            blobs = [((e, q_width), w), ((e, hkv * d), w), ((e, hkv * d), w),
                     ((d,), norm), ((d,), norm), ((hq * d, e), out)]
            if kind == "dsa_attention":
                j, di = c["index_heads"], c["index_dim"]
                blobs += [((e, j * di), w), ((e, di), w), ((di,), "ones"),
                          ((di,), "zeros"), ((e, j), w)]
            return blobs
        if kind == "mla_attention":
            nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
            rank, dv = c["kv_lora_rank"], c["v_head_dim"]
            return [((e, hq * (nope + rope)), w), ((e, rank + rope), w),
                    ((rank,), norm), ((rank, hq * (nope + dv)), w),
                    ((hq * dv, e), out)]
        if kind == "short_conv":
            return [((e, 3 * e), w), ((e, c["conv_L_cache"]), w), ((e, e), out)]
        hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
        dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
        channels = 2 * hk * dk + hv * dv
        return [((e, channels + hv * dv), w), ((e, 2 * hv), w),
                ((channels, c["linear_conv_kernel_dim"]), w),
                ((hv,), "a_log"), ((hv,), "ones"), ((dv,), "ones"),
                ((hv * dv, e), out)]

    def _blob_plan(self) -> List[Tuple[str, List[Tuple[Tuple[int, ...], str]]]]:
        c = self.config
        e, v = c["hidden_size"], c["vocab_size"]
        f, fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
        n = self.experts_held[1]
        w, norm, out = "matrix", "norm", "out"
        mlp = lambda width: [((e, width), w), ((e, width), w),  # noqa: E731
                             ((width, e), out)]
        plan = [("embed", [((v, e), "embed")])]
        for i in range(c["num_hidden_layers"]):
            plan.append((f"l{i}_n1", [((e,), norm)]))
            plan.append((f"l{i}_mixer", self._mixer_shapes(i)))
            plan.append((f"l{i}_n2", [((e,), norm)]))
            if c["ffns"][i] == "dense":
                plan.append((f"l{i}_mlp", mlp(c["intermediate_size"])))
                continue
            plan.append((f"l{i}_router", [((e, c["num_experts"]), w)]))
            plan.append((f"l{i}_experts", [((n, e, f), w), ((n, e, f), w),
                                           ((n, f, e), out)]))
            if fs:
                plan.append((f"l{i}_shared", mlp(fs) + (
                    [((e, 1), w)] if c["shared_expert_gate"] else [])))
        plan.append(("norm_f", [((e,), norm)]))
        if not c["tied"]:
            plan.append(("head", [((e, v), w)]))
        return plan

    def init(self, seed: int = 0):
        """Matrices normal(0, 0.02), but where the description names another
        deviation for the embedding or for the matrices that write to the
        residual stream (``init_std``: ``embed``, ``out``); a norm's weight
        its identity (0 where zero-centred, else 1); the DeltaNet output
        norm 1; ``A_log = log U(0, 16)``, ``dt_bias = 1``; the indexer's
        LayerNorm weight 1, bias 0.  The ``stats`` are the routers'
        selection biases and loads, zeros: where the balancing rule starts
        from.  One jitted program with the key as its argument: blob by
        blob, eagerly, the 35 generators take a minute to compile on the
        chip."""
        identity = 0.0 if self.config["zero_centred_norm"] else 1.0
        std = {"matrix": 0.02, "embed": 0.02, "out": 0.02,
               **self.config.get("init_std", {})}

        def one(key, shape, how):
            if how in std:
                return std[how] * jax.random.normal(key, shape, F32)
            if how == "a_log":
                return jnp.log(jax.random.uniform(
                    key, shape, F32, minval=2.0 ** -20, maxval=16.0))
            return jnp.full(shape, {"norm": identity, "zeros": 0.0}.get(how, 1.0),
                            F32)

        def make(key):
            params: Dict[str, List[jnp.ndarray]] = {}
            for gi, (group, shapes) in enumerate(self._group_blobs):
                gkey = jax.random.fold_in(key, gi)
                params[group] = [
                    one(jax.random.fold_in(gkey, bi), shape, how)
                    for bi, (shape, how) in enumerate(
                        zip(shapes, self._blob_inits[group]))]
            return params

        experts = self.config["num_experts"]
        stats = {g: [jnp.zeros((experts,), F32), jnp.zeros((experts,), F32)]
                 for g in self.biased_routers}
        return jax.jit(make)(jax.random.PRNGKey(seed)), stats

    def param_multipliers(self):
        """lr_mult 1 everywhere; weight decay on the matrices only."""
        lr = {g: [1.0] * len(s) for g, s in self._group_blobs}
        decay = {g: [1.0 if len(x) > 1 else 0.0 for x in s]
                 for g, s in self._group_blobs}
        return lr, decay

    def num_params(self) -> int:
        return int(sum(int(np.prod(s)) for _, shapes in self._group_blobs
                       for s in shapes))

    # ------------------------------------------------------------------
    def _dot(self, x, w, out_dtype=None):
        cd = self.compute_dtype or F32
        y = jnp.dot(x.astype(cd), w.astype(cd), preferred_element_type=F32)
        return y.astype(out_dtype or cd)

    def _norm(self, x, w):
        return rms_norm(
            x, w, self.config["eps"], self.config["zero_centred_norm"])

    def _qkv(self, x, blobs, i: int, gated: bool = False):
        """Layer ``i``'s projections as heads, RMSNorm over each head of
        ``q`` and ``k``, its rotary: ``(q, k, v, gate or None)``."""
        q_proj, k_proj, v_proj, q_norm, k_norm = blobs[:5]
        c = self.config
        b, t, _ = x.shape
        hq, hkv, d = c["heads"][i], c["num_key_value_heads"], c["head_dim"]
        rope = c["rope"][i]
        gate = None
        if gated:
            qg = self._dot(x, q_proj).reshape(b, t, hq, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
        else:
            q = self._dot(x, q_proj).reshape(b, t, hq, d)
        k = self._dot(x, k_proj).reshape(b, t, hkv, d)
        v = self._dot(x, v_proj).reshape(b, t, hkv, d)
        q = rotary(self._norm(q, q_norm), rope["theta"], rope["dim"],
                   rope["yarn"])
        k = rotary(self._norm(k, k_norm), rope["theta"], rope["dim"],
                   rope["yarn"])
        return q, k, v, gate

    def _softmax_attention(self, x, blobs, i: int, gated: bool):
        """Layer ``i``'s attention over its window, if it has one, from the
        normed input to the output projection."""
        c = self.config
        b, t, _ = x.shape
        q, k, v, gate = self._qkv(x, blobs, i, gated)
        attn = causal_gqa_attention(
            q, k, v, compute_dtype=self.compute_dtype, window=c["window"][i],
            out_dtype=c["attention_out_dtype"])
        # gated with heads side by side, (B, T, Hq D), as the kernels write
        # the output and o_proj reads it: heads apart, (.., Hq, D) tiles
        # otherwise, and the float32 output and its cotangent are each
        # copied from one tiling to the other (0.8 ms each on the v5e)
        attn = attn.reshape(b, t, -1)
        if gated:
            attn = attn * jax.nn.sigmoid(gate.reshape(b, t, -1).astype(F32))
        return self._dot(attn, blobs[5], F32)

    def _gated_attention(self, x, blobs, i: int):
        return self._softmax_attention(x, blobs, i, gated=True)

    _window_attention = _gated_attention

    def _attention(self, x, blobs, i: int):
        return self._softmax_attention(x, blobs, i, gated=False)

    def _dsa_indexer(self, u, blobs):
        """The indexer's three projections of ``u``: ``qI (B, T, J, Di)``
        and its one shared key ``kI (B, T, Di)`` (LayerNorm first), both
        under rotary and in the compute dtype, and the heads' weights ``(B,
        T, J)`` float32 with the two scale factors in them."""
        index_q, index_k, norm_w, norm_b, index_w = blobs
        c = self.config
        b, t, _ = u.shape
        j, di = c["index_heads"], c["index_dim"]
        cd = self.compute_dtype or F32
        qi = self._dot(u, index_q, F32).reshape(b, t, j, di)
        ki = self._dot(u, index_k, F32)
        mean = jnp.mean(ki, -1, keepdims=True)
        ki = (ki - mean) * jax.lax.rsqrt(
            jnp.mean((ki - mean) ** 2, -1, keepdims=True) + INDEX_NORM_EPS
        ) * norm_w + norm_b
        qi = rotary(qi, c["rope_theta"], di)
        ki = rotary(ki[:, :, None, :], c["rope_theta"], di)[:, :, 0]
        w = self._dot(u, index_w, F32) * (j ** -0.5 * di ** -0.5)
        return qi.astype(cd), ki.astype(cd), w

    def _dsa_attention(self, x, blobs, mask, i: int = 0):
        """Layer ``i``'s plain grouped attention over the keys ``mask``
        keeps; beside the output, what the alignment loss reads of it: the
        scaled queries, the keys and the rows' log-sum-exp."""
        b, t, _ = x.shape
        q, k, v, _ = self._qkv(x, blobs, i)
        q = sparse_attention.scaled_queries(q, self.compute_dtype)
        k = k.astype(q.dtype)
        attn, lse = sparse_attention.masked_attention(
            q, k, v, mask, block_q=self.config["index_block"])
        return self._dot(attn.reshape(b, t, -1), blobs[5], F32), q, k, lse

    def _dsa_select(self, i: int, normed, blobs):
        """The indexer's parts and the selection of ``normed``'s queries as
        bits.  No gradient reaches ``normed`` from here: the indexer learns
        from its alignment loss alone."""
        c = self.config
        with jax.named_scope(f"DSAIndexer:l{i}_indexer"):
            qi, ki, w = jax.checkpoint(self._dsa_indexer)(
                jax.lax.stop_gradient(normed), blobs)
            scores = sparse_attention.index_scores_by_run(
                *jax.lax.stop_gradient((qi, w, ki)), block_q=c["index_block"])
        with jax.named_scope(f"DSASelect:l{i}_select"):
            mask = sparse_attention.select(
                scores, normed.shape[1], c["index_topk"],
                block_q=c["index_block"])
        return qi, ki, w, mask

    def _dsa_mixer(self, i: int, normed, blobs, probe: bool = False):
        """A selected-key attention layer's mixer under its four scopes:
        ``Attn(normed)`` and the layer's alignment loss, the mean over its
        queries (with ``probe``, a pair: that loss and the selected keys'
        share of the dense attention's probability).  Kept between forward and
        backward, beside the normed input: the selection as bits (33.5 MB at
        16,384 tokens), the attention's output and log-sum-exp (270 MB), and
        the alignment loss's gradient to ``qI`` / ``w`` / ``kI`` (36.5 MB),
        which its forward computes with its value
        (``sparse_attention.alignment_loss``): the loss keeps neither the
        scaled queries and keys (151 MB: ``_dsa_attention``'s backward makes
        its own again) nor the indexer's parts; the index scores of ``(T,
        T)`` float32 live from ``DSAIndexer`` to ``DSASelect`` in the forward
        pass alone."""
        c = self.config
        b, t, _ = normed.shape
        qi, ki, w, mask = self._dsa_select(i, normed, blobs[6:])
        with jax.named_scope(f"DSAAttention:l{i}_mixer"):
            out, q, k, lse = jax.checkpoint(
                partial(self._dsa_attention, i=i), policy=MIXER_KEEPS)(
                    normed, blobs[:6], mask)
        with jax.named_scope(f"DSAIndexerLoss:l{i}_align"):
            align = sparse_attention.alignment_loss(
                qi, w, ki, q, k, lse, mask, block_q=c["index_block"]) / (b * t)
        if probe:
            return out, (align, sparse_attention.selection_mass(
                q, k, mask, block_q=c["index_block"]))
        return out, align

    def _mla_latent(self, x, blobs):
        """What the latent attention reads, from the normed input: ``q_nope
        (B, T, H, nope)``, ``q_rope (B, T, H, rope)``, ``k_nope`` and ``v (B,
        T, H, nope)`` from the normed latent, and the ONE ``k_rope (B, T, 1,
        rope)``; rotary over adjacent pairs on the rope parts alone, in
        float32; all five in the compute dtype."""
        q_proj, kv_a, kv_norm, kv_b = blobs
        c = self.config
        b, t, _ = x.shape
        h, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"])
        rank, theta = c["kv_lora_rank"], c["rope_theta"]
        cd = self.compute_dtype or F32
        q = self._dot(x, q_proj, F32).reshape(b, t, h, nope + rope)
        latent = self._dot(x, kv_a, F32)
        k_rope = latent[..., rank:].reshape(b, t, 1, rope)
        kv = self._dot(self._norm(latent[..., :rank], kv_norm), kv_b)
        kv = kv.reshape(b, t, h, nope + c["v_head_dim"])
        return (q[..., :nope].astype(cd),
                rotary_pairs(q[..., nope:], theta).astype(cd),
                kv[..., :nope], rotary_pairs(k_rope, theta).astype(cd),
                kv[..., nope:])

    def _mla_attention(self, parts, o_proj):
        """The kernels (or the XLA path, as ``attention_path`` says) and the
        output projection; heads side by side as the kernels write them."""
        attn = causal_mla_attention(*parts, compute_dtype=self.compute_dtype)
        b, t = attn.shape[:2]
        return self._dot(attn.reshape(b, t, -1), o_proj, F32)

    def _mla_mixer(self, i: int, normed, blobs):
        """A latent-attention layer's mixer under its two scopes, each with
        its ``jax.checkpoint`` inside: ``MLALatent`` holds what making q, k
        and v costs (``q_proj``, ``kv_a_proj``, the latent's norm,
        ``kv_b_proj``, rotary), ``MLAAttention`` the attention and
        ``o_proj``.  Kept between forward and backward, beside the normed
        input: the five parts in the compute dtype (472 MB a layer at 2 x
        8,192 tokens in bfloat16) and what the flash kernels name."""
        with jax.named_scope(f"MLALatent:l{i}_latent"):
            parts = jax.checkpoint(self._mla_latent)(normed, blobs[:4])
        with jax.named_scope(f"MLAAttention:l{i}_mixer"):
            return jax.checkpoint(self._mla_attention, policy=MIXER_KEEPS)(
                parts, blobs[4])

    def _short_conv(self, x, blobs):
        in_proj, conv, out_proj = blobs
        return self._dot(
            gated_short_conv(self._dot(x, in_proj), conv), out_proj, F32)

    def _gated_delta_net(self, x, blobs):
        in_qkvz, in_ba, conv, a_log, dt_bias, norm, out_proj = blobs
        c = self.config
        b, t, _ = x.shape
        hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
        dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
        channels = 2 * hk * dk + hv * dv
        qkvz = self._dot(x, in_qkvz)
        ba = self._dot(x, in_ba, F32)
        mixed, z = qkvz[..., :channels], qkvz[..., channels:]
        mixed = jax.nn.silu(causal_depthwise_conv(mixed, conv))
        q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
        k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
        v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        l2 = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        # key head j // (hv // hk) serves value head j
        q = jnp.repeat(l2(q) * dk ** -0.5, hv // hk, axis=2)
        k = jnp.repeat(l2(k), hv // hk, axis=2)
        o = gated_delta_rule(q, k, v, g, beta,
                             compute_dtype=self.compute_dtype)
        o = norm * o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + c["eps"])
        o = o * jax.nn.silu(z.reshape(b, t, hv, dv).astype(F32))
        return self._dot(o.reshape(b, t, hv * dv), out_proj, F32)

    def _route(self, h2d, norm_w, w_router, bias=None):
        # the router reads the float32 normed input, recomputed here so that
        # only its compute-dtype copy is kept for the experts
        c = self.config
        weights, ids = moe.route(
            self._norm(h2d, norm_w), w_router, c["num_experts_per_tok"],
            scores=c["router_scores"], bias=bias,
            scale=c["routed_scaling_factor"], eps=c["topk_eps"])
        order, counts = moe.plan(ids, *self.experts_held)
        return weights, ids, order, counts

    def _held_experts(self, x2d, weights, ids, order, counts, blobs):
        c = self.config
        lo, n = self.experts_held
        rows = moe.fast_rows_for(
            x2d.shape[0], c["num_experts_per_tok"], c["num_experts"], n)
        return moe.held_experts(
            x2d, weights, ids, order, counts, *blobs, lo=lo, fast_rows=rows,
            compute_dtype=self.compute_dtype)

    def _shared_expert(self, x2d, blobs):
        """The shared expert's gated MLP; times ``sigmoid(x w_s)`` where the
        family gates its output (a fourth blob)."""
        y = moe.gated_mlp(x2d, *blobs[:3], self.compute_dtype)
        if len(blobs) == 3:
            return y
        return y * jax.nn.sigmoid(jnp.dot(
            x2d.astype(F32), blobs[3], precision=jax.lax.Precision.HIGHEST))

    def _dense_mlp(self, x2d, blobs):
        return moe.gated_mlp(x2d, *blobs, self.compute_dtype)

    def _layer(self, params, i: int, x, bias=None, probe: bool = False):
        """``h = x + mixer(norm(x)); y = h + ffn(norm(h))``; of a layer with
        routed experts also the held experts' assignment counts ``(n,)``
        and, given its selection ``bias``, every expert's load
        ``(num_experts,)``; else ``None``; of a selected-key attention layer
        what ``_dsa_mixer`` hands up (its alignment loss), else ``None``."""
        c = self.config
        cd = self.compute_dtype or F32
        kind = c["mixers"][i]
        aux = None
        with jax.named_scope(f"RMSNorm:l{i}_n1"):
            normed = self._norm(x, params[f"l{i}_n1"][0]).astype(cd)
        if kind == "dsa_attention":
            mixed, aux = self._dsa_mixer(i, normed, params[f"l{i}_mixer"], probe)
            h = x + mixed
        elif kind == "mla_attention":
            h = x + self._mla_mixer(i, normed, params[f"l{i}_mixer"])
        else:
            mixer = getattr(self, "_" + kind)
            if self.is_attention_layer(i):  # the layer's heads, rotary, window
                mixer = partial(mixer, i=i)
            with jax.named_scope(f"{MIXERS[kind]}:l{i}_mixer"):
                h = x + jax.checkpoint(mixer, policy=MIXER_KEEPS)(
                    normed, params[f"l{i}_mixer"])
        b, t, e = h.shape
        with jax.named_scope(f"RMSNorm:l{i}_n2"):
            normed = self._norm(h, params[f"l{i}_n2"][0]).astype(cd)
            normed = normed.reshape(b * t, e)
        if c["ffns"][i] == "dense":
            with jax.named_scope(f"DenseMLP:l{i}_mlp"):
                y = jax.checkpoint(self._dense_mlp)(
                    normed, params[f"l{i}_mlp"])
            return h + y.reshape(b, t, e), None, None, aux
        with jax.named_scope(f"MoERouter:l{i}_router"):
            weights, ids, order, counts = jax.checkpoint(self._route)(
                h.reshape(b * t, e), params[f"l{i}_n2"][0],
                *params[f"l{i}_router"], bias)
            # what the balancing rule reads: every expert's load, held or not
            load = None if bias is None else moe.load(ids, c["num_experts"])
        with jax.named_scope(f"MoEExperts:l{i}_experts"):
            y = jax.checkpoint(self._held_experts)(
                normed, weights, ids, order, counts, params[f"l{i}_experts"])
        if c["shared_expert_intermediate_size"]:
            with jax.named_scope(f"MoEShared:l{i}_shared"):
                shared = jax.checkpoint(self._shared_expert)(
                    normed, params[f"l{i}_shared"])
            y = y + shared
        return h + y.reshape(b, t, e), counts, load, aux

    def _hidden(self, params, tokens, stats=None, probe: bool = False):
        """The last layer's output, the held experts' counts ``(routed
        layers, n)``, by router group the load of every expert whose
        selection bias ``stats`` holds, and the selected-key attention
        layers' alignment losses, a list in layer order."""
        tokens = tokens.astype(jnp.int32)
        with jax.named_scope("Embedding:embed"):
            x = jnp.take(params["embed"][0], tokens, axis=0)
        biases = {g: blobs[0] for g, blobs in (stats or {}).items()}
        counts, loads, aligns = [], {}, []
        for i in range(self.depth):
            group = f"l{i}_router"
            x, held, load, aux = self._layer(
                params, i, x, biases.get(group), probe)
            if held is not None:
                counts.append(held)
            if load is not None:
                loads[group] = load
            if aux is not None:
                aligns.append(aux)
        if not counts:  # no layer routes
            counts = jnp.zeros((0, self.experts_held[1]), jnp.int32)
        else:
            counts = jnp.stack(counts)
        return x, counts, loads, aligns

    def _head_blobs(self, params):
        """The final norm's weight and the head as ``(E, vocab)``: a tied
        head is the embedding's transpose, which the product absorbs."""
        head = (params["embed"][0].T if self.config["tied"]
                else params["head"][0])
        return params["norm_f"][0], head

    def _head(self, x, norm_f, head):
        return self._dot(self._norm(x, norm_f), head, F32)

    def forward_logits(self, params, tokens, stats=None):
        """``(B, T)`` int tokens -> ``(B, T, vocab)`` float32 logits."""
        x = self._hidden(params, tokens, stats)[0]
        with jax.named_scope("LMHead:head"):
            return self._head(x, *self._head_blobs(params))

    def loss_fn(self, params, stats, batch, rng=None, train=True):
        """Next-token cross-entropy over the global token count, plus the
        selected-key attention layers' alignment losses where the model has
        them: the two reach disjoint parameters (the indexer reads a
        ``stop_gradient`` of its input, the selection passes no gradient and
        the attention's probabilities enter the alignment loss as
        constants), so one backward pass trains both.  Returns ``(loss,
        (aux, stats))``; ``aux`` is empty but for such a model, where it
        holds the two losses apart (``lm_loss``, ``indexer_loss``, and
        ``indexer_loss_by_layer``): logits of ``(B, T, vocab)`` are not kept
        beside a training step (``forward_logits`` gives them).  A training
        step moves each selection bias in ``stats`` one step of its
        balancing rule."""
        x, _, loads, aligns = self._hidden(params, batch["tokens"], stats)
        targets = batch["targets"]
        tied = self.config["tied"]

        def nll_sum(x, norm_f, head):
            return lm_loss.nll_sum(self._norm(x, norm_f), head, targets,
                                   self.compute_dtype, vocab_first=tied)

        with jax.named_scope("LMHead:head"):
            # a tied head is read where the embedding lies, (vocab, E)
            total = jax.checkpoint(nll_sum, policy=HEAD_KEEPS)(
                x, params["norm_f"][0], params["embed" if tied else "head"][0])
        if train and loads:
            rate = self.config["expert_bias_update_rate"]
            stats = {**stats, **{
                g: [moe.balance(stats[g][0], load, rate), load]
                for g, load in loads.items()}}
        loss = total / jnp.asarray(targets.size, F32)
        if not aligns:
            return loss, ({}, stats)
        by_layer = jnp.stack(aligns)
        aux = {"lm_loss": loss, "indexer_loss": jnp.sum(by_layer),
               "indexer_loss_by_layer": by_layer}
        return loss + aux["indexer_loss"], (aux, stats)

    def forward(self, params, stats, batch, rng=None):
        return {"logits": self.forward_logits(params, batch["tokens"], stats)}

    def routing_counts(self, params, tokens, stats=None):
        """Assignments each held expert receives, per layer that routes
        (``routed_layers``): ``(routed layers, n)`` int32 for the ``(B, T)``
        tokens given."""
        return self._hidden(params, tokens, stats)[1]

    def selection_readings(self, params, tokens, stats=None):
        """Of the ``(B, T)`` tokens given, per selected-key attention layer:
        its alignment loss, and the share of the dense causal attention's
        probability (head mean) that its selected keys hold; and, of the
        same pass, ``routing_counts``.  A forward pass, outside any training
        step; ``(layers,)`` float32 each, empty where the model has no such
        layer."""
        _, counts, _, pairs = self._hidden(params, tokens, stats, probe=True)
        loss, mass = ([jnp.stack(x) for x in zip(*pairs)] if pairs
                      else [jnp.zeros((0,), F32)] * 2)
        return {"indexer_loss": loss, "selection_mass": mass,
                "held_counts": counts}

    # ------------------------------------------------------------------
    def prefill_with_kv(self, *args, **kwargs):
        raise NotImplementedError(
            f"{self.name}: generation is not supported — a linear-attention "
            "layer decodes from a recurrent state, a short convolution from "
            "its last inputs, beside the paged K/V, which serve/ does not "
            "have (ROADMAP R5)")

    decode_step_with_kv = prefill_with_kv

    def ring_hop_bytes_per_iter(self, batch: int) -> int:
        return 0  # no sequence-parallel ring in this model


def routing_gauges(counts, tokens: int) -> Dict[str, List[float]]:
    """From ``routing_counts`` (``(routed layers, n)``) and the number of
    tokens routed: per layer, assignments to held experts a token, and the
    largest held expert's load over the mean."""
    counts = np.asarray(counts, np.float64)
    mean = np.maximum(counts.mean(axis=1), 1e-30)
    return {
        "held_assignments_per_token": list(counts.sum(axis=1) / tokens),
        "held_load_skew": list(counts.max(axis=1) / mean),
    }
