"""Operations of kanana-2-30b-a3b's block stack (``model_type:
deepseek_v3``), from the keys of its configuration file and nothing of the
program.

The convention of ``flops.py``, ``lm_flops.py``, ``lfm2_flops.py`` and
``keye_flops.py``: a multiply-accumulate is 2 operations, the backward pass
costs twice the forward, recomputation is not counted.  The count is of the
work the MODEL asks for at its PUBLISHED widths, whatever implements it: the
latent attention's projections (``q_proj``, ``kv_a_proj``, ``kv_b_proj``)
under ``MLALatent``; its scores ``qk_nope_head_dim + qk_rope_head_dim`` wide
(192: a kernel that pads the 64-wide rope term to a whole MXU pass earns
nothing for the padding) and its values ``v_head_dim`` wide (128), causal,
over ``(T + 1) / 2`` keys a token, and ``o_proj``, under ``MLAAttention``; the
dense MLP of the leading layers; the router for every token of the others;
routed experts at the EXPECTED ``top_k * held / experts`` assignments a token
(the measured share moves with the seed; the expectation keeps ``mfu`` a
constant times the rate); the shared experts, one gated MLP of
``n_shared_experts x moe_intermediate_size``, for every token; the untied
head once (the embedding gather is no product).  Norms, rotary, the softmax
and the router's sigmoid are not MXU work and count 0.
"""

# layer types as the program's scopes name them (ARCHITECTURE.md)
TYPES = ("Embedding", "RMSNorm", "MLALatent", "MLAAttention", "DenseMLP",
         "MoERouter", "MoEExperts", "MoEShared", "LMHead")


def forward_flops_per_token_by_type(c, seq_len):
    """{layer type: operations of one token's forward pass}."""
    e, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, rank = c["v_head_dim"], c["kv_lora_rank"]
    latent = (
        2 * e * h * (nope + rope)  # q_proj
        + 2 * e * (rank + rope)  # kv_a_proj: the latent and the one rope key
        + 2 * rank * h * (nope + dv)  # kv_b_proj
    )
    attention = (
        2 * h * (nope + rope + dv) * (seq_len + 1) / 2  # q k^T and p v
        + 2 * h * dv * e  # o_proj
    )
    mlp = lambda width: 3 * 2 * e * width  # noqa: E731
    assignments = (c["num_experts_per_tok"] * c["experts_held"][1]
                   / c["n_routed_experts"])
    layers = c["num_hidden_layers"]
    dense = min(c["first_k_dense_replace"], layers)
    routed = layers - dense
    out = dict.fromkeys(TYPES, 0.0)
    out["MLALatent"] = float(latent * layers)
    out["MLAAttention"] = float(attention * layers)
    out["DenseMLP"] = float(mlp(c["intermediate_size"]) * dense)
    out["MoERouter"] = float(2 * e * c["n_routed_experts"] * routed)
    out["MoEExperts"] = float(
        assignments * mlp(c["moe_intermediate_size"]) * routed)
    out["MoEShared"] = float(
        mlp(c["n_shared_experts"] * c["moe_intermediate_size"]) * routed)
    out["LMHead"] = float(2 * e * c["vocab_size"])
    return out


def train_flops_per_sequence_by_type(config, seq_len):
    """{layer type: operations to train on one sequence of ``seq_len``}."""
    per_token = forward_flops_per_token_by_type(config, seq_len)
    return {k: 3.0 * seq_len * v for k, v in per_token.items()}


def train_flops_per_sequence(config, seq_len):
    return sum(train_flops_per_sequence_by_type(config, seq_len).values())
