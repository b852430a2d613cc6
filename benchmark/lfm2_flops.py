"""Operations of the LFM2 mixture-of-experts LM, from the keys of its
configuration file and nothing of the program.

The convention of ``flops.py`` and ``lm_flops.py``: a multiply-accumulate is 2
operations, the backward pass costs twice the forward, recomputation is not
counted.  The count is of the work the model asks for, whatever implements
it: projections as published; the causal depthwise convolution's
``conv_L_cache`` taps; causal attention over ``(T + 1) / 2`` keys a token;
the dense MLP of the leading layers and the router for every token of
theirs; routed experts at the EXPECTED ``top_k * held / experts`` assignments
a token (the measured share moves with the seed; the expectation keeps
``mfu`` a constant times the rate); the tied head once (the embedding gather
is no product).  Norms, the softmax, the convolution's two gates and the
router's sigmoid are not MXU work and count 0.
"""

# layer types as the program's scopes name them (ARCHITECTURE.md)
TYPES = ("Embedding", "RMSNorm", "ShortConv", "Attention", "DenseMLP",
         "MoERouter", "MoEExperts", "LMHead")


def forward_flops_per_token_by_type(c, seq_len):
    """{layer type: operations of one token's forward pass}."""
    e = c["hidden_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim", e // hq)
    conv = (
        2 * e * 3 * e  # in_proj: [B | C | u]
        + 2 * e * c["conv_L_cache"]  # the taps
        + 2 * e * e  # out_proj
    )
    attention = (
        2 * e * hq * d  # q_proj
        + 2 * 2 * e * hkv * d  # k_proj, v_proj
        + 2 * 2 * hq * d * (seq_len + 1) / 2  # q k^T and p v, causal
        + 2 * hq * d * e  # o_proj
    )
    mlp = lambda width: 3 * 2 * e * width  # noqa: E731
    assignments = (
        c["num_experts_per_tok"] * c["experts_held"][1] / c["num_experts"])
    types = c["layer_types"]
    dense = c["num_dense_layers"]
    routed = c["num_hidden_layers"] - dense
    out = dict.fromkeys(TYPES, 0.0)
    out["ShortConv"] = float(conv * types.count("conv"))
    out["Attention"] = float(attention * types.count("full_attention"))
    out["DenseMLP"] = float(mlp(c["intermediate_size"]) * dense)
    out["MoERouter"] = float(2 * e * c["num_experts"] * routed)
    out["MoEExperts"] = float(
        assignments * mlp(c["moe_intermediate_size"]) * routed)
    out["LMHead"] = float(2 * e * c["vocab_size"])
    return out


def train_flops_per_sequence_by_type(config, seq_len):
    """{layer type: operations to train on one sequence of ``seq_len``}."""
    per_token = forward_flops_per_token_by_type(config, seq_len)
    return {k: 3.0 * seq_len * v for k, v in per_token.items()}


def train_flops_per_sequence(config, seq_len):
    return sum(train_flops_per_sequence_by_type(config, seq_len).values())
