"""Operations of the hybrid linear-attention / sparse-expert LM, from the
keys of its configuration file and nothing of the program.

The convention of ``flops.py``: a multiply-accumulate is 2 operations, the
backward pass costs twice the forward, recomputation is not counted.  The
count is of the work the model asks for, whatever implements it:
projections as published; the causal depthwise convolution; the delta rule in
its recurrent form, ``6 * dk * dv`` a value head a token (decay-free: the
read ``S^T k``, the rank-one write, the read ``S^T q``); causal attention over
``(T + 1) / 2`` keys a token; router and shared expert for every token; routed
experts at the EXPECTED ``top_k * held / experts`` assignments a token (the
measured share moves with the seed; the expectation keeps ``mfu`` a constant
times the rate).  Norms, softmaxes, gates and the embedding gather are not
MXU work and count 0.
"""

# layer types as the program's scopes name them (ARCHITECTURE.md)
TYPES = ("Embedding", "RMSNorm", "GatedDeltaNet", "GatedAttention",
         "MoERouter", "MoEExperts", "MoEShared", "LMHead")


def is_attention_layer(i, c):
    return (i + 1) % c["full_attention_interval"] == 0


def forward_flops_per_token_by_type(c, seq_len):
    """{layer type: operations of one token's forward pass}."""
    e = c["hidden_size"]
    hq, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    conv_channels = 2 * hk * dk + hv * dv
    delta = (
        2 * e * (conv_channels + hv * dv)  # in_proj_qkvz
        + 2 * e * 2 * hv  # in_proj_ba
        + 2 * conv_channels * c["linear_conv_kernel_dim"]
        + 6 * dk * dv * hv  # the recurrence
        + 2 * hv * dv * e  # out_proj
    )
    attention = (
        2 * e * 2 * hq * d  # q_proj with the gate
        + 2 * 2 * e * hkv * d  # k_proj, v_proj
        + 2 * 2 * hq * d * (seq_len + 1) / 2  # q k^T and p v, causal
        + 2 * hq * d * e  # o_proj
    )
    mlp = lambda width: 3 * 2 * e * width  # noqa: E731
    held = c["experts_held"][1]
    assignments = c["num_experts_per_tok"] * held / c["num_experts"]
    layers = range(c["num_hidden_layers"])
    n_attention = sum(is_attention_layer(i, c) for i in layers)
    out = dict.fromkeys(TYPES, 0.0)
    out["GatedDeltaNet"] = float(delta * (len(layers) - n_attention))
    out["GatedAttention"] = float(attention * n_attention)
    out["MoERouter"] = float(2 * e * c["num_experts"] * len(layers))
    out["MoEExperts"] = float(
        assignments * mlp(c["moe_intermediate_size"]) * len(layers))
    out["MoEShared"] = float(
        (mlp(c["shared_expert_intermediate_size"]) + 2 * e) * len(layers))
    out["LMHead"] = float(2 * e * c["vocab_size"])
    return out


def train_flops_per_sequence_by_type(config, seq_len):
    """{layer type: operations to train on one sequence of ``seq_len``}."""
    per_token = forward_flops_per_token_by_type(config, seq_len)
    return {k: 3.0 * seq_len * v for k, v in per_token.items()}


def train_flops_per_sequence(config, seq_len):
    return sum(train_flops_per_sequence_by_type(config, seq_len).values())
