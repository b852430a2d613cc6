"""Operations of Laguna-XS.2's block stack (``model_type: laguna``), from the
keys of its configuration file and nothing of the program.

The convention of ``flops.py``, ``lm_flops.py`` and ``kanana_flops.py``: a
multiply-accumulate is 2 operations, the backward pass costs twice the
forward, recomputation is not counted.  The count is of the work the MODEL
asks for at its PUBLISHED widths, whatever implements it: a layer's own
query heads (``num_attention_heads_per_layer``); its projections (``q_proj``
with the gate's half, ``k_proj``, ``v_proj``, ``o_proj``), scores and values
over the keys a query sees: ``(T + 1) / 2`` a token under full causal
attention, ``min(t + 1, sliding_window)`` for token ``t`` under a window (the
pairs a kernel meets outside the window are in its time and not here),
under ``GatedAttention`` and ``WindowAttention`` as the program's scopes
split them; the dense MLP where ``mlp_layer_types`` says ``dense``; the router
for every token of the others; routed experts at the EXPECTED ``top_k * held
/ experts`` assignments a token; the shared expert for every token; the
untied head once (the embedding gather is no product).  Norms, rotary, the
softmax, the output gate and the router's sigmoid are not MXU work and count
0.
"""

# layer types as the program's scopes name them (ARCHITECTURE.md)
TYPES = ("Embedding", "RMSNorm", "GatedAttention", "WindowAttention",
         "DenseMLP", "MoERouter", "MoEExperts", "MoEShared", "LMHead")
SCOPE = {"full_attention": "GatedAttention",
         "sliding_attention": "WindowAttention"}


def keys_per_query(kind, c, seq_len):
    """The mean keys a query of a layer type sees over a sequence."""
    if kind == "full_attention":
        return (seq_len + 1) / 2
    w = min(c["sliding_window"], seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def forward_flops_per_token_by_type(c, seq_len):
    """{layer type: operations of one token's forward pass}."""
    e, d, hkv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    mlp = lambda width: 3 * 2 * e * width  # noqa: E731
    assignments = (c["num_experts_per_tok"] * c["experts_held"][1]
                   / c["num_experts"])
    out = dict.fromkeys(TYPES, 0.0)
    for i in range(c["num_hidden_layers"]):
        kind, h = c["layer_types"][i], c["num_attention_heads_per_layer"][i]
        out[SCOPE[kind]] += (
            2 * e * 2 * h * d  # q_proj: queries and their gates
            + 2 * 2 * e * hkv * d  # k_proj, v_proj
            + 2 * 2 * h * d * keys_per_query(kind, c, seq_len)  # q k^T, p v
            + 2 * h * d * e)  # o_proj
        if c["mlp_layer_types"][i] == "dense":
            out["DenseMLP"] += mlp(c["intermediate_size"])
            continue
        out["MoERouter"] += 2 * e * c["num_experts"]
        out["MoEExperts"] += assignments * mlp(c["moe_intermediate_size"])
        out["MoEShared"] += mlp(c["shared_expert_intermediate_size"])
    out["LMHead"] = float(2 * e * c["vocab_size"])
    return {k: float(v) for k, v in out.items()}


def train_flops_per_sequence_by_type(config, seq_len):
    """{layer type: operations to train on one sequence of ``seq_len``}."""
    per_token = forward_flops_per_token_by_type(config, seq_len)
    return {k: 3.0 * seq_len * v for k, v in per_token.items()}


def train_flops_per_sequence(config, seq_len):
    return sum(train_flops_per_sequence_by_type(config, seq_len).values())
