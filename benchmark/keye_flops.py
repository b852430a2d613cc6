"""Operations of Keye-VL-2.0's language model, from the keys of its
configuration file and nothing of the program.

The convention of ``flops.py``, ``lm_flops.py`` and ``lfm2_flops.py``: a
multiply-accumulate is 2 operations, the backward pass costs twice the
forward, recomputation is not counted.  The count is of the work the MODEL
asks for, whatever implements it: projections as published; the indexer's
three projections and its scores over ALL causal pairs, ``(T + 1) / 2`` keys a
token (it has to score a key to leave it out); attention, both products, over
the SELECTED pairs alone, ``sum_t min(t + 1, topk) / T`` keys a token (a
masked dense pass executes every causal pair for them and earns no more);
the router for every token; routed experts at the EXPECTED ``top_k * held /
experts`` assignments a token; the untied head once (the embedding gather is
no product).  Norms, softmaxes, the ReLU and the head weighting of the index
scores, the selection (comparisons and counts) and the alignment loss (which
recomputes products already counted) are not MXU work of the model and count
0: ``DSASelect`` and ``DSAIndexerLoss`` are in ``TYPES`` with nothing.
"""

# layer types as the program's scopes name them (ARCHITECTURE.md)
TYPES = ("Embedding", "RMSNorm", "DSAIndexer", "DSASelect", "DSAAttention",
         "DSAIndexerLoss", "MoERouter", "MoEExperts", "LMHead")


def causal_pairs_per_token(seq_len):
    return (seq_len + 1) / 2


def selected_pairs_per_token(seq_len, topk):
    """``sum_t min(t + 1, topk) / T``: 1,920.06 at 16,384 and 2,048."""
    full = min(topk, seq_len)  # rows 0 .. full - 1 keep all their keys
    return (full * (full + 1) / 2 + (seq_len - full) * topk) / seq_len


def forward_flops_per_token_by_type(c, seq_len):
    """{layer type: operations of one token's forward pass}."""
    e, layers = c["hidden_size"], c["num_hidden_layers"]
    hq, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    sa = c["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    indexer = (
        2 * e * (j * di + di + j)  # index_q, index_k, index_w
        + 2 * j * di * causal_pairs_per_token(seq_len)  # qI . kI, every head
    )
    attention = (
        2 * e * hq * d  # q_proj
        + 2 * 2 * e * hkv * d  # k_proj, v_proj
        + 2 * 2 * hq * d * selected_pairs_per_token(seq_len, sa["topk"])
        + 2 * hq * d * e  # o_proj
    )
    assignments = (
        c["num_experts_per_tok"] * c["experts_held"][1] / c["num_experts"])
    out = dict.fromkeys(TYPES, 0.0)
    out["DSAIndexer"] = float(indexer * layers)
    out["DSAAttention"] = float(attention * layers)
    out["MoERouter"] = float(2 * e * c["num_experts"] * layers)
    out["MoEExperts"] = float(
        assignments * 3 * 2 * e * c["moe_intermediate_size"] * layers)
    out["LMHead"] = float(2 * e * c["vocab_size"])
    return out


def train_flops_per_sequence_by_type(config, seq_len):
    """{layer type: operations to train on one sequence of ``seq_len``}."""
    per_token = forward_flops_per_token_by_type(config, seq_len)
    return {k: 3.0 * seq_len * v for k, v in per_token.items()}


def train_flops_per_sequence(config, seq_len):
    return sum(train_flops_per_sequence_by_type(config, seq_len).values())
