"""Plain float32 reference of kanana-2-30b-a3b-instruct-2601's block stack
(``model_type: deepseek_v3``): forward pass and next-token loss in
straightforward ``jax.numpy`` (gradients by ``jax.grad``), written from the
keys of the published ``config.json`` and the layer equations of the family's
public implementation.  It shares no code with ``sparknet_tpu/``: the keys of
a head are materialised ``qk_head_dim`` wide, a head at a time, and its
softmax runs over a full masked row, a block of queries at a time (so that T
= 8192 fits); rotary is written over adjacent pairs; the experts are a loop
over the held range with dense masks; no kernels.  Callers wrap it in
``jax.default_matmul_precision("highest")``.

The equations (``E`` the hidden size, ``H`` heads, no bias anywhere;
``RMSNorm(x; w) = w x rsqrt(mean(x^2) + eps)``, eps ``rms_norm_eps``):

- block: ``h = x + mixer(RMSNorm(x; n1)); y = h + ffn(RMSNorm(h; n2))``; after
  the last layer ``RMSNorm(.; norm_f)`` and logits ``. @ head``;
- mixer, multi-head latent attention without a query rank, of ``u``:
  ``q = u Wq`` as ``H`` heads of ``[q_nope (qk_nope_head_dim) | q_rope
  (qk_rope_head_dim)]``; ``[c | k_rope] = u Wkva`` with ``c`` ``kv_lora_rank``
  wide and ``k_rope`` ONE head for all ``H``; ``RMSNorm(c; kv_norm) Wkvb`` as
  ``H`` heads of ``[k_nope | v (v_head_dim)]``; rotary over ADJACENT pairs
  (``rope_interleave``: the pair ``(x[2i], x[2i + 1])`` turns by ``t
  theta^(-2i / qk_rope_head_dim)``) on ``q_rope`` and ``k_rope``, the nope
  parts carry no position; ``k_h = [k_nope_h | k_rope]``; causal softmax of
  ``q_h . k_h qk_head_dim^(-1/2)``; ``out = concat_h(p v_h) @ Wo``;
- dense feed-forward (layers before ``first_k_dense_replace``): ``down(silu(
  gate x) up x)`` at ``intermediate_size``;
- routed feed-forward (the other layers): ``s = sigmoid(x @ router)`` in
  float32; ``sel = top_k(s + expert_bias)`` (``noaux_tc``; ``n_group =
  topk_group = 1``: the group limit is the identity); ``w = s[sel]``; ``w = w
  / (sum(w) + 1e-20)`` (``norm_topk_prob``); ``w = routed_scaling_factor w``;
  ``sum_k w_k expert_{sel_k}(x) + shared(x)``, each expert a gated MLP of
  ``moe_intermediate_size`` and ``shared`` ONE gated MLP of ``n_shared_experts
  x moe_intermediate_size`` whose output is added as it is (no gate);
- after a training step, per routed layer: ``load_e`` = the assignments
  expert ``e`` received in the step, and ``expert_bias_e += rate
  sign(mean(load) - load_e)`` (``balance_step``).

Departures from the published model, each shared with the program:
- the public implementation moves each rotary pair's halves apart before it
  rotates (``[x_even | x_odd]``, then rotate-half), on queries and keys
  alike; here a pair turns where it lies.  A score, which is all that reads
  the rope parts, is the same;
- ``expert_bias`` (the public ``e_score_correction_bias``) is no parameter:
  no gradient reaches it and Adam never sees it.  It starts at zero and moves
  by the balancing rule it was published with (auxiliary-loss-free balancing,
  arXiv:2408.15664), on the load of this chip's own tokens over all
  ``n_routed_experts``.  The rate, ``expert_bias_update_rate``, is not in
  ``config.json``: the configuration's ``assumed`` says where it is from;
- the ``1e-20`` of the renormalisation is the public implementation's, not
  ``config``'s; no auxiliary loss and no multi-token-prediction module (no
  key for either in ``config.json``);
- ``experts_held = [lo, n]``: the router is over all ``n_routed_experts``,
  and only the terms of experts ``lo .. lo + n - 1`` are added (one chip's
  share of an expert-parallel layer); the shared expert is whole on every
  chip and is added whole;
- the vocabulary is the slice the configuration states (``vocab_size`` rows);
- column layouts (not arithmetic): ``Wq`` head-major, each head ``[nope |
  rope]``; ``Wkva`` ``[c | k_rope]``; ``Wkvb`` head-major, each head
  ``[k_nope | v]``.

Parameters are read in the program's layout, ``params[group][index]``:
``embed`` [(V, E)]; ``l<i>_n1`` / ``l<i>_n2`` [(E,)]; ``l<i>_mixer`` [Wq (E,
H (nope + rope)), Wkva (E, rank + rope), kv_norm (rank,), Wkvb (rank, H (nope
+ v)), Wo (H v, E)]; ``l<i>_mlp`` of a dense layer [gate (E, F), up (E, F),
down (F, E)]; of a routed layer ``l<i>_router`` [(E, experts)],
``l<i>_experts`` [gate (n, E, Fm), up (n, E, Fm), down (n, Fm, E)] and
``l<i>_shared`` [gate (E, Fs), up (E, Fs), down (Fs, E)]; ``norm_f`` [(E,)];
``head`` [(E, V)].  The selection biases come beside them, as the program
carries them: ``stats["l<i>_router"] = [expert_bias (experts,), expert_load
(experts,)]``; without ``stats`` every bias is zero.

``operand_dtype`` rounds the operands of every matrix product to that dtype
first: PERF.md's reading of what a lower precision than the stated one gives.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOPK_EPS = 1e-20


def mm(x, w, operand_dtype=None):
    if operand_dtype is not None:
        x, w = x.astype(operand_dtype), w.astype(operand_dtype)
    return jnp.matmul(x.astype(F32), w.astype(F32))


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


# -- multi-head latent attention ---------------------------------------------
def rotate_pairs(x, theta):
    """Rotary over adjacent pairs of the last axis; ``x`` is ``(B, T, ...,
    D)``: ``(x[2i], x[2i + 1])`` of token ``t`` turns by ``t theta^(-2i /
    D)``."""
    t, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=F32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=F32) / d)[None, :]
    angle = angle.reshape(1, t, *(1,) * (x.ndim - 3), d // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(angle) - odd * jnp.sin(angle))
    return out.at[..., 1::2].set(odd * jnp.cos(angle) + even * jnp.sin(angle))


def score_scale(config):
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5


def latent_norm(c, w, eps):
    return rms_norm(c, w, eps)


def positions(q_nope, q_rope, k_nope, k_rope, theta):
    """Rotary on the rope parts alone; ``k_rope`` is ``(B, T, rope)``."""
    return (q_nope, rotate_pairs(q_rope, theta), k_nope,
            rotate_pairs(k_rope, theta))


def rope_key_of(k_rope, head):
    """The rope key head ``head`` reads: the one there is."""
    return k_rope


def latent_attention(x, blobs, config, operand_dtype=None, query_block=512):
    w_q, w_kva, kv_norm, w_kvb, w_o = blobs
    b, t, _ = x.shape
    h, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, dv = config["qk_nope_head_dim"], config["v_head_dim"]
    rope = config["qk_rope_head_dim"]
    q = mm(x, w_q, operand_dtype).reshape(b, t, h, nope + rope)
    kva = mm(x, w_kva, operand_dtype)
    c, k_rope = kva[..., :rank], kva[..., rank:]
    kv = mm(latent_norm(c, kv_norm, config["rms_norm_eps"]), w_kvb,
            operand_dtype).reshape(b, t, h, nope + dv)
    q_nope, q_rope, k_nope, k_rope = positions(
        q[..., :nope], q[..., nope:], kv[..., :nope], k_rope,
        config["rope_theta"])
    v = kv[..., nope:]
    scale = score_scale(config)

    def one_head(xs):
        head, qn, qr, kn, vh = xs  # (B, T, .) each
        q_h = jnp.concatenate([qn, qr], -1)
        k_h = jnp.concatenate([kn, rope_key_of(k_rope, head)], -1)
        outs = []
        for start in range(0, t, query_block):
            qi = q_h[:, start:start + query_block]
            s = jnp.einsum("bqd,bkd->bqk", qi, k_h) * scale
            rows = start + jnp.arange(qi.shape[1])[:, None]
            s = jnp.where(rows >= jnp.arange(t)[None, :], s, -jnp.inf)
            outs.append(jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), vh))
        return jnp.concatenate(outs, axis=1)

    # a head at a time: a loop, written as a map so that the compiler sees
    # one head's body and not ``H`` copies of it
    heads_first = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    attn = jax.lax.map(one_head, (jnp.arange(h), *(
        heads_first(a) for a in (q_nope, q_rope, k_nope, v))))
    attn = jnp.moveaxis(attn, 0, 2)  # (B, T, H, v)
    return mm(attn.reshape(b, t, h * dv), w_o, operand_dtype)


# -- feed-forwards -----------------------------------------------------------
def mlp(x, gate, up, down, operand_dtype=None):
    return mm(jax.nn.silu(mm(x, gate, operand_dtype)) * mm(x, up, operand_dtype),
              down, operand_dtype)


def shared_expert(x, blobs, operand_dtype=None):
    """The shared experts: one gated MLP, its output added as it is."""
    return mlp(x, *blobs, operand_dtype)


def route(x, w_router, config, bias=None):
    """``w_router``: ``(E, experts)``; ``bias``: ``expert_bias (experts,)``
    or none.  Sigmoid scores in float32; the top-k is chosen on ``scores +
    expert_bias``, the weights are the UNbiased scores of the chosen,
    renormalised and scaled.  Returns ``(weights, ids, scores)``."""
    s = jax.nn.sigmoid(mm(x, w_router))
    chosen_on = s if bias is None else s + bias
    _, ids = jax.lax.top_k(chosen_on, config["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, ids, axis=-1)
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + TOPK_EPS)
    return weights * config["routed_scaling_factor"], ids, s


def routed_experts(x, weights, ids, experts, held):
    """The terms of the experts ``held = [lo, n]``, one expert at a time
    over every token with a dense mask."""
    lo, n = held

    def one(out, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return out + w_e[..., None] * mlp(x, gate, up, down), None

    # a loop, written as a scan so that the compiler sees one expert's body
    # and not ``n`` copies of it
    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (lo + jnp.arange(n), *experts))
    return out


def expert_load(ids, experts):
    """How many assignments each expert received: ``(experts,)`` float32."""
    return jnp.sum(ids[..., None] == jnp.arange(experts), axis=tuple(
        range(ids.ndim))).astype(F32)


def balance_step(bias, load, rate):
    """The bias after one step of its balancing rule."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def moe(x, router, experts, config, held=None, operand_dtype=None,
        bias=None):
    """``x``: ``(..., E)``; ``router``: ``[w_router]``.  ``held`` defaults
    to the configuration's.  Returns the held experts' sum (WITHOUT the
    shared expert's term) and the ids."""
    held = config["experts_held"] if held is None else held
    weights, ids, _ = route(x, router[0], config, bias)
    if operand_dtype is not None:
        r = lambda a: a.astype(operand_dtype).astype(F32)  # noqa: E731
        x, experts = r(x), tuple(r(a) for a in experts)
    return routed_experts(x, weights, ids, experts, held), ids


# -- the stack -----------------------------------------------------------------
def layer(x, i, blobs, config, operand_dtype=None):
    """``blobs``: layer ``i``'s ``(n1, mixer, n2, feed-forward)``, the last
    the dense MLP's three matrices or ``(router, experts, shared, expert_bias
    or none)``.  Returns the layer's output and, of a routed layer, every
    expert's load, else none."""
    n1, mixer_blobs, n2, ffn = blobs
    eps = config["rms_norm_eps"]
    h = x + latent_attention(
        rms_norm(x, n1, eps), mixer_blobs, config, operand_dtype)
    normed = rms_norm(h, n2, eps)
    if i < config["first_k_dense_replace"]:
        return h + mlp(normed, *ffn, operand_dtype), None
    router, experts, shared, bias = ffn
    out, ids = moe(normed, router, experts, config,
                   operand_dtype=operand_dtype, bias=bias)
    out = out + shared_expert(normed, shared, operand_dtype)
    return h + out, expert_load(ids, config["n_routed_experts"])


def hidden(params, tokens, config, operand_dtype=None, remat=False,
           stats=None):
    """``remat`` makes each layer a ``jax.checkpoint``: ``jax.grad`` then
    keeps the residual stream between layers and recomputes a layer inside
    its backward pass.  A memory policy, not arithmetic.  Returns the normed
    last output and the routed layers' loads by router group."""
    x = params["embed"][0][tokens]
    loads = {}
    for i in range(config["num_hidden_layers"]):
        group = f"l{i}_router"
        ffn = (params[f"l{i}_mlp"] if i < config["first_k_dense_replace"]
               else (params[group], params[f"l{i}_experts"],
                     params[f"l{i}_shared"],
                     None if stats is None else stats[group][0]))
        blobs = (params[f"l{i}_n1"][0], params[f"l{i}_mixer"],
                 params[f"l{i}_n2"][0], ffn)
        one = lambda x, blobs, i=i: layer(  # noqa: E731
            x, i, blobs, config, operand_dtype)
        x, load = (jax.checkpoint(one) if remat else one)(x, blobs)
        if load is not None:
            loads[group] = load
    return rms_norm(x, params["norm_f"][0], config["rms_norm_eps"]), loads


def logits(params, tokens, config, operand_dtype=None, remat=False,
           stats=None):
    """``tokens``: ``(B, T)`` int -> ``(B, T, vocab_size)`` float32; the
    head is its own matrix."""
    x, _ = hidden(params, tokens, config, operand_dtype, remat, stats)
    return mm(x, params["head"][0], operand_dtype)


def loss(params, tokens, targets, config, operand_dtype=None, stats=None):
    """Next-token cross-entropy, the mean over all tokens; the caller gives
    the shifted ``targets``."""
    logp = jax.nn.log_softmax(
        logits(params, tokens, config, operand_dtype, stats=stats), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def balanced_stats(params, tokens, config, stats):
    """``stats`` after one training step on ``tokens``: each routed layer's
    load in the step, and its bias one ``balance_step`` on."""
    _, loads = hidden(params, tokens, config, stats=stats)
    rate = config.get("expert_bias_update_rate", 0.0)
    return {g: [balance_step(stats[g][0], load, rate), load]
            for g, load in loads.items()}


# -- one training step -------------------------------------------------------
def adam_step(w, m, v, g, t, lr, beta1, beta2, delta):
    """Adam as the configuration's ``solver`` states it (Caffe's AdamSolver:
    the bias corrections folded into the rate, ``delta`` added to the
    uncorrected ``sqrt(v)``), one leaf, step ``t`` counted from 1.  Returns
    the new ``(w, m, v)``."""
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    rate = lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
    return w - rate * m / (jnp.sqrt(v) + delta), m, v
