"""Plain float32 reference of the LFM2 mixture-of-experts block stack
(``model_type: lfm2_moe``): forward pass and next-token loss in
straightforward ``jax.numpy`` (gradients by ``jax.grad``), written from the
keys of the published ``config.json`` and the layer equations of the family's
public implementation.  It shares no code with ``sparknet_tpu/``: the short
convolution is a loop over its taps, one shifted copy of the sequence a tap;
the experts are a loop over the held range with dense masks; attention is
the full score matrix (in query blocks so that T = 8192 fits); no kernels.
Callers wrap it in ``jax.default_matmul_precision("highest")``.

The equations (``E`` the hidden size, no bias anywhere; ``RMSNorm(x; w) = w
x rsqrt(mean(x^2) + eps)``):

- block: ``h = x + mixer(RMSNorm(x; n1)); y = h + ffn(RMSNorm(h; n2))``; after
  the last layer ``RMSNorm(.; norm_f)`` and logits ``. @ embed^T``;
- ``conv`` mixer: ``[B | C | u] = x @ in_proj``; ``z = B u``; ``c[t] = sum_j
  w[:, j] z[t - (L - 1) + j]`` (depthwise, causal, zeros before the sequence,
  ``L = conv_L_cache``); ``out = (C c) @ out_proj``; no activation;
- ``full_attention`` mixer: ``q``, ``k``, ``v`` projections as heads of
  ``head_dim``; RMSNorm over each head of ``q`` and ``k``, then rotate-half
  over the whole head; causal softmax of ``q k^T / sqrt(head_dim)``, K/V head
  ``j`` serving query heads ``j g .. j g + g - 1``; ``out = o @ o_proj``;
- dense feed-forward (layers before ``num_dense_layers``): ``down(silu(gate
  x) up x)`` at ``intermediate_size``;
- routed feed-forward (the other layers): ``s = sigmoid(x @ router)`` in
  float32; ``sel = top_k(s + expert_bias)``; ``w = s[sel]``; ``w = w / (sum(w)
  + 1e-6)`` (``norm_topk_prob``); ``w = routed_scaling_factor w``; ``sum_k w_k
  expert_{sel_k}(x)``, each expert a gated MLP of ``moe_intermediate_size``;
- after a training step, per routed layer: ``load_e`` = the assignments
  expert ``e`` received in the step, and ``expert_bias_e += rate
  sign(mean(load) - load_e)`` (``balance_step``).

Departures from the published model, each shared with the program:
- ``tie_word_embeddings`` is taken as true (the catalog's ``config`` has no
  such key; the LFM2 family ties its head to the embedding);
- the ``1e-6`` of the renormalisation and ``head_dim = hidden_size /
  num_attention_heads`` are the public implementation's, not ``config``'s;
- ``expert_bias`` is no parameter: no gradient reaches it (it only decides
  a selection) and Adam never sees it.  It starts at zero and moves by the
  balancing rule the family's ``use_expert_bias`` goes with
  (auxiliary-loss-free balancing, arXiv:2408.15664: up by a fixed rate where
  an expert received less than the mean load, down where more), on the load
  of this chip's own tokens over all ``num_experts`` experts.  The rate,
  ``expert_bias_update_rate``, is not in ``config.json``: the
  configuration's ``assumed`` says where it is from;
- no auxiliary loss (no coefficient in ``config.json``);
- ``experts_held = [lo, n]``: the router is over all ``num_experts``, and
  only the terms of experts ``lo .. lo + n - 1`` are added (one chip's share
  of an expert-parallel layer); there is no shared expert to add whole;
- the vocabulary is the slice the configuration states (``vocab_size`` rows);
- ``in_proj`` columns are ``[B | C | u]`` (a layout, not arithmetic).

Parameters are read in the program's layout, ``params[group][index]``:
``embed`` [(V, E)]; ``l<i>_n1`` / ``l<i>_n2`` [(E,)]; ``l<i>_mixer`` of a
``conv`` layer [in_proj (E, 3 E), conv (E, L), out_proj (E, E)], of an
attention layer [q_proj (E, Hq D), k_proj (E, Hkv D), v_proj (E, Hkv D),
q_norm (D,), k_norm (D,), o_proj (Hq D, E)]; ``l<i>_mlp`` of a dense layer
[gate (E, F), up (E, F), down (F, E)]; of a routed layer ``l<i>_router``
[(E, experts)] and ``l<i>_experts`` [gate (n, E, Fm), up (n, E, Fm), down (n,
Fm, E)]; ``norm_f`` [(E,)].  The selection biases come beside them, as the
program carries them: ``stats["l<i>_router"] = [expert_bias (experts,),
expert_load (experts,)]``; without ``stats`` every bias is zero.

``operand_dtype`` rounds the operands of every matrix product to that dtype
first: PERF.md's reading of what a lower precision than the stated one gives.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOPK_EPS = 1e-6


def mm(x, w, operand_dtype=None):
    if operand_dtype is not None:
        x, w = x.astype(operand_dtype), w.astype(operand_dtype)
    return jnp.matmul(x.astype(F32), w.astype(F32))


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def head_dim(config):
    return config.get(
        "head_dim", config["hidden_size"] // config["num_attention_heads"])


# -- the gated short convolution -------------------------------------------
def delayed(z, steps):
    """``z`` ``(B, T, C)`` moved ``steps`` tokens later, zeros first."""
    t = z.shape[1]
    return jnp.pad(z, ((0, 0), (steps, 0), (0, 0)))[:, :t]


def causal_conv_taps(z, w):
    """``c[t] = sum_j w[:, j] z[t - (L - 1) + j]``, one tap at a time:
    tap ``j`` reads the token ``L - 1 - j`` back."""
    taps = w.shape[1]
    c = jnp.zeros_like(z)
    for j in range(taps):
        c = c + w[:, j] * delayed(z, taps - 1 - j)
    return c


def gated_conv_core(bcu, w):
    """``C * conv(B * u)`` of ``[B | C | u]``: the mixer between its two
    projections."""
    e = bcu.shape[-1] // 3
    gate_in, gate_out, u = bcu[..., :e], bcu[..., e:2 * e], bcu[..., 2 * e:]
    return gate_out * causal_conv_taps(gate_in * u, w)


def short_conv(x, blobs, config, operand_dtype=None):
    in_proj, conv, out_proj = blobs
    assert conv.shape[1] == config["conv_L_cache"]
    return mm(gated_conv_core(mm(x, in_proj, operand_dtype), conv), out_proj,
              operand_dtype)


# -- grouped softmax attention ---------------------------------------------
def rotate_half(x, theta):
    """Rotary positions over the whole head; ``x`` is ``(B, T, H, D)``."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    angle = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, blobs, config, operand_dtype=None, query_block=512):
    q_proj, k_proj, v_proj, q_norm, k_norm, o_proj = blobs
    b, t, _ = x.shape
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = head_dim(config), config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    q = mm(x, q_proj, operand_dtype).reshape(b, t, hq, d)
    k = mm(x, k_proj, operand_dtype).reshape(b, t, hkv, d)
    v = mm(x, v_proj, operand_dtype).reshape(b, t, hkv, d)
    q = rotate_half(rms_norm(q, q_norm, eps), theta)
    k = rotate_half(rms_norm(k, k_norm, eps), theta)
    # each K/V head serves hq // hkv query heads
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    outs = []
    for start in range(0, t, query_block):
        qi = q[:, start:start + query_block]
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) * d ** -0.5
        rows = start + jnp.arange(qi.shape[1])[:, None]
        s = jnp.where(rows >= jnp.arange(t)[None, :], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v))
    attn = jnp.concatenate(outs, axis=1)
    return mm(attn.reshape(b, t, hq * d), o_proj, operand_dtype)


# -- feed-forwards -----------------------------------------------------------
def mlp(x, gate, up, down, operand_dtype=None):
    return mm(jax.nn.silu(mm(x, gate, operand_dtype)) * mm(x, up, operand_dtype),
              down, operand_dtype)


def route(x, w_router, config, bias=None):
    """``w_router``: ``(E, experts)``; ``bias``: ``expert_bias (experts,)``
    or none.  Sigmoid scores in float32; the top-k is chosen on ``scores +
    expert_bias``, the weights are the UNbiased scores of the chosen,
    renormalised and scaled.  Returns ``(weights, ids, scores)``."""
    s = jax.nn.sigmoid(mm(x, w_router))
    chosen_on = s if bias is None else s + bias
    _, ids = jax.lax.top_k(chosen_on, config["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, ids, axis=-1)
    if config.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + TOPK_EPS)
    return weights * config.get("routed_scaling_factor", 1.0), ids, s


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
    to the configuration's.  Returns the held experts' sum and the ids."""
    held = config["experts_held"] if held is None else held
    weights, ids, _ = route(x, router[0], config, bias)
    if operand_dtype is not None:
        r = lambda a: a.astype(operand_dtype).astype(F32)  # noqa: E731
        x, experts = r(x), tuple(r(a) for a in experts)
    return routed_experts(x, weights, ids, experts, held), ids


# -- the stack -----------------------------------------------------------------
def layer(x, i, blobs, config, operand_dtype=None):
    """``blobs``: layer ``i``'s ``(n1, mixer, n2, feed-forward)``, the last
    the dense MLP's three matrices or ``(router, experts, expert_bias or
    none)``.  Returns the layer's output and, of a routed layer, every
    expert's load, else none."""
    n1, mixer_blobs, n2, ffn = blobs
    eps = config["norm_eps"]
    mixer = {"conv": short_conv, "full_attention": attention}[
        config["layer_types"][i]]
    h = x + mixer(rms_norm(x, n1, eps), mixer_blobs, config, operand_dtype)
    normed = rms_norm(h, n2, eps)
    if i < config["num_dense_layers"]:
        return h + mlp(normed, *ffn, operand_dtype), None
    router, experts, bias = ffn
    out, ids = moe(normed, router, experts, config,
                   operand_dtype=operand_dtype, bias=bias)
    return h + out, expert_load(ids, config["num_experts"])


def hidden(params, tokens, config, operand_dtype=None, remat=False,
           stats=None):
    """``remat`` makes each layer a ``jax.checkpoint``: ``jax.grad`` then
    keeps the residual stream between layers and recomputes a layer inside
    its backward pass.  A memory policy, not arithmetic.  Returns the normed
    last output and the routed layers' loads by router group."""
    x = params["embed"][0][tokens]
    biased = config.get("use_expert_bias", True) and stats is not None
    loads = {}
    for i in range(config["num_hidden_layers"]):
        group = f"l{i}_router"
        ffn = (params[f"l{i}_mlp"] if i < config["num_dense_layers"]
               else (params[group], params[f"l{i}_experts"],
                     stats[group][0] if biased else None))
        blobs = (params[f"l{i}_n1"][0], params[f"l{i}_mixer"],
                 params[f"l{i}_n2"][0], ffn)
        one = lambda x, blobs, i=i: layer(  # noqa: E731
            x, i, blobs, config, operand_dtype)
        x, load = (jax.checkpoint(one) if remat else one)(x, blobs)
        if load is not None:
            loads[group] = load
    return rms_norm(x, params["norm_f"][0], config["norm_eps"]), loads


def logits(params, tokens, config, operand_dtype=None, remat=False,
           stats=None):
    """``tokens``: ``(B, T)`` int -> ``(B, T, vocab_size)`` float32; the
    head is the embedding's transpose."""
    x, _ = hidden(params, tokens, config, operand_dtype, remat, stats)
    return mm(x, params["embed"][0].T, operand_dtype)


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
