"""Plain float32 reference of Laguna-XS.2's block stack (``model_type:
laguna``): forward pass and next-token loss in straightforward ``jax.numpy``
(gradients by ``jax.grad``), written from the keys of the published
``config.json`` and the layer equations of the family's public description.
It shares no code with ``sparknet_tpu/``: a head's softmax runs over a full
row of ``T`` keys with the window as an explicit mask, a head and a block of
queries at a time (so that T = 8192 fits); YaRN's frequencies are computed
from the formula here; the experts are a loop over the held range with dense
masks (``reference/kanana2.py``'s, the same mathematics); no kernels.
Callers wrap it in ``jax.default_matmul_precision("highest")``.

The equations (``E`` the hidden size, heads of ``D = head_dim`` over
``num_key_value_heads`` K/V heads, no bias anywhere; ``RMSNorm(x; w) = w x
rsqrt(mean(x^2) + eps)``, eps ``rms_norm_eps``):

- block: ``h = x + mixer(RMSNorm(x; n1)); y = h + ffn(RMSNorm(h; n2))``; after
  the last layer ``RMSNorm(.; norm_f)`` and logits ``. @ head``;
- mixer of layer ``i``, of type ``layer_types[i]``, with ``H =
  num_attention_heads_per_layer[i]`` query heads: ``[q_h | g_h] = (u Wq)_h``
  (head-major, each head ``D + D`` wide), ``k = u Wk``, ``v = u Wv``;
  RMSNorm over each head of ``q`` and ``k``; rotary (``rope_parameters[type]``)
  by rotate-half on the first ``R = D partial_rotary_factor`` of each head,
  the rest passing through: pair ``m`` of token ``t`` turns by ``t f_m``, and
  cos and sin are multiplied by ``attention_factor`` where the type's rope is
  YaRN (``yarn_frequencies``), else ``f_m = theta^(-2m / R)``; query head
  ``h`` reads K/V head ``h // (H / num_key_value_heads)``; scores ``q_h . k /
  sqrt(D)``; a ``full_attention`` query ``t`` sees the keys ``s <= t``, a
  ``sliding_attention`` one ``t - sliding_window < s <= t``; ``o_h =
  softmax(scores) v``; ``out = concat_h(o_h sigmoid(g_h)) Wo``;
- dense feed-forward (``mlp_layer_types[i] == "dense"``): ``down(silu(gate
  x) up x)`` at ``intermediate_size``;
- routed feed-forward (``sparse``): ``reference/kanana2.py``'s ``route`` and
  ``routed_experts`` with ``moe_routed_scaling_factor`` as its scaling:
  sigmoid scores, top-k on ``scores + expert_bias``, the unbiased scores of
  the chosen renormalised (``+ 1e-20``) and scaled; ``sum_k w_k
  expert_{sel_k}(x) + shared(x)``, each expert and the shared expert a gated
  MLP (``moe_intermediate_size``, ``shared_expert_intermediate_size``), the
  shared one's output added as it is;
- after a training step, per routed layer, the load and the bias's
  balancing step of ``reference/kanana2.py``.

Departures from the published model, each shared with the program (the
configuration's ``assumed`` says where each is from):
- ``gating: true`` is the elementwise output gate above (a head's gate as
  wide as the head, head-major ``[q | gate]``), and RMSNorm over the heads
  of q and k; the row has no key for either's form;
- the router's scoring (sigmoid, ``noaux_tc``, the 1e-20) is DeepSeek-V3's;
  ``expert_bias`` is no parameter and moves by its balancing rule at
  ``expert_bias_update_rate``, as in ``reference/kanana2.py``;
- ``experts_held = [lo, n]``: the router is over all ``num_experts``, and
  only the terms of experts ``lo .. lo + n - 1`` are added (one chip's share
  of an expert-parallel layer); the shared expert is added whole;
- the vocabulary is the slice the configuration states (``vocab_size``
  rows); no multi-token-prediction module and no auxiliary loss.

Parameters are read in the program's layout, ``params[group][index]``:
``embed`` [(V, E)]; ``l<i>_n1`` / ``l<i>_n2`` [(E,)]; ``l<i>_mixer`` [Wq (E,
2 H D), Wk (E, Hkv D), Wv (E, Hkv D), q_norm (D,), k_norm (D,), Wo (H D,
E)]; ``l<i>_mlp`` of a dense layer [gate (E, F), up (E, F), down (F, E)]; of
a routed layer ``l<i>_router`` [(E, experts)], ``l<i>_experts`` [gate (n, E,
Fm), up (n, E, Fm), down (n, Fm, E)] and ``l<i>_shared`` [gate (E, Fs), up
(E, Fs), down (Fs, E)]; ``norm_f`` [(E,)]; ``head`` [(E, V)]; the selection
biases beside them as ``stats["l<i>_router"] = [expert_bias, expert_load]``.

``operand_dtype`` rounds the operands of every matrix product to that dtype
first: PERF.md's reading of what a lower precision than the stated one gives.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import kanana2
from benchmark.reference.kanana2 import (  # noqa: F401  (the step's pieces)
    adam_step, balance_step, expert_load, mlp, mm, rms_norm, routed_experts,
    shared_expert)

F32 = jnp.float32


# -- positions ------------------------------------------------------------------
def yarn_frequencies(theta, dim, factor, original, beta_fast, beta_slow):
    """YaRN (arXiv:2309.00071) for a rotary part ``dim`` wide, float64: the
    extrapolated ``f_e = theta^(-2m / dim)`` and interpolated ``f_e /
    factor``, mixed by a ramp over the pair index ``m`` that is 0 up to
    ``low`` and 1 from ``high`` on; ``low`` and ``high`` are the pairs that
    turn ``beta_fast`` and ``beta_slow`` times over ``original`` positions,
    ``dim ln(original / (2 pi r)) / (2 ln theta)``, floored and ceiled."""
    m = np.arange(dim // 2, dtype=np.float64)
    extrapolated = theta ** (-2.0 * m / dim)
    turns = lambda r: dim * math.log(  # noqa: E731
        original / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
    return (extrapolated / factor) * ramp + extrapolated * (1.0 - ramp)


def rope_of(config, kind):
    """``(theta, rotary width, YaRN's (frequencies, attention_factor) or
    None)`` of a layer type."""
    p = config["rope_parameters"][kind]
    dim = int(config["head_dim"] * p.get(
        "partial_rotary_factor", config["partial_rotary_factor"]))
    if p["rope_type"] != "yarn":
        return p["rope_theta"], dim, None
    freq = yarn_frequencies(
        p["rope_theta"], dim, p["factor"], p["original_max_position_embeddings"],
        p["beta_fast"], p["beta_slow"])
    return p["rope_theta"], dim, (freq, p["attention_factor"])


def rotary(x, theta, dim, yarn):
    """Rotate-half on the first ``dim`` of each head of ``(B, T, H, D)``."""
    t, half = x.shape[1], dim // 2
    if yarn is None:
        freq, scale = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / dim), 1.0
    else:
        freq, scale = jnp.asarray(yarn[0], F32), yarn[1]
    angle = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos = scale * jnp.cos(angle)[None, :, None, :]
    sin = scale * jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


# -- attention ------------------------------------------------------------------
def window_of(config, kind):
    """The keys a query of a layer type sees, itself the last; None: all
    before it."""
    return config["sliding_window"] if kind == "sliding_attention" else None


def heads_of(config, i):
    return config["num_attention_heads_per_layer"][i]


def output_gate(attn, gate):
    """``(B, T, H, D)`` each: the elementwise sigmoid gate."""
    return attn * jax.nn.sigmoid(gate)


def attention_core(q, k, v, window, query_block=512):
    """``softmax(q_h . k / sqrt(D), masked) v`` with ``q`` ``(B, T, H, D)``
    and ``k``, ``v`` ``(B, T, Hkv, D)``: a head and a block of queries at a
    time over full rows of ``T`` keys, the mask ``s <= t`` (and ``t - window
    < s`` with a window).  Each block is a ``jax.checkpoint``: a gradient
    keeps the inputs and not the probabilities (a memory policy)."""
    b, t, h, d = q.shape
    group = h // k.shape[2]

    @jax.checkpoint
    def rows(qi, kh, vh, start):
        s = jnp.einsum("bqd,bkd->bqk", qi, kh) * d ** -0.5
        ahead = start + jnp.arange(qi.shape[1])[:, None] - jnp.arange(t)[None, :]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), vh)

    def one_head(xs):
        head, qh = xs
        kh, vh = k[:, :, head // group], v[:, :, head // group]
        return jnp.concatenate([
            rows(qh[:, start:start + query_block], kh, vh, start)
            for start in range(0, t, query_block)], axis=1)

    # a head at a time: a map, so that the compiler sees one head's body
    out = jax.lax.map(one_head, (jnp.arange(h), jnp.moveaxis(q, 2, 0)))
    return jnp.moveaxis(out, 0, 2)


def attention(x, i, blobs, config, operand_dtype=None):
    q_proj, k_proj, v_proj, q_norm, k_norm, o_proj = blobs
    b, t, _ = x.shape
    kind = config["layer_types"][i]
    h, hkv, d = heads_of(config, i), config["num_key_value_heads"], config["head_dim"]
    eps = config["rms_norm_eps"]
    qg = mm(x, q_proj[:, :2 * h * d], operand_dtype).reshape(b, t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = mm(x, k_proj, operand_dtype).reshape(b, t, hkv, d)
    v = mm(x, v_proj, operand_dtype).reshape(b, t, hkv, d)
    theta, dim, yarn = rope_of(config, kind)
    q = rotary(rms_norm(q, q_norm, eps), theta, dim, yarn)
    k = rotary(rms_norm(k, k_norm, eps), theta, dim, yarn)
    if operand_dtype is not None:  # the score and value products' operands
        q, k, v = (a.astype(operand_dtype).astype(F32) for a in (q, k, v))
    attn = attention_core(q, k, v, window_of(config, kind))
    attn = output_gate(attn, gate)
    return mm(attn.reshape(b, t, h * d), o_proj[:h * d], operand_dtype)


# -- feed-forwards ------------------------------------------------------------
def route(x, w_router, config, bias=None):
    """``reference/kanana2.route`` with this family's scaling factor:
    ``(weights, ids, scores)``."""
    return kanana2.route(x, w_router, {
        **config, "routed_scaling_factor": config["moe_routed_scaling_factor"]},
        bias)


def moe(x, router, experts, config, operand_dtype=None, bias=None):
    """The held experts' sum (WITHOUT the shared expert's term) and the ids."""
    weights, ids, _ = route(x, router[0], config, bias)
    if operand_dtype is not None:
        r = lambda a: a.astype(operand_dtype).astype(F32)  # noqa: E731
        x, experts = r(x), tuple(r(a) for a in experts)
    return routed_experts(x, weights, ids, experts, config["experts_held"]), ids


# -- the stack -------------------------------------------------------------------
def layer(x, i, blobs, config, operand_dtype=None):
    """``blobs``: layer ``i``'s ``(n1, mixer, n2, feed-forward)``, the last
    the dense MLP's three matrices or ``(router, experts, shared, expert_bias
    or none)``.  Returns the layer's output and, of a routed layer, every
    expert's load, else none."""
    n1, mixer_blobs, n2, ffn = blobs
    eps = config["rms_norm_eps"]
    h = x + attention(rms_norm(x, n1, eps), i, mixer_blobs, config,
                      operand_dtype)
    normed = rms_norm(h, n2, eps)
    if config["mlp_layer_types"][i] == "dense":
        return h + mlp(normed, *ffn, operand_dtype), None
    router, experts, shared, bias = ffn
    out, ids = moe(normed, router, experts, config, operand_dtype, bias)
    out = out + shared_expert(normed, shared, operand_dtype)
    return h + out, expert_load(ids, config["num_experts"])


def hidden(params, tokens, config, operand_dtype=None, remat=False,
           stats=None):
    """``remat`` makes each layer a ``jax.checkpoint`` (a memory policy).
    Returns the normed last output and the routed layers' loads."""
    x = params["embed"][0][tokens]
    loads = {}
    for i in range(config["num_hidden_layers"]):
        group = f"l{i}_router"
        ffn = (params[f"l{i}_mlp"] if config["mlp_layer_types"][i] == "dense"
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
    """``tokens``: ``(B, T)`` int -> ``(B, T, vocab_size)`` float32."""
    x, _ = hidden(params, tokens, config, operand_dtype, remat, stats)
    return mm(x, params["head"][0], operand_dtype)


def loss(params, tokens, targets, config, operand_dtype=None, stats=None):
    """Next-token cross-entropy, the mean over all tokens."""
    logp = jax.nn.log_softmax(
        logits(params, tokens, config, operand_dtype, stats=stats), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def balanced_stats(params, tokens, config, stats):
    """``stats`` after one training step on ``tokens``."""
    _, loads = hidden(params, tokens, config, stats=stats)
    rate = config.get("expert_bias_update_rate", 0.0)
    return {g: [balance_step(stats[g][0], load, rate), load]
            for g, load in loads.items()}
