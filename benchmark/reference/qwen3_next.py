"""Plain float32 reference of the Qwen3-Next block stack: forward pass and
next-token loss in straightforward ``jax.numpy`` (gradients by ``jax.grad``),
written from the published ``config.json`` keys and the layer equations of
``modeling_qwen3_next.py``'s torch fallback paths.  It shares no code with
``sparknet_tpu/``: no chunking of the delta rule (token by token under
``lax.scan``), a loop over experts with dense masks, full-matrix attention
(in query blocks so that T = 8192 fits), no kernels.  Callers wrap it in
``jax.default_matmul_precision("highest")``.

Departures from the published model, each shared with the program:
- no multi-token-prediction module (it has no key in ``config.json``);
- no auxiliary load-balancing loss (no coefficient in ``config.json``);
- ``experts_held = [lo, n]``: the router is over all ``num_experts``, and
  only the terms of experts ``lo .. lo + n - 1`` are added (one chip's share
  of an expert-parallel layer); the shared expert is added whole;
- the vocabulary is the slice the configuration states (``vocab_size`` rows);
- ``in_proj_qkvz`` / ``in_proj_ba`` columns are ``[q | k | v | z]`` and
  ``[b | a]``, heads contiguous inside each part (a layout, not arithmetic).

Parameters are read in the program's layout, ``params[group][index]``:
``embed`` [(V, E)]; ``l<i>_n1`` / ``l<i>_n2`` [(E,)];
``l<i>_mixer`` of a DeltaNet layer [in_proj_qkvz (E, 2 Hk dk + 2 Hv dv),
in_proj_ba (E, 2 Hv), conv (2 Hk dk + Hv dv, width), A_log (Hv,),
dt_bias (Hv,), norm (dv,), out_proj (Hv dv, E)], of an attention layer
[q_proj (E, 2 Hq D), k_proj (E, Hkv D), v_proj (E, Hkv D), q_norm (D,),
k_norm (D,), o_proj (Hq D, E)]; ``l<i>_router`` [(E, experts)];
``l<i>_experts`` [gate (n, E, F), up (n, E, F), down (n, F, E)];
``l<i>_shared`` [gate (E, Fs), up (E, Fs), down (Fs, E), w_s (E, 1)];
``norm_f`` [(E,)]; ``head`` [(E, V)].

``operand_dtype`` rounds the operands of every matrix product to that dtype
first: PERF.md's reading of what a lower precision than the stated one gives.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def mm(x, w, operand_dtype=None):
    if operand_dtype is not None:
        x, w = x.astype(operand_dtype), w.astype(operand_dtype)
    return jnp.matmul(x.astype(F32), w.astype(F32))


def rms_norm0(x, w, eps):
    """Zero-centred RMSNorm: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def is_attention_layer(i, config):
    return (i + 1) % config["full_attention_interval"] == 0


# -- gated softmax attention ---------------------------------------------
def rotary(x, theta, rotary_dim):
    """Rotate-half on the first ``rotary_dim`` of each head; ``x`` is
    ``(B, T, H, D)``."""
    t = x.shape[1]
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary_dim)
    angle = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def gated_attention(x, blobs, config, operand_dtype=None, query_block=512):
    q_proj, k_proj, v_proj, q_norm, k_norm, o_proj = blobs
    b, t, _ = x.shape
    hq, hkv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                  config["head_dim"])
    eps = config["rms_norm_eps"]
    qg = mm(x, q_proj, operand_dtype).reshape(b, t, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = mm(x, k_proj, operand_dtype).reshape(b, t, hkv, d)
    v = mm(x, v_proj, operand_dtype).reshape(b, t, hkv, d)
    rotary_dim = int(d * config["partial_rotary_factor"])
    q = rotary(rms_norm0(q, q_norm, eps), config["rope_theta"], rotary_dim)
    k = rotary(rms_norm0(k, k_norm, eps), config["rope_theta"], rotary_dim)
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
    attn = jnp.concatenate(outs, axis=1) * jax.nn.sigmoid(gate)
    return mm(attn.reshape(b, t, hq * d), o_proj, operand_dtype)


# -- Gated DeltaNet --------------------------------------------------------
def delta_rule_recurrent(q, k, v, g, beta, segment=64, state_dtype=None):
    """Token by token.  ``q``, ``k``: ``(B, T, H, dk)``; ``v``:
    ``(B, T, H, dv)``; ``g``, ``beta``: ``(B, T, H)``.  ``S`` is
    ``(B, H, dk, dv)`` and starts at zero:
    ``S <- exp(g_t) S; d_t = beta_t (v_t - S^T k_t); S <- S + k_t d_t^T;
    o_t = S^T q_t``.

    Where ``T`` divides by ``segment`` the scan over tokens is cut into
    scans of ``segment`` tokens, each a ``jax.checkpoint``: the same steps in
    the same order, and ``jax.grad`` keeps one state a segment where it kept
    one a token (4 MB a token a layer at the published widths).  A memory
    policy, not arithmetic.  ``state_dtype`` rounds ``S`` to that dtype
    after every token: PERF.md's reading of a state kept in a lower
    precision."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        d = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * d[..., None, :]
        if state_dtype is not None:  # reduce_precision: a pair of casts
            # the compiler may take out (xla_allow_excess_precision)
            info = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    s0 = jnp.zeros((b, h, dk, dv), F32)
    if t % segment or t == segment:
        _, o = jax.lax.scan(step, s0, xs)
    else:
        cut = lambda x: x.reshape(t // segment, segment, *x.shape[1:])  # noqa: E731
        _, o = jax.lax.scan(
            jax.checkpoint(lambda s, seg: jax.lax.scan(step, s, seg)),
            s0, tuple(cut(x) for x in xs))
        o = o.reshape(t, *o.shape[2:])
    return jnp.moveaxis(o, 0, 1)


def l2_normalise(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def gated_delta_net(x, blobs, config, operand_dtype=None):
    in_qkvz, in_ba, conv, a_log, dt_bias, norm, out_proj = blobs
    b, t, _ = x.shape
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    width = config["linear_conv_kernel_dim"]
    qkvz = mm(x, in_qkvz, operand_dtype)
    ba = mm(x, in_ba, operand_dtype)
    mixed, z = qkvz[..., :2 * hk * dk + hv * dv], qkvz[..., 2 * hk * dk + hv * dv:]
    # causal depthwise convolution: y_t = sum_j conv[:, j] x_{t - (width-1) + j}
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    mixed = sum(padded[:, j:j + t] * conv[:, j] for j in range(width))
    mixed = jax.nn.silu(mixed)
    q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
    q = l2_normalise(q) * dk ** -0.5
    k = l2_normalise(k)
    # key head j // (hv // hk) serves value head j
    q = jnp.repeat(q, hv // hk, axis=2)
    k = jnp.repeat(k, hv // hk, axis=2)
    o = delta_rule_recurrent(q, k, v, g, beta)
    o = norm * o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + config["rms_norm_eps"])
    o = o * jax.nn.silu(z.reshape(b, t, hv, dv))
    return mm(o.reshape(b, t, hv * dv), out_proj, operand_dtype)


# -- sparse mixture of experts -------------------------------------------
def route(x, w_router, config):
    """Top-k of the float32 softmax over all experts, renormalised."""
    p = jax.nn.softmax(mm(x, w_router), axis=-1)
    weights, ids = jax.lax.top_k(p, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights, ids


def mlp(x, gate, up, down, operand_dtype=None):
    return mm(jax.nn.silu(mm(x, gate, operand_dtype)) * mm(x, up, operand_dtype),
              down, operand_dtype)


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


def shared_expert(x, blobs, operand_dtype=None):
    gate, up, down, w_s = blobs
    return mlp(x, gate, up, down, operand_dtype) * jax.nn.sigmoid(mm(x, w_s))


def moe(x, router, experts, shared, config, held=None, operand_dtype=None):
    """``x``: ``(..., E)``.  ``held`` defaults to the configuration's."""
    held = config["experts_held"] if held is None else held
    weights, ids = route(x, router, config)
    gate, up, down = experts
    if operand_dtype is not None:
        r = lambda a: a.astype(operand_dtype).astype(F32)  # noqa: E731
        routed = routed_experts(r(x), weights, ids, (r(gate), r(up), r(down)), held)
    else:
        routed = routed_experts(x, weights, ids, experts, held)
    return routed + shared_expert(x, shared, operand_dtype)


# -- the stack -------------------------------------------------------------
def layer(x, i, blobs, config, operand_dtype=None):
    """``blobs``: layer ``i``'s ``(n1, mixer, n2, router, experts, shared)``."""
    n1, mixer_blobs, n2, router, experts, shared = blobs
    eps = config["rms_norm_eps"]
    mixer = gated_attention if is_attention_layer(i, config) else gated_delta_net
    h = x + mixer(rms_norm0(x, n1, eps), mixer_blobs, config, operand_dtype)
    return h + moe(rms_norm0(h, n2, eps), router, experts, shared, config,
                   operand_dtype=operand_dtype)


def hidden(params, tokens, config, operand_dtype=None, remat=False):
    """``remat`` makes each layer a ``jax.checkpoint``: ``jax.grad`` then
    keeps the residual stream between layers and recomputes a layer inside
    its backward pass.  A memory policy, not arithmetic."""
    x = params["embed"][0][tokens]
    for i in range(config["num_hidden_layers"]):
        blobs = (params[f"l{i}_n1"][0], params[f"l{i}_mixer"],
                 params[f"l{i}_n2"][0], params[f"l{i}_router"][0],
                 params[f"l{i}_experts"], params[f"l{i}_shared"])
        one = lambda x, blobs, i=i: layer(  # noqa: E731
            x, i, blobs, config, operand_dtype)
        x = (jax.checkpoint(one) if remat else one)(x, blobs)
    return rms_norm0(x, params["norm_f"][0], config["rms_norm_eps"])


def logits(params, tokens, config, operand_dtype=None, remat=False):
    """``tokens``: ``(B, T)`` int -> ``(B, T, vocab_size)`` float32."""
    return mm(hidden(params, tokens, config, operand_dtype, remat),
              params["head"][0], operand_dtype)


def loss(params, tokens, targets, config, operand_dtype=None):
    """Next-token cross-entropy, the mean over all tokens; the caller gives
    the shifted ``targets``."""
    logp = jax.nn.log_softmax(logits(params, tokens, config, operand_dtype), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


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
