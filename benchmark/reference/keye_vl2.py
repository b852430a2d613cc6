"""Plain float32 reference of Keye-VL-2.0's language model (``model_type:
KeyeVL2``): forward pass, next-token loss and the indexer's alignment loss
in straightforward ``jax.numpy`` (gradients by ``jax.grad``), written from
the keys of the published ``config.json``, the Qwen3-MoE family's layer
equations and DeepSeek-V3.2-Exp's public sparse attention, which the model's
``sa_config`` names.  It shares no code with ``sparknet_tpu/``: the index
scores are a full matrix over all keys, a block of queries at a time; the
selection is ``lax.top_k`` of a row; attention is a masked full softmax; the
experts are a dense loop over the held range; no kernels.  Callers wrap it
in ``jax.default_matmul_precision("highest")``.

The equations (``E`` the hidden size, no bias but the LayerNorm's; ``RMSNorm(x;
w) = w x rsqrt(mean(x^2) + eps)``; all layers alike):

- block: ``u = RMSNorm(x; n1); h = x + Attn(u); y = h + MoE(RMSNorm(h; n2))``;
  after the last layer ``RMSNorm(.; norm_f)`` and logits ``. @ head``;
- main path: ``q = u Wq`` (``Hq`` heads of ``D``), ``k = u Wk``, ``v = u Wv``
  (``Hkv`` heads); RMSNorm over each head of ``q`` and ``k``; rotate-half over
  the whole head, ``rope_theta``; K/V head ``h // (Hq / Hkv)`` serves query
  head ``h``;
- indexer, on ``stop_gradient(u)``: ``qI = u WqI`` (``J`` heads of ``Di``),
  ``kI = LayerNorm(u WkI)`` (ONE head of ``Di``, shared by the index heads),
  ``w = u WwI`` (``J``); rotate-half on ``qI`` and ``kI``; ``I[t, s] = sum_j
  w[t, j] J^-1/2 Di^-1/2 relu(qI[t, j] . kI[s])`` for ``s <= t``;
- selection: ``S_t`` = the ``min(t + 1, topk)`` keys ``s <= t`` of largest
  ``I[t, s]`` (``lax.top_k``: ties to the lower ``s``).  No gradient;
- attention: ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, g(h)]
  / sqrt(D)) v[s, g(h)]``; ``Attn = concat_h(o) Wo``;
- alignment loss (DeepSeek-V3.2-Exp's sparse training stage): ``p[t, s] =
  stop_gradient(mean_h A[t, h, s])`` over ``S_t``; ``L_I = sum_layers mean_t
  sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s])``, a
  term with ``p = 0`` being 0.  The step minimises ``L_LM + L_I``: ``L_LM``
  reaches every parameter but the indexer's, ``L_I`` those alone;
- MoE: ``s = softmax(x @ router)`` over all experts in float32, ``top_k``,
  weights renormalised over the chosen (``norm_topk_prob``), gated-SiLU
  experts of ``moe_intermediate_size``, no shared expert.

Departures from the published model, each shared with the program:
- text only: the vision tower and image positions are left out.  On text the
  three position streams of ``mrope_section`` (16 + 24 + 24 = 64 frequencies,
  the head's 64 pairs) are equal, so M-RoPE IS plain rotary;
- RMSNorm on each head of ``q`` and ``k`` with ``rms_norm_eps`` (the Qwen3-MoE
  family's implementation; ``config.json`` has no key for it);
- the indexer as DeepSeek-V3.2-Exp publishes it: rotary over the index head
  (here its whole 64), LayerNorm (eps 1e-6, weight and bias) on ``kI``, the
  two scale factors, ReLU, one shared key head (``indexer_num_kv_heads``: 1);
  ``q_chunk_size`` / ``kv_chunk_size`` tile its computation and change no
  equation;
- ``L_I`` at weight 1 under the same Adam; no router auxiliary loss (no
  coefficient in ``config.json``);
- ``experts_held = [lo, n]``: the router is over all ``num_experts``, and only
  the terms of experts ``lo .. lo + n - 1`` are added (one chip's share of an
  expert-parallel layer);
- the vocabulary is the slice the configuration states (``vocab_size`` rows);
- a score of ``-0.0`` counts as ``0.0`` (equal scores are ties).

Parameters are read in the program's layout, ``params[group][index]``:
``embed`` [(V, E)]; ``l<i>_n1`` / ``l<i>_n2`` [(E,)]; ``l<i>_mixer`` [q_proj (E,
Hq D), k_proj (E, Hkv D), v_proj (E, Hkv D), q_norm (D,), k_norm (D,), o_proj
(Hq D, E), index_q (E, J Di), index_k (E, Di), index_k_norm weight (Di,) and
bias (Di,), index_w (E, J)]; ``l<i>_router`` [(E, experts)]; ``l<i>_experts``
[gate (n, E, F), up (n, E, F), down (n, F, E)]; ``norm_f`` [(E,)]; ``head``
[(E, V)].

``operand_dtype`` rounds the operands of every matrix product to that dtype
first: PERF.md's reading of what a lower precision than the stated one gives.
``remat`` makes each block of queries and each layer a ``jax.checkpoint``: a
memory policy, not arithmetic.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
LAYER_NORM_EPS = 1e-6
QUERY_BLOCK = 512
# the indexer's blobs follow the attention's six in the mixer's group
INDEXER = slice(6, 11)


def rounded(x, operand_dtype):
    return x if operand_dtype is None else x.astype(operand_dtype).astype(F32)


def mm(x, w, operand_dtype=None):
    return jnp.matmul(rounded(x, operand_dtype), rounded(w, operand_dtype))


def ein(spec, a, b, operand_dtype=None):
    return jnp.einsum(spec, rounded(a, operand_dtype), rounded(b, operand_dtype))


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def layer_norm(x, w, b, eps=LAYER_NORM_EPS):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rotate_half(x, theta):
    """Rotary positions over the whole head; ``x`` is ``(B, T, H, D)``."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    angle = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the indexer ---------------------------------------------------------------
def indexer(u, blobs, config, operand_dtype=None, weighted=True):
    """``qI (B, T, J, Di)``, ``kI (B, T, Di)`` and the head weights ``(B, T,
    J)`` with the two scale factors in them (all ones times the factors
    where not ``weighted``: a planted fault)."""
    index_q, index_k, norm_w, norm_b, index_w = blobs[INDEXER]
    sa = config["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    assert sa["indexer_num_kv_heads"] == 1
    b, t, _ = u.shape
    theta = config["rope_theta"]
    qi = rotate_half(mm(u, index_q, operand_dtype).reshape(b, t, j, di), theta)
    ki = layer_norm(mm(u, index_k, operand_dtype), norm_w, norm_b)
    ki = rotate_half(ki[:, :, None, :], theta)[:, :, 0]
    w = mm(u, index_w, operand_dtype) if weighted else jnp.ones((b, t, j), F32)
    return qi, ki, w * j ** -0.5 * di ** -0.5


def index_scores(qi, w, ki, operand_dtype=None, relu=True):
    """``I`` of the queries ``qi (B, Q, J, Di)`` against every key ``ki (B,
    T, Di)``: ``(B, Q, T)``, no mask applied."""
    s = ein("bqjd,bsd->bqjs", qi, ki, operand_dtype)
    if relu:
        s = jax.nn.relu(s)
    i = jnp.sum(w[..., None] * s, axis=2)
    return jnp.where(i == 0.0, 0.0, i)


def selection(scores, first, topk):
    """The keep-mask ``(B, Q, T)`` of the queries ``first .. first + Q - 1``:
    ``lax.top_k`` of each row's causal scores."""
    b, q, t = scores.shape
    causal = jnp.arange(t)[None, :] <= (first + jnp.arange(q))[:, None]
    values, ids = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    chosen = values > -jnp.inf  # a row with fewer causal keys takes them all
    return jnp.zeros((b, q, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(q)[None, :, None], ids
    ].set(chosen)


# -- attention over the selection -----------------------------------------------
def heads(u, blobs, config, operand_dtype=None):
    """``q (B, T, Hq, D)`` and ``k``, ``v`` repeated to ``Hq`` heads."""
    q_proj, k_proj, v_proj, q_norm, k_norm = blobs[:5]
    b, t, _ = u.shape
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps, theta = config["head_dim"], config["rms_norm_eps"], config["rope_theta"]
    q = mm(u, q_proj, operand_dtype).reshape(b, t, hq, d)
    k = mm(u, k_proj, operand_dtype).reshape(b, t, hkv, d)
    v = mm(u, v_proj, operand_dtype).reshape(b, t, hkv, d)
    q = rotate_half(rms_norm(q, q_norm, eps), theta)
    k = rotate_half(rms_norm(k, k_norm, eps), theta)
    return q, jnp.repeat(k, hq // hkv, axis=2), jnp.repeat(v, hq // hkv, axis=2)


def attend(q, k, v, keep, operand_dtype=None):
    """``q (B, Q, H, D)`` against ``k``, ``v (B, T, H, D)`` over the kept keys
    ``(B, Q, T)``: the output ``(B, Q, H, D)`` and the probabilities ``(B, H,
    Q, T)``."""
    s = ein("bqhd,bkhd->bhqk", q, k, operand_dtype) * q.shape[-1] ** -0.5
    a = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
    return ein("bhqk,bkhd->bqhd", a, v, operand_dtype), a


def alignment_rows(a, scores, keep):
    """``sum_{s in S_t} p (log p - log softmax_{S_t}(I))`` a query, ``p`` the
    head mean of ``a``, no gradient through it: ``(B, Q)``."""
    p = jax.lax.stop_gradient(jnp.mean(a, axis=1))
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    live = keep & (p > 0.0)
    return jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0)) - jnp.where(live, log_q, 0.0)),
        0.0), -1)


def sparse_attention(u, blobs, config, operand_dtype=None, remat=False,
                     query_block=QUERY_BLOCK, given_keep=None):
    """``Attn(u)`` ``(B, T, E)`` and the layer's alignment loss (the mean
    over its queries).  ``given_keep (B, T, T)`` takes the selection's place
    (the checks' use)."""
    b, t, _ = u.shape
    topk = config["sa_config"]["topk"]
    q, k, v = heads(u, blobs, config, operand_dtype)
    qi, ki, w = indexer(jax.lax.stop_gradient(u), blobs, config, operand_dtype)
    block = min(query_block, t)
    pad = (-t) % block
    padded = lambda x: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    cut = lambda x: jnp.moveaxis(  # noqa: E731
        padded(x).reshape(b, -1, block, *x.shape[2:]), 1, 0)
    given = () if given_keep is None else (cut(given_keep),)

    def one(xs):
        first, qb, qib, wb = xs[:4]
        scores = index_scores(qib, wb, ki, operand_dtype)
        keep = xs[4] if given else selection(scores, first, topk)
        keep = keep & (jnp.arange(t)[None, :] <= jnp.minimum(
            first + jnp.arange(block), t - 1)[:, None])
        o, a = attend(qb, k, v, keep, operand_dtype)
        rows = first + jnp.arange(block)
        return o, jnp.where(rows[None, :] < t, alignment_rows(a, scores, keep), 0.0)

    firsts = jnp.arange(0, t + pad, block)
    o, kl = jax.lax.map(jax.checkpoint(one) if remat else one,
                        (firsts, cut(q), cut(qi), cut(w), *given))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, -1)[:, :t]
    return mm(o, blobs[5], operand_dtype), jnp.sum(kl) / (b * t)


# -- the feed-forward -------------------------------------------------------------
def mlp(x, gate, up, down, operand_dtype=None):
    return mm(jax.nn.silu(mm(x, gate, operand_dtype)) * mm(x, up, operand_dtype),
              down, operand_dtype)


def route(x, w_router, config):
    """``w_router``: ``(E, experts)``.  Softmax over all experts in float32,
    top-k, renormalised.  Returns ``(weights, ids)``."""
    s = jax.nn.softmax(mm(x, w_router), -1)
    weights, ids = jax.lax.top_k(s, config["num_experts_per_tok"])
    return weights / jnp.sum(weights, -1, keepdims=True), ids


def routed_experts(x, weights, ids, experts, held, operand_dtype=None):
    """The terms of the experts ``held = [lo, n]``, one expert at a time
    over every token with a dense mask."""
    lo, n = held

    def one(out, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return out + w_e[..., None] * mlp(x, gate, up, down, operand_dtype), None

    # a loop, written as a scan so that the compiler sees one expert's body
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (lo + jnp.arange(n), *experts))
    return out


def moe(x, router, experts, config, held=None, operand_dtype=None):
    held = config["experts_held"] if held is None else held
    weights, ids = route(x, router[0], config)
    return routed_experts(x, weights, ids, experts, held, operand_dtype)


# -- the stack ----------------------------------------------------------------------
def layer(x, blobs, config, operand_dtype=None, remat=False):
    """``blobs``: a layer's ``(n1, mixer, n2, router, experts)``.  Returns
    the layer's output and its alignment loss."""
    n1, mixer, n2, router, experts = blobs
    eps = config["rms_norm_eps"]
    attn, align = sparse_attention(
        rms_norm(x, n1, eps), mixer, config, operand_dtype, remat)
    h = x + attn
    return h + moe(rms_norm(h, n2, eps), router, experts, config,
                   operand_dtype=operand_dtype), align


def hidden(params, tokens, config, operand_dtype=None, remat=False):
    """The normed last output and the sum of the layers' alignment losses."""
    x = params["embed"][0][tokens]
    total = jnp.zeros((), F32)
    for i in range(config["num_hidden_layers"]):
        blobs = (params[f"l{i}_n1"][0], params[f"l{i}_mixer"],
                 params[f"l{i}_n2"][0], params[f"l{i}_router"],
                 params[f"l{i}_experts"])
        one = lambda x, blobs: layer(  # noqa: E731
            x, blobs, config, operand_dtype, remat)
        x, align = (jax.checkpoint(one) if remat else one)(x, blobs)
        total = total + align
    return rms_norm(x, params["norm_f"][0], config["rms_norm_eps"]), total


def logits_and_alignment(params, tokens, config, operand_dtype=None,
                         remat=False):
    x, align = hidden(params, tokens, config, operand_dtype, remat)
    return mm(x, params["head"][0], operand_dtype), align


def logits(params, tokens, config, operand_dtype=None, remat=False):
    """``tokens``: ``(B, T)`` int -> ``(B, T, vocab_size)`` float32."""
    return logits_and_alignment(params, tokens, config, operand_dtype, remat)[0]


def losses(params, tokens, targets, config, operand_dtype=None, remat=False):
    """``(L_LM, L_I)``: the next-token cross-entropy, the mean over all
    tokens (the caller gives the shifted ``targets``), and the alignment
    loss.  The step minimises their sum."""
    out, align = logits_and_alignment(params, tokens, config, operand_dtype, remat)
    logp = jax.nn.log_softmax(out, -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1)), align


def loss(params, tokens, targets, config, operand_dtype=None, remat=False):
    return sum(losses(params, tokens, targets, config, operand_dtype, remat))


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
