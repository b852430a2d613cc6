"""Decoder-only LM whose layer pattern, norms, positions and head counts are
VALUES: linear-attention (Gated DeltaNet) layers with one gated softmax-
attention layer every ``full_attention_interval``, a sparse mixture of
experts with a shared expert in every layer, zero-centred RMSNorm, rotary
positions on part of each head, grouped K/V heads, an untied head.

Built from a plain dict of the keys of a published ``config.json``
(``qwen3_next``'s names) plus two of this system's own:

- ``experts_held: [lo, n]`` — the contiguous range of routed experts this
  chip holds.  The router keeps its published width; only the held experts'
  terms are added (``ops/moe.py``).  Default: all of them.
- ``compute_dtype`` — matrix products take their operands in it and
  accumulate in float32 over float32 master weights; norms, softmaxes, the
  router, the decay ``g``, the delta-rule state and the loss stay float32.
  ``Solver(net=..., compute_dtype=...)`` sets it (``set_compute_dtype``).

The block is written once (``_layer``), for training.  The generation seams
of ``TransformerLM`` (``prefill_with_kv`` / ``decode_step_with_kv``) are not
supported: a linear-attention layer decodes from a recurrent state beside
the paged K/V, which ``serve/`` does not have (ROADMAP R5).

Solver protocol as ``TransformerLM``: ``init`` / ``loss_fn`` /
``param_multipliers`` / ``feed_blobs`` and the checkpoint interface
(``layers`` + ``_blob_refs``).  Every sublayer runs under one
``jax.named_scope("<Type>:<name>")`` (ARCHITECTURE.md "Telemetry
reference") with a ``jax.checkpoint`` INSIDE the scope: between sublayers
only the residual stream and the normed input are kept (and, of an
attention layer, what the flash kernels name: ``MIXER_KEEPS``), each
sublayer's forward is recomputed in its backward, and autodiff names both
``transpose(jvp(<Type>:<name>))``, i.e. backward.

Layout of ``in_proj_qkvz`` / ``in_proj_ba`` columns: ``[q | k | v | z]`` and
``[b | a]``, heads contiguous inside each part; ``q_proj`` is head-major,
each head ``[q | gate]``.
"""

from __future__ import annotations

import json
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.models.transformer_lm import _Group, _Ref
from sparknet_tpu.ops import moe
from sparknet_tpu.ops.attention import causal_gqa_attention
from sparknet_tpu.ops.delta_rule import gated_delta_rule
from sparknet_tpu.ops.pallas_attention import SAVED as FLASH_SAVED

F32 = jnp.float32
# a mixer's recomputation keeps what the flash kernels name, their output and
# its row log-sum-exp (269 MB an attention layer at 2 x 8,192 tokens), and so
# does not run the forward kernel a second time; nothing else is kept
MIXER_KEEPS = jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED)

# the keys of config.json that decide a shape or an equation
CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "full_attention_interval",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "rms_norm_eps",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size",
)


def load_config(path: str) -> Dict:
    """A configuration file as ``benchmark/configs/`` keeps them: the
    published keys at the top level beside this system's own."""
    with open(path) as f:
        return json.load(f)


def rms_norm0(x, w, eps):
    """``x * rsqrt(mean(x^2) + eps) * (1 + w)`` in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, theta: float, rotary_dim: int):
    """Rotate-half on the first ``rotary_dim`` of each head of ``(B, T, H,
    D)``; the rest passes through."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary_dim)
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1)


class HybridMoELM:
    """See the module docstring.  The forward takes any ``T``: there are no
    learned positions."""

    def __init__(self, config: Dict, name: str = "HybridMoELM"):
        missing = [k for k in CONFIG_KEYS if k not in config]
        if missing:
            raise ValueError(f"{name}: configuration lacks {missing}")
        c = self.config = {k: config[k] for k in CONFIG_KEYS}
        if not config.get("norm_topk_prob", True):
            raise ValueError(f"{name}: norm_topk_prob=false is not supported")
        self.experts_held = tuple(
            config.get("experts_held") or (0, c["num_experts"]))
        lo, n = self.experts_held
        if not (0 <= lo and 0 < n and lo + n <= c["num_experts"]):
            raise ValueError(
                f"experts_held={list(self.experts_held)} is not a range of "
                f"the {c['num_experts']} experts")
        if c["num_attention_heads"] % c["num_key_value_heads"] or (
                c["linear_num_value_heads"] % c["linear_num_key_heads"]):
            raise ValueError("query / value heads must divide by K/V / key heads")
        self.set_compute_dtype(config.get("compute_dtype"))
        self.name = name
        self.depth = c["num_hidden_layers"]
        self.feed_blobs = ("tokens", "targets")
        self._group_blobs = self._blob_plan()
        self.layers = [_Group(k) for k, _ in self._group_blobs]
        self._blob_refs = {
            k: [_Ref(k, i) for i in range(len(shapes))]
            for k, shapes in self._group_blobs
        }

    def set_compute_dtype(self, dtype) -> None:
        """``None`` is float32 throughout."""
        self.compute_dtype = None if dtype is None else jnp.dtype(dtype)

    def is_attention_layer(self, i: int) -> bool:
        return (i + 1) % self.config["full_attention_interval"] == 0

    # ------------------------------------------------------------------
    def _blob_plan(self) -> List[Tuple[str, List[Tuple[int, ...]]]]:
        c = self.config
        e, v = c["hidden_size"], c["vocab_size"]
        hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
        hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
        dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
        f, fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
        n = self.experts_held[1]
        conv_channels = 2 * hk * dk + hv * dv
        plan = [("embed", [(v, e)])]
        for i in range(c["num_hidden_layers"]):
            plan.append((f"l{i}_n1", [(e,)]))
            if self.is_attention_layer(i):
                plan.append((f"l{i}_mixer", [
                    (e, 2 * hq * d), (e, hkv * d), (e, hkv * d), (d,), (d,),
                    (hq * d, e)]))
            else:
                plan.append((f"l{i}_mixer", [
                    (e, conv_channels + hv * dv), (e, 2 * hv),
                    (conv_channels, c["linear_conv_kernel_dim"]),
                    (hv,), (hv,), (dv,), (hv * dv, e)]))
            plan.append((f"l{i}_n2", [(e,)]))
            plan.append((f"l{i}_router", [(e, c["num_experts"])]))
            plan.append((f"l{i}_experts", [(n, e, f), (n, e, f), (n, f, e)]))
            plan.append((f"l{i}_shared", [(e, fs), (e, fs), (fs, e), (e, 1)]))
        plan.append(("norm_f", [(e,)]))
        plan.append(("head", [(e, v)]))
        return plan

    def init(self, seed: int = 0):
        """Matrices normal(0, 0.02); zero-centred norm weights 0; the
        DeltaNet output norm 1; ``A_log = log U(0, 16)``, ``dt_bias = 1``.
        No running statistics.  One jitted program with the key as its
        argument: blob by blob, eagerly, the 35 generators take a minute to
        compile on the chip."""

        def make(key):
            params: Dict[str, List[jnp.ndarray]] = {}
            for gi, (group, shapes) in enumerate(self._group_blobs):
                gkey = jax.random.fold_in(key, gi)
                delta = group.endswith("_mixer") and len(shapes) == 7
                blobs = []
                for bi, shape in enumerate(shapes):
                    bkey = jax.random.fold_in(gkey, bi)
                    if len(shape) > 1:
                        blobs.append(0.02 * jax.random.normal(bkey, shape, F32))
                    elif delta and bi == 3:  # A_log
                        blobs.append(jnp.log(jax.random.uniform(
                            bkey, shape, F32, minval=2.0 ** -20, maxval=16.0)))
                    elif delta and bi in (4, 5):  # dt_bias, the output norm
                        blobs.append(jnp.ones(shape, F32))
                    else:
                        blobs.append(jnp.zeros(shape, F32))
                params[group] = blobs
            return params

        return jax.jit(make)(jax.random.PRNGKey(seed)), {}

    def param_multipliers(self):
        """lr_mult 1 everywhere; weight decay on the matrices only."""
        lr = {g: [1.0] * len(s) for g, s in self._group_blobs}
        decay = {g: [1.0 if len(x) > 1 else 0.0 for x in s]
                 for g, s in self._group_blobs}
        return lr, decay

    def num_params(self) -> int:
        return int(sum(int(np.prod(s)) for _, shapes in self._group_blobs
                       for s in shapes))

    # ------------------------------------------------------------------
    def _dot(self, x, w, out_dtype=None):
        cd = self.compute_dtype or F32
        y = jnp.dot(x.astype(cd), w.astype(cd), preferred_element_type=F32)
        return y.astype(out_dtype or cd)

    def _gated_attention(self, x, blobs):
        q_proj, k_proj, v_proj, q_norm, k_norm, o_proj = blobs
        c = self.config
        b, t, _ = x.shape
        hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
        eps, theta = c["rms_norm_eps"], c["rope_theta"]
        rotary_dim = int(d * c["partial_rotary_factor"])
        qg = self._dot(x, q_proj).reshape(b, t, hq, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = self._dot(x, k_proj).reshape(b, t, hkv, d)
        v = self._dot(x, v_proj).reshape(b, t, hkv, d)
        q = rotary(rms_norm0(q, q_norm, eps), theta, rotary_dim)
        k = rotary(rms_norm0(k, k_norm, eps), theta, rotary_dim)
        attn = causal_gqa_attention(q, k, v, compute_dtype=self.compute_dtype)
        # gated with heads side by side, (B, T, Hq D), as the kernels write
        # the output and o_proj reads it: heads apart, (.., Hq, D) tiles
        # otherwise, and the float32 output and its cotangent are each
        # copied from one tiling to the other (0.8 ms each on the v5e)
        attn = attn.reshape(b, t, hq * d) * jax.nn.sigmoid(
            gate.reshape(b, t, hq * d).astype(F32))
        return self._dot(attn, o_proj, F32)

    def _gated_delta_net(self, x, blobs):
        in_qkvz, in_ba, conv, a_log, dt_bias, norm, out_proj = blobs
        c = self.config
        b, t, _ = x.shape
        hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
        dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
        width = c["linear_conv_kernel_dim"]
        channels = 2 * hk * dk + hv * dv
        qkvz = self._dot(x, in_qkvz)
        ba = self._dot(x, in_ba, F32)
        mixed, z = qkvz[..., :channels], qkvz[..., channels:]
        padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(
            padded[:, j:j + t].astype(F32) * conv[:, j] for j in range(width)))
        q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
        k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
        v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        l2 = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        # key head j // (hv // hk) serves value head j
        q = jnp.repeat(l2(q) * dk ** -0.5, hv // hk, axis=2)
        k = jnp.repeat(l2(k), hv // hk, axis=2)
        o = gated_delta_rule(q, k, v, g, beta,
                             compute_dtype=self.compute_dtype)
        o = norm * o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + c["rms_norm_eps"])
        o = o * jax.nn.silu(z.reshape(b, t, hv, dv).astype(F32))
        return self._dot(o.reshape(b, t, hv * dv), out_proj, F32)

    def _route(self, h2d, norm_w, w_router):
        # the router reads the float32 normed input, recomputed here so that
        # only its compute-dtype copy is kept for the experts
        x2d = rms_norm0(h2d, norm_w, self.config["rms_norm_eps"])
        weights, ids = moe.route(x2d, w_router, self.config["num_experts_per_tok"])
        order, counts = moe.plan(ids, *self.experts_held)
        return weights, ids, order, counts

    def _held_experts(self, x2d, weights, ids, order, counts, blobs):
        c = self.config
        lo, n = self.experts_held
        rows = moe.fast_rows_for(
            x2d.shape[0], c["num_experts_per_tok"], c["num_experts"], n)
        return moe.held_experts(
            x2d, weights, ids, order, counts, *blobs, lo=lo, fast_rows=rows,
            compute_dtype=self.compute_dtype)

    def _shared_expert(self, x2d, blobs):
        gate, up, down, w_s = blobs
        y = moe.gated_mlp(x2d, gate, up, down, self.compute_dtype)
        return y * jax.nn.sigmoid(
            jnp.dot(x2d.astype(F32), w_s, precision=jax.lax.Precision.HIGHEST))

    def _layer(self, params, i: int, x):
        """``h = x + mixer(norm(x)); y = h + moe(norm(h))``; also the held
        experts' assignment counts ``(n,)``."""
        eps = self.config["rms_norm_eps"]
        cd = self.compute_dtype or F32
        attention = self.is_attention_layer(i)
        with jax.named_scope(f"RMSNorm:l{i}_n1"):
            normed = rms_norm0(x, params[f"l{i}_n1"][0], eps).astype(cd)
        kind = "GatedAttention" if attention else "GatedDeltaNet"
        mixer = self._gated_attention if attention else self._gated_delta_net
        with jax.named_scope(f"{kind}:l{i}_mixer"):
            h = x + jax.checkpoint(mixer, policy=MIXER_KEEPS)(
                normed, params[f"l{i}_mixer"])
        b, t, e = h.shape
        with jax.named_scope(f"RMSNorm:l{i}_n2"):
            normed = rms_norm0(h, params[f"l{i}_n2"][0], eps).astype(cd)
            normed = normed.reshape(b * t, e)
        with jax.named_scope(f"MoERouter:l{i}_router"):
            weights, ids, order, counts = jax.checkpoint(self._route)(
                h.reshape(b * t, e), params[f"l{i}_n2"][0],
                params[f"l{i}_router"][0])
        with jax.named_scope(f"MoEExperts:l{i}_experts"):
            routed = jax.checkpoint(self._held_experts)(
                normed, weights, ids, order, counts, params[f"l{i}_experts"])
        with jax.named_scope(f"MoEShared:l{i}_shared"):
            shared = jax.checkpoint(self._shared_expert)(
                normed, params[f"l{i}_shared"])
        return h + (routed + shared).reshape(b, t, e), counts

    def _hidden(self, params, tokens):
        tokens = tokens.astype(jnp.int32)
        with jax.named_scope("Embedding:embed"):
            x = jnp.take(params["embed"][0], tokens, axis=0)
        counts = []
        for i in range(self.depth):
            x, c = self._layer(params, i, x)
            counts.append(c)
        return x, jnp.stack(counts)

    def _head(self, x, norm_f, head):
        return self._dot(
            rms_norm0(x, norm_f, self.config["rms_norm_eps"]), head, F32)

    def forward_logits(self, params, tokens):
        """``(B, T)`` int tokens -> ``(B, T, vocab)`` float32 logits."""
        x, _ = self._hidden(params, tokens)
        with jax.named_scope("LMHead:head"):
            return self._head(x, params["norm_f"][0], params["head"][0])

    def loss_fn(self, params, stats, batch, rng=None, train=True):
        """Next-token cross-entropy over the global token count.  Returns
        ``(loss, (aux, stats))``; ``aux`` is empty: logits of ``(B, T,
        vocab)`` are not kept beside a training step
        (``forward_logits`` gives them)."""
        x, _ = self._hidden(params, batch["tokens"])
        targets = batch["targets"].astype(jnp.int32)

        def nll_sum(x, norm_f, head):
            logp = jax.nn.log_softmax(self._head(x, norm_f, head), axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))

        with jax.named_scope("LMHead:head"):
            total = jax.checkpoint(nll_sum)(
                x, params["norm_f"][0], params["head"][0])
        return total / jnp.asarray(targets.size, F32), ({}, stats)

    def forward(self, params, stats, batch, rng=None):
        return {"logits": self.forward_logits(params, batch["tokens"])}

    def routing_counts(self, params, tokens):
        """Assignments each held expert receives, per layer: ``(layers, n)``
        int32 for the ``(B, T)`` tokens given."""
        return self._hidden(params, tokens)[1]

    # ------------------------------------------------------------------
    def prefill_with_kv(self, *args, **kwargs):
        raise NotImplementedError(
            f"{self.name}: generation is not supported — a linear-attention "
            "layer decodes from a recurrent state beside the paged K/V, which "
            "serve/ does not have (ROADMAP R5)")

    decode_step_with_kv = prefill_with_kv

    def ring_hop_bytes_per_iter(self, batch: int) -> int:
        return 0  # no sequence-parallel ring in this model


def routing_gauges(counts, tokens: int) -> Dict[str, List[float]]:
    """From ``routing_counts`` (``(layers, n)``) and the number of tokens
    routed: per layer, assignments to held experts a token, and the largest
    held expert's load over the mean."""
    counts = np.asarray(counts, np.float64)
    mean = np.maximum(counts.mean(axis=1), 1e-30)
    return {
        "held_assignments_per_token": list(counts.sum(axis=1) / tokens),
        "held_load_skew": list(counts.max(axis=1) / mean),
    }
