"""Decoder-only transformer LM — the framework's first sequence model.

The reference is a 2015 convnet framework (SURVEY §5 names long-context
as "absent entirely"); this module opens the non-CNN workload the
ROADMAP's scenario-diversity item asks for: a byte-level, pre-norm,
decoder-only transformer whose **sequence dimension shards over the
``sp`` mesh axis** while the ``dp`` axis keeps running the same
tau-round parameter averaging every CNN app uses.

Two attention paths, one function (pinned up to float associativity by
``tests/test_lm.py::test_sp_trajectory_matches_dense``):

- ``sp_axis=None`` (sp=1): single-shard causal attention — the Pallas
  flash kernel (``ops.pallas_attention.flash_attention``, fused
  forward AND custom_vjp backward) wherever it lowers natively
  (``pallas_attention.lowerable()``), the dense
  ``ops.attention.mha_reference`` elsewhere and as the correctness
  ground truth;
- ``sp_axis="sp"``: ``parallel.ring_attention`` — the model then MUST
  run inside ``shard_map`` with that axis bound (the
  ``ParameterAveragingTrainer`` does this when given the matching
  ``batch_spec``), each shard holding (B, T/sp) of the sequence, KV
  rotating one ICI hop per ring step.  Positions offset by
  ``axis_index(sp) * T_local`` so the sharded forward computes the
  same function as the dense one; each ring step's local attention
  rides the same flash kernel under the same gate.

Solver protocol: this class is a drop-in "net" for ``Solver(...,
net=lm)`` — it exposes ``init`` / ``loss_fn`` / ``param_multipliers``
/ ``feed_blobs`` plus the checkpoint blob interface (``layers`` +
``_blob_refs``), so snapshots, the health sentry's audit, comm-plane
compression, the hierarchy schedule and journal jobstate all compose
onto the LM unchanged.  The loss is next-token cross-entropy over the
GLOBAL token count (``psum`` over ``sp`` of per-shard sums), so the
loss value is identical on every sp shard.  Parameters are replicated
over ``sp`` and the activations vary over it, so ``shard_map``'s
varying-axes typing sums each parameter's per-shard gradient over the
ring when it transposes that broadcast — no explicit gradient psum.

Naming note: ``data/transformer.py`` is the Caffe **image augmenter**
(DataTransformer — crop/mirror/mean-subtract), not this model; see its
module docstring for the same cross-reference in the other direction.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.ops import pallas_attention
from sparknet_tpu.ops.attention import mha_reference
from sparknet_tpu.parallel.ring_attention import ring_attention

VOCAB = 256  # byte-level: the tokenizer IS the identity over bytes


class _Ref:
    """Checkpoint blob reference (io/caffemodel.py protocol): every
    blob of the LM is a learnable param owned by its own group."""

    __slots__ = ("collection", "owner", "index")

    def __init__(self, owner: str, index: int):
        self.collection = "params"
        self.owner = owner
        self.index = index


class _Group:
    """Minimal layer stand-in for the checkpoint walkers (they read
    ``.name`` only)."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name


def _layer_norm(x, g, b, eps: float = 1e-5):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


class TransformerLM:
    """Small decoder-only LM (embedding + N pre-norm blocks + tied-free
    head) exposing the Solver "net" protocol.

    ``seq_len`` is the GLOBAL sequence length; with ``sp_size > 1``
    each shard sees ``seq_len // sp_size`` positions and ``seq_len``
    must divide evenly (the app/mesh layer enforces it up front, the
    forward re-checks at trace time)."""

    def __init__(
        self,
        vocab: int = VOCAB,
        dim: int = 64,
        depth: int = 2,
        heads: int = 2,
        seq_len: int = 128,
        mlp_ratio: int = 4,
        sp_axis: Optional[str] = None,
        sp_size: int = 1,
        name: str = "TransformerLM",
    ):
        if dim % heads:
            raise ValueError(f"dim={dim} not divisible by heads={heads}")
        if sp_size > 1 and sp_axis is None:
            raise ValueError("sp_size > 1 needs sp_axis (the mesh axis name)")
        if sp_size > 1 and seq_len % sp_size:
            raise ValueError(
                f"seq_len={seq_len} not divisible by sp={sp_size} — the "
                "ring shards the sequence evenly (pad or pick a multiple)"
            )
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.depth = int(depth)
        self.heads = int(heads)
        self.head_dim = self.dim // self.heads
        self.seq_len = int(seq_len)
        self.mlp_ratio = int(mlp_ratio)
        self.sp_axis = sp_axis
        self.sp_size = int(sp_size)
        self.name = name
        self.feed_blobs = ("tokens", "targets")
        # declared feed shapes are per-shard (what one worker's batch
        # entry looks like after sp sharding); batch dim is free
        self.local_seq = self.seq_len // max(1, self.sp_size)
        # checkpoint interface: one group per param-dict key, blobs in
        # init() order (io/caffemodel.net_blobs / apply_blobs walk this)
        self._group_blobs = self._blob_plan()
        self.layers = [_Group(k) for k, _ in self._group_blobs]
        self._blob_refs = {
            k: [_Ref(k, i) for i in range(len(shapes))]
            for k, shapes in self._group_blobs
        }

    # ------------------------------------------------------------------
    def _blob_plan(self) -> List[Tuple[str, List[Tuple[int, ...]]]]:
        """(group_name, [blob shapes]) in init order."""
        V, E, T = self.vocab, self.dim, self.seq_len
        M = E * self.mlp_ratio
        plan: List[Tuple[str, List[Tuple[int, ...]]]] = [
            ("embed", [(V, E), (T, E)]),
        ]
        for i in range(self.depth):
            plan.append((f"block{i}_ln1", [(E,), (E,)]))
            plan.append((f"block{i}_attn", [(E, E), (E, E), (E, E), (E, E)]))
            plan.append((f"block{i}_ln2", [(E,), (E,)]))
            plan.append((f"block{i}_mlp", [(E, M), (M,), (M, E), (E,)]))
        plan.append(("ln_f", [(E,), (E,)]))
        plan.append(("head", [(E, V)]))
        return plan

    def init(self, seed: int = 0) -> Tuple[Dict, Dict]:
        """(params, stats): params follow the solver's dict-of-lists
        convention; the LM carries no running stats (LayerNorm, not
        BatchNorm), so stats is empty — the averaging epilogue's stats
        pass is a no-op."""
        key = jax.random.PRNGKey(seed)
        params: Dict[str, List[jnp.ndarray]] = {}
        std = 0.02
        # residual-branch output projections scale down with depth (the
        # GPT-2 init) so the pre-norm stack starts near-identity
        res_std = std / math.sqrt(max(1, 2 * self.depth))
        for gi, (group, shapes) in enumerate(self._group_blobs):
            gkey = jax.random.fold_in(key, gi)
            blobs = []
            is_ln = group.endswith(("ln1", "ln2")) or group == "ln_f"
            for bi, shape in enumerate(shapes):
                if len(shape) == 1:
                    # ln gains start at 1, every bias (incl. ln's) at 0
                    blobs.append(
                        jnp.ones(shape, jnp.float32)
                        if is_ln and bi == 0
                        else jnp.zeros(shape, jnp.float32)
                    )
                    continue
                s = std
                if group.endswith("_attn") and bi == 3:
                    s = res_std  # w_out
                if group.endswith("_mlp") and bi == 2:
                    s = res_std  # w2
                blobs.append(
                    s
                    * jax.random.normal(
                        jax.random.fold_in(gkey, bi), shape, jnp.float32
                    )
                )
            params[group] = blobs
        return params, {}

    def param_multipliers(self):
        """All groups learn at lr_mult 1; weight decay applies to the
        2-D matrices only (LN gains/biases and biases are decay-free,
        the standard transformer split)."""
        lr: Dict[str, List[float]] = {}
        decay: Dict[str, List[float]] = {}
        for group, shapes in self._group_blobs:
            lr[group] = [1.0] * len(shapes)
            decay[group] = [1.0 if len(s) > 1 else 0.0 for s in shapes]
        return lr, decay

    # ------------------------------------------------------------------
    def _attention(self, x, blobs):
        wq, wk, wv, wo = blobs
        B, T, E = x.shape
        H, D = self.heads, self.head_dim

        def split(w):
            return (x @ w).reshape(B, T, H, D)

        q, k, v = split(wq), split(wk), split(wv)
        # the Pallas flash kernel (fused forward AND backward —
        # custom_vjp) wherever it lowers natively, the XLA reference
        # elsewhere
        if self.sp_axis is not None and self.sp_size > 1:
            # inside shard_map: T here is T_global/sp, KV rotate around
            # the ring (one ICI hop per step), global causality kept by
            # the ring's absolute position bookkeeping
            out = ring_attention(q, k, v, self.sp_axis, causal=True)
        elif pallas_attention.lowerable():
            out = pallas_attention.flash_attention(q, k, v, causal=True)
        else:
            out = mha_reference(q, k, v, causal=True)
        return out.reshape(B, T, E) @ wo

    def forward_logits(self, params, tokens):
        """(B, T_local) int tokens -> (B, T_local, vocab) f32 logits.
        Under sp sharding the caller is inside shard_map and T_local =
        seq_len // sp; positions offset by the shard's ring index."""
        tokens = tokens.astype(jnp.int32)
        B, T = tokens.shape
        if T != self.local_seq:
            raise ValueError(
                f"tokens have T={T}, model expects per-shard "
                f"T={self.local_seq} (seq_len={self.seq_len}, "
                f"sp={self.sp_size})"
            )
        tok_table, pos_table = params["embed"]
        x = jnp.take(tok_table, tokens, axis=0)
        if self.sp_axis is not None and self.sp_size > 1:
            off = jax.lax.axis_index(self.sp_axis) * T
            pos = jax.lax.dynamic_slice_in_dim(pos_table, off, T, axis=0)
        else:
            pos = pos_table[:T]
        x = (x + pos[None]).astype(jnp.float32)
        for i in range(self.depth):
            g1, b1 = params[f"block{i}_ln1"]
            x = x + self._attention(
                _layer_norm(x, g1, b1), params[f"block{i}_attn"]
            )
            g2, b2 = params[f"block{i}_ln2"]
            w1, c1, w2, c2 = params[f"block{i}_mlp"]
            h = _layer_norm(x, g2, b2)
            x = x + (jax.nn.gelu(h @ w1 + c1) @ w2 + c2)
        gf, bf = params["ln_f"]
        (wh,) = params["head"]
        return _layer_norm(x, gf, bf) @ wh

    def loss_fn(self, params, stats, batch, rng=None, train=True):
        """Next-token cross-entropy, averaged over the GLOBAL token
        count.  Returns ``(loss, (aux, stats))`` — the Solver's grad
        contract.  With sp sharding the per-shard sums ``psum`` over
        the ring axis, so the loss value is bit-identical on every sp
        shard (and equals the dense sp=1 loss up to float
        associativity).  The psum is differentiable: its transpose hands
        every shard the same cotangent, each shard back-propagates its
        own tokens, and the gradient of the sp-replicated params comes
        out summed over the ring (the varying-axes type system inserts
        that reduction), i.e. exactly the global gradient."""
        logits = self.forward_logits(params, batch["tokens"])
        tgt = batch["targets"].astype(jnp.int32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        local_sum = jnp.sum(nll)
        count = tgt.shape[0] * tgt.shape[1] * max(1, self.sp_size)
        if self.sp_axis is not None and self.sp_size > 1:
            total = jax.lax.psum(local_sum, self.sp_axis)
        else:
            total = local_sum
        loss = total / jnp.asarray(count, jnp.float32)
        return loss, ({"logits": logits}, stats)

    def forward(self, params, stats, batch, rng=None):
        """Inference logits (the deploy-ish surface; sp=1 path only —
        serving a ring-sharded model would need its own mesh plumbing)."""
        return {"logits": self.forward_logits(params, batch["tokens"])}

    # ------------------------------------------------------------------
    # Generation seams (serve/generate.py) — sp=1 only: serving decodes
    # on one chip; the ring path is a training-time construct.
    # ------------------------------------------------------------------
    def _mlp(self, params, i, x):
        g2, b2 = params[f"block{i}_ln2"]
        w1, c1, w2, c2 = params[f"block{i}_mlp"]
        h = _layer_norm(x, g2, b2)
        return x + (jax.nn.gelu(h @ w1 + c1) @ w2 + c2)

    def prefill_with_kv(self, params, tokens):
        """Causal prefill that ALSO returns every layer's K/V.

        ``tokens`` is (B, T) with T <= seq_len (a prefill length bucket,
        pad rows at the END — causality keeps the valid prefix exact).
        Returns ``(logits (B,T,V), k (depth,B,T,H,D), v (same))``.
        Prefill attention rides the Pallas flash kernel where it lowers
        natively, the dense reference elsewhere."""
        if self.sp_size > 1:
            raise ValueError("generation serves the sp=1 dense model only")
        tokens = tokens.astype(jnp.int32)
        B, T = tokens.shape
        if T > self.seq_len:
            raise ValueError(f"prefill T={T} exceeds seq_len={self.seq_len}")
        H, D = self.heads, self.head_dim
        tok_table, pos_table = params["embed"]
        x = (
            jnp.take(tok_table, tokens, axis=0) + pos_table[:T][None]
        ).astype(jnp.float32)
        ks, vs = [], []
        for i in range(self.depth):
            g1, b1 = params[f"block{i}_ln1"]
            h = _layer_norm(x, g1, b1)
            wq, wk, wv, wo = params[f"block{i}_attn"]
            q = (h @ wq).reshape(B, T, H, D)
            k = (h @ wk).reshape(B, T, H, D)
            v = (h @ wv).reshape(B, T, H, D)
            ks.append(k)
            vs.append(v)
            if pallas_attention.lowerable():
                out = pallas_attention.flash_attention(q, k, v, causal=True)
            else:
                out = mha_reference(q, k, v, causal=True)
            x = x + out.reshape(B, T, self.dim) @ wo
            x = self._mlp(params, i, x)
        gf, bf = params["ln_f"]
        (wh,) = params["head"]
        return _layer_norm(x, gf, bf) @ wh, jnp.stack(ks), jnp.stack(vs)

    def decode_step_with_kv(self, params, tokens, positions, k_ctx, v_ctx):
        """One decode position per sequence against gathered KV context.

        ``tokens`` (B,) — the token to embed at ``positions`` (B,) (=
        the number of already-cached positions per sequence); ``k_ctx``/
        ``v_ctx`` (depth, B, S, H, D) — the paged-cache gather, rows at
        index >= positions[b] are garbage and masked off.  This step's
        own K/V are written into the context copy (so attention sees
        them) AND returned as ``new_k``/``new_v`` (depth, B, H, D) for
        the engine to scatter into the arena.  Returns
        ``(logits (B,V), new_k, new_v)``."""
        if self.sp_size > 1:
            raise ValueError("generation serves the sp=1 dense model only")
        tokens = tokens.astype(jnp.int32)
        positions = positions.astype(jnp.int32)
        B = tokens.shape[0]
        H, D = self.heads, self.head_dim
        tok_table, pos_table = params["embed"]
        x = (
            jnp.take(tok_table, tokens, axis=0)
            + jnp.take(pos_table, positions, axis=0)
        )[:, None, :].astype(jnp.float32)
        new_ks, new_vs = [], []
        rows = jnp.arange(B)
        for i in range(self.depth):
            g1, b1 = params[f"block{i}_ln1"]
            h = _layer_norm(x, g1, b1)
            wq, wk, wv, wo = params[f"block{i}_attn"]
            q = (h @ wq).reshape(B, 1, H, D)
            k1 = (h @ wk).reshape(B, H, D)
            v1 = (h @ wv).reshape(B, H, D)
            new_ks.append(k1)
            new_vs.append(v1)
            kc = k_ctx[i].at[rows, positions].set(k1)
            vc = v_ctx[i].at[rows, positions].set(v1)
            out = pallas_attention.decode_attention(
                q, kc, vc, lengths=positions + 1
            )
            x = x + out.reshape(B, 1, self.dim) @ wo
            x = self._mlp(params, i, x)
        gf, bf = params["ln_f"]
        (wh,) = params["head"]
        logits = (_layer_norm(x, gf, bf) @ wh)[:, 0]
        return logits, jnp.stack(new_ks), jnp.stack(new_vs)

    # ------------------------------------------------------------------
    def with_sp(self, sp_axis: Optional[str], sp_size: int) -> "TransformerLM":
        """The same architecture re-instantiated for a different ring
        width — init from the same seed yields identical params, which
        is how the sp=1 vs sp=2 identity legs share a start point."""
        return TransformerLM(
            vocab=self.vocab,
            dim=self.dim,
            depth=self.depth,
            heads=self.heads,
            seq_len=self.seq_len,
            mlp_ratio=self.mlp_ratio,
            sp_axis=sp_axis,
            sp_size=sp_size,
            name=self.name,
        )

    def num_params(self) -> int:
        return int(
            sum(
                int(np.prod(s))
                for _, shapes in self._group_blobs
                for s in shapes
            )
        )

    def ring_hop_bytes_per_iter(self, batch: int) -> int:
        """Modeled ring-exchange bytes for ONE forward+backward
        iteration: each of sp devices sends its K and V shards
        (B x T_local x E f32, x2 tensors) sp-1 times per attention
        layer, and the backward pass mirrors the forward's exchanges
        (transposed ppermute).  0 when sp=1 — there is no ring."""
        if self.sp_size <= 1:
            return 0
        shard_bytes = batch * self.local_seq * self.dim * 4
        hops = (self.sp_size - 1) * self.sp_size  # per layer, all devices
        return 2 * 2 * shard_bytes * hops * self.depth  # K+V, fwd+bwd
