"""GenerationEngine: autoregressive LM serving with prefill/decode split.

The serving analog of ``serve/engine.py`` for ``TransformerLM``
checkpoints, built on the two designs that became standard for LM
inference — iteration-level continuous batching (Orca) and block-table
paged KV caching (vLLM) — scaled to this framework's single-chip
replicas:

- **Prefill/decode disaggregation.**  Prefill is jitted per prompt
  LENGTH bucket (one sequence at a time, padded to the bucket; causal
  masking keeps the valid prefix exact) and writes the prompt's K/V
  straight into the paged arena.  Decode is ONE fixed-shape jitted step
  over all ``max_streams`` slots — active or not — so after
  ``warmup()`` nothing ever recompiles: ``jit_cache_size()`` ==
  ``len(prefill_buckets) + 2`` (decode + canary scorer), and the bench
  pins the delta at 0.
- **Paged KV cache.**  ``serve/kv_cache.py`` owns the arena; the engine
  keeps per-slot block tables as a host index map (slot, position) ->
  arena row, gathers each step's context from it, and scatters the new
  position back.  Inactive slots point at the trash block.
- **Greedy decode, logprob out.**  Each admitted stream returns its
  first generated token from the prefill itself (the TTFT token — and
  the property that makes mid-stream resume-by-re-prefill exact: greedy
  decode is deterministic, so re-prefilling prompt + tokens-so-far on a
  sibling replica continues the identical sequence).  ``score_tokens``
  is the canary surface: teacher-forced per-token logprobs of an
  incumbent's output under THIS engine's weights, one fixed shape.

The engine is deliberately batcher-agnostic: ``serve/batcher.py``'s
``StreamBatcher`` drives admit/step/finish from its worker thread, and
the fleet/delivery planes treat it exactly like ``InferenceEngine``
(``warmup()``, ``jit_cache_size()``, hot-swappable by attribute store).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from sparknet_tpu.obs.metrics import MetricsRegistry
from sparknet_tpu.obs.trace import span
from sparknet_tpu.serve.kv_cache import KVBlockPool

DEFAULT_PREFILL_BUCKETS = (16, 32, 64, 128)


class GenerationEngine:
    """Serves greedy autoregressive decode for one ``TransformerLM``.

    Parameters
    ----------
    lm:
        A ``models.transformer_lm.TransformerLM`` (sp=1 — the dense
        single-shard view; serving a ring-sharded model is a training
        construct this engine refuses).
    weights:
        Optional ``.caffemodel`` / snapshot path (io/checkpoint.py
        format — what ``publish_snapshot`` writes); None serves the
        seeded init (boot weights).
    prefill_buckets:
        Ascending prompt-length buckets to pre-compile; prompts longer
        than the top bucket are refused (400 upstream).
    max_streams:
        Decode slots — the fixed decode batch width.
    kv_blocks / kv_block_size:
        Paged-arena geometry (see ``serve/kv_cache.py``).
    """

    def __init__(
        self,
        lm,
        weights: Optional[str] = None,
        prefill_buckets: Sequence[int] = DEFAULT_PREFILL_BUCKETS,
        max_streams: int = 8,
        kv_blocks: int = 64,
        kv_block_size: int = 16,
        registry: Optional[MetricsRegistry] = None,
        seed: int = 0,
    ):
        import jax
        import jax.numpy as jnp

        if lm.sp_size > 1:
            raise ValueError("GenerationEngine serves the sp=1 model only")
        self.lm = lm
        self.max_streams = int(max_streams)
        if self.max_streams < 1:
            raise ValueError(f"need >= 1 decode slot, got {max_streams}")
        self.buckets: List[int] = sorted(
            {min(int(b), lm.seq_len) for b in prefill_buckets if int(b) >= 1}
        )
        if not self.buckets:
            raise ValueError(f"no usable prefill buckets in {prefill_buckets}")
        self.max_prompt = self.buckets[-1]
        self.item_shape = None  # not an image engine; /predict never routes here

        params, stats = lm.init(seed)
        if weights:
            from sparknet_tpu.io import caffemodel, checkpoint

            loaded = checkpoint._load_model_blobs(weights)
            params, stats = caffemodel.apply_blobs(lm, params, stats, loaded)
        self.params = jax.device_put(params)

        self.pool = KVBlockPool(
            lm.depth,
            lm.heads,
            lm.head_dim,
            num_blocks=kv_blocks,
            block_size=kv_block_size,
            registry=registry,
        )

        # host-side slot state (the decode step's fixed-shape inputs)
        S = lm.seq_len
        self._index_map = np.zeros((self.max_streams, S), np.int32)
        self._positions = np.zeros((self.max_streams,), np.int32)
        self._last = np.zeros((self.max_streams,), np.int32)
        self._slot_blocks: List[List[int]] = [
            [] for _ in range(self.max_streams)
        ]
        self._active = [False] * self.max_streams
        # request id occupying each slot (None untraced) — decode_step
        # spans carry the active set's ids for per-request attribution
        self._slot_rids: List[Optional[str]] = [None] * self.max_streams
        self._lock = threading.Lock()

        def _prefill(params, tokens, last, idx, ak, av):
            logits, k, v = lm.prefill_with_kv(params, tokens)
            # pad positions carry an out-of-bounds index -> dropped
            ak = ak.at[:, idx].set(k[:, 0], mode="drop")
            av = av.at[:, idx].set(v[:, 0], mode="drop")
            lp = jax.nn.log_softmax(logits[0, last])
            tok = jnp.argmax(lp)
            return tok, lp[tok], ak, av

        def _decode(params, tokens, positions, index_map, ak, av):
            kc = ak[:, index_map]  # (L, B, S, H, D) gathered context
            vc = av[:, index_map]
            logits, nk, nv = lm.decode_step_with_kv(
                params, tokens, positions, kc, vc
            )
            write = index_map[jnp.arange(tokens.shape[0]), positions]
            ak = ak.at[:, write].set(nk)
            av = av.at[:, write].set(nv)
            lp = jax.nn.log_softmax(logits, axis=-1)
            nxt = jnp.argmax(lp, axis=-1)
            chosen = jnp.take_along_axis(lp, nxt[:, None], axis=-1)[:, 0]
            return nxt, chosen, ak, av

        def _score(params, tokens, targets):
            logits = lm.forward_logits(params, tokens)
            lp = jax.nn.log_softmax(logits, axis=-1)
            return jnp.take_along_axis(
                lp, targets[..., None].astype(jnp.int32), axis=-1
            )[..., 0]

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)
        self._score = jax.jit(_score)

    def to_device(self, device) -> None:
        """Commit the weights and the KV arena to ``device``: prefill
        and decode then run there (a program follows its committed
        operands), whatever the calling thread's default device.  Call
        before ``warmup()``."""
        import jax

        self.params = jax.device_put(self.params, device)
        self.pool.k = jax.device_put(self.pool.k, device)
        self.pool.v = jax.device_put(self.pool.v, device)

    @property
    def device(self):
        """The device the weights live on."""
        import jax

        (dev,) = jax.tree_util.tree_leaves(self.params)[0].devices()
        return dev

    # ------------------------------------------------------------------
    # Compilation control
    # ------------------------------------------------------------------
    def warmup(self) -> int:
        """Trace + compile every program the steady state uses: one
        prefill per length bucket, the one decode step, the canary
        scorer.  Warmup scatters target OOB / trash rows, so the arena
        stays untouched.  Returns the pinned jit cache size."""
        import jax

        oob = np.int32(self.pool.oob_row)
        for b in self.buckets:
            toks = np.zeros((1, b), np.int32)
            idx = np.full((b,), oob, np.int32)
            jax.block_until_ready(
                self._prefill(
                    self.params, toks, np.int32(0), idx, self.pool.k,
                    self.pool.v,
                )
            )
        jax.block_until_ready(
            self._decode(
                self.params,
                np.zeros((self.max_streams,), np.int32),
                np.zeros((self.max_streams,), np.int32),
                np.zeros((self.max_streams, self.lm.seq_len), np.int32),
                self.pool.k,
                self.pool.v,
            )
        )
        S = self.lm.seq_len
        jax.block_until_ready(
            self._score(
                self.params,
                np.zeros((1, S), np.int32),
                np.zeros((1, S), np.int32),
            )
        )
        return self.jit_cache_size()

    def jit_cache_size(self) -> int:
        """Compiled programs across prefill + decode + scorer — stable
        after ``warmup()`` iff no recompiles happened (the pinned
        no-recompile invariant: ``len(buckets) + 2``)."""
        return int(
            self._prefill._cache_size()
            + self._decode._cache_size()
            + self._score._cache_size()
        )

    # ------------------------------------------------------------------
    # Admission geometry
    # ------------------------------------------------------------------
    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds the largest prefill "
            f"bucket ({self.max_prompt})"
        )

    def validate(self, prompt_len: int, max_new: int) -> None:
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if prompt_len + max_new > self.lm.seq_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new}) "
                f"exceeds the model context ({self.lm.seq_len})"
            )
        self.bucket_for(prompt_len)

    def reserve(self, prompt_len: int, max_new: int,
                rid: Optional[str] = None) -> List[int]:
        """Worst-case KV-block reservation at SUBMIT time: raises
        ``KVBudgetExceeded`` (-> 429) when the arena cannot cover
        ``prompt + max_new`` positions — admission control instead of a
        mid-stream OOM.  The returned blocks are handed to ``admit``
        (or ``release``d if the stream dies queued).  With a request id
        the reservation emits a ``kv_reserve`` span tagged with it."""
        self.validate(prompt_len, max_new)
        n = self.pool.blocks_for(prompt_len + max_new)
        if rid is not None:
            with span("kv_reserve", cat="req", req=rid, blocks=n):
                return self.pool.alloc(n)
        return self.pool.alloc(n)

    def release(self, blocks: List[int]) -> None:
        self.pool.free(blocks)

    def free_slots(self) -> int:
        with self._lock:
            return self._active.count(False)

    def active_slots(self) -> int:
        with self._lock:
            return self._active.count(True)

    # ------------------------------------------------------------------
    # Generation
    # ------------------------------------------------------------------
    def admit(
        self,
        prompt: Sequence[int],
        max_new: int,
        blocks: Optional[List[int]] = None,
        rid: Optional[str] = None,
    ) -> Tuple[int, int, float]:
        """Prefill one prompt into a free decode slot; returns ``(slot,
        first_token, first_logprob)`` — the first generated token comes
        straight out of the prefill (TTFT is one forward away from
        admission)."""
        prompt = [int(t) for t in prompt]
        n = len(prompt)
        self.validate(n, int(max_new))
        bucket = self.bucket_for(n)
        with self._lock:
            try:
                slot = self._active.index(False)
            except ValueError:
                # the caller still owns ``blocks`` (if any) — ownership
                # transfers to the engine only on successful admit
                raise RuntimeError("no free decode slot") from None
            allocated_here = blocks is None
            if blocks is None:
                blocks = self.pool.alloc(
                    self.pool.blocks_for(n + int(max_new))
                )
            row = self.pool.index_row(blocks, self.lm.seq_len)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = prompt
            idx = row[:bucket].copy()
            idx[n:] = self.pool.oob_row
            sp_args = {"req": rid} if rid is not None else {}
            try:
                with span("prefill", cat="gen", bucket=bucket, **sp_args):
                    tok, lp, ak, av = self._prefill(
                        self.params, padded, np.int32(n - 1), idx,
                        self.pool.k, self.pool.v,
                    )
                    self.pool.k, self.pool.v = ak, av
                    # sparknet: sync-ok(the first generated token IS the response — TTFT materializes here)
                    tok, lp = int(tok), float(lp)
            except BaseException:
                if allocated_here:
                    self.pool.free(blocks)
                raise
            self._index_map[slot] = row
            self._positions[slot] = n
            self._last[slot] = tok
            self._slot_blocks[slot] = list(blocks)
            self._slot_rids[slot] = rid
            self._active[slot] = True
        return slot, tok, lp

    def step(self) -> Dict[int, Tuple[int, float]]:
        """One decode iteration over EVERY active slot (fixed shape —
        inactive slots compute into the trash block).  Returns
        ``{slot: (token, logprob)}`` for the active ones."""
        with self._lock:
            act = [i for i in range(self.max_streams) if self._active[i]]
            if not act:
                return {}
            # active-set membership: every traced stream sharing this
            # iteration gets the step's duration attributed to it
            rids = [r for r in (self._slot_rids[i] for i in act)
                    if r is not None]
            with span("decode_step", cat="gen", active=len(act),
                      reqs=rids):
                nxt, lps, ak, av = self._decode(
                    self.params,
                    self._last.copy(),
                    self._positions.copy(),
                    self._index_map,
                    self.pool.k,
                    self.pool.v,
                )
                self.pool.k, self.pool.v = ak, av
                # sparknet: sync-ok(streamed tokens ARE the response — one D2H per decode iteration)
                nxt = np.asarray(nxt)
                lps = np.asarray(lps)
            out: Dict[int, Tuple[int, float]] = {}
            for s in act:
                self._positions[s] += 1
                self._last[s] = int(nxt[s])
                out[s] = (int(nxt[s]), float(lps[s]))
            return out

    def finish(self, slot: int) -> None:
        """Release a slot and its blocks (stream completed)."""
        with self._lock:
            if not self._active[slot]:
                return
            blocks, self._slot_blocks[slot] = self._slot_blocks[slot], []
            self._active[slot] = False
            self._slot_rids[slot] = None
            self._positions[slot] = 0
            self._last[slot] = 0
            self._index_map[slot, :] = 0
        self.pool.free(blocks)

    def evict(self, slot: int) -> None:
        """Same release as ``finish``, named for the other reason: the
        stream is NOT done, its blocks are being reclaimed, and the
        caller re-prefills prompt + generated-so-far later (greedy
        decode is deterministic, so the continuation is exact — tested
        in ``tests/test_generate.py``)."""
        self.finish(slot)

    # ------------------------------------------------------------------
    # Canary surface
    # ------------------------------------------------------------------
    def score_tokens(
        self, prompt: Sequence[int], tokens: Sequence[int]
    ) -> np.ndarray:
        """Teacher-forced per-token logprobs of ``tokens`` (an
        incumbent's output for ``prompt``) under THIS engine's weights
        — the generation canary's divergence signal, one fixed-shape
        jitted forward regardless of lengths."""
        prompt = [int(t) for t in prompt]
        tokens = [int(t) for t in tokens]
        if not prompt or not tokens:
            raise ValueError("score_tokens needs a prompt and tokens")
        seq = prompt + tokens
        S = self.lm.seq_len
        if len(seq) > S:
            raise ValueError(
                f"prompt + tokens ({len(seq)}) exceeds context ({S})"
            )
        toks = np.zeros((1, S), np.int32)
        toks[0, : len(seq)] = seq
        tgts = np.zeros((1, S), np.int32)
        tgts[0, : len(seq) - 1] = seq[1:]
        lp = self._score(self.params, toks, tgts)
        # sparknet: sync-ok(canary scoring output is a host-side decision input)
        return np.asarray(lp)[0, len(prompt) - 1 : len(prompt) - 1 + len(tokens)]

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Release every slot (frees all blocks — drain exactness)."""
        for s in range(self.max_streams):
            self.finish(s)
