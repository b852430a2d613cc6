"""Generation-serving tests (ISSUE 16): decode attention at q_len=1
pinned against the dense reference (the first in-repo pallas decode
callers), the GenerationEngine's paged greedy decode pinned against a
dense forward loop (block boundaries included), exact KV-block
accounting and 429 admission, the no-recompile invariant, evict ->
re-prefill exact continuation, StreamBatcher continuous batching,
hot-swap stream pinning, and the stream fleet's kill -> resume and
canary promote/rollback contracts."""

import os

import numpy as np
import pytest
import jax

from sparknet_tpu.config import parse_solver_prototxt
from sparknet_tpu.models.transformer_lm import TransformerLM
from sparknet_tpu.ops import pallas_attention
from sparknet_tpu.ops.attention import mha_reference
from sparknet_tpu.serve import (
    GenerationEngine,
    KVBudgetExceeded,
    QueueFull,
    ReplicaPool,
    Router,
    StreamBatcher,
)
from sparknet_tpu.serve.kv_cache import KVBlockPool
from sparknet_tpu.solver import Solver

T = 32  # model context for every engine in this module


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(dim=32, depth=2, heads=2, seq_len=T, vocab=64)


@pytest.fixture(scope="module")
def engine(lm):
    eng = GenerationEngine(
        lm, prefill_buckets=(8, T), max_streams=3, kv_blocks=30,
        kv_block_size=4, seed=0,
    )
    eng.warmup()
    return eng


def _greedy_reference(lm, params, prompt, max_new):
    """Greedy decode through the plain dense forward — no KV cache, no
    paging: the correctness pin for the whole serving path."""
    toks = list(prompt)
    out_toks, out_lps = [], []
    for _ in range(max_new):
        # fixed-shape dense forward (causal: right-padding is inert)
        x = np.zeros((1, lm.seq_len), np.int32)
        x[0, : len(toks)] = toks
        logits = np.asarray(lm.forward_logits(params, x))[0, len(toks) - 1]
        lp = jax.nn.log_softmax(logits)
        t = int(np.argmax(lp))
        out_toks.append(t)
        out_lps.append(float(lp[t]))
        toks.append(t)
    return out_toks, out_lps


def _run_stream(engine, prompt, max_new):
    """Drive one stream synchronously on a bare engine."""
    blocks = engine.reserve(len(prompt), max_new)
    slot, tok, lp = engine.admit(prompt, max_new, blocks=blocks)
    toks, lps = [tok], [lp]
    while len(toks) < max_new:
        out = engine.step()
        toks.append(out[slot][0])
        lps.append(out[slot][1])
    engine.finish(slot)
    return toks, lps


# ---------------------------------------------------------------------------
# decode attention (ops/pallas_attention.py) at q_len=1
# ---------------------------------------------------------------------------
def test_decode_kernel_matches_dense_reference():
    """The pallas decode kernel (interpreter mode on CPU) against the
    dense masked reference over ragged valid lengths."""
    r = np.random.RandomState(0)
    B, S, H, D = 3, 16, 2, 8
    q = r.randn(B, 1, H, D).astype(np.float32)
    k = r.randn(B, S, H, D).astype(np.float32)
    v = r.randn(B, S, H, D).astype(np.float32)
    lengths = np.array([3, 16, 9], np.int32)
    got = np.asarray(
        pallas_attention.decode_attention(q, k, v, lengths, interpret=True)
    )
    want = np.asarray(
        pallas_attention._decode_reference(q, k, v, lengths)
    )
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_decode_matches_causal_mha_last_position():
    """q_len=1 decode over n cached positions == the last row of a
    causal full-sequence mha_reference (the definition of incremental
    decoding being exact)."""
    r = np.random.RandomState(1)
    S, H, D, n = 16, 2, 8, 11
    q_full = r.randn(1, n, H, D).astype(np.float32)
    k = np.zeros((1, S, H, D), np.float32)
    v = np.zeros((1, S, H, D), np.float32)
    k[:, :n] = r.randn(1, n, H, D)
    v[:, :n] = r.randn(1, n, H, D)
    want = np.asarray(
        mha_reference(q_full, k[:, :n], v[:, :n], causal=True)
    )[:, n - 1]
    got = np.asarray(
        pallas_attention.decode_attention(
            q_full[:, n - 1 : n], k, v, lengths=np.array([n], np.int32)
        )
    )[:, 0]
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_decode_lowerability_gate_falls_back_on_cpu():
    """On a non-TPU backend the gate takes the dense reference, NOT
    interpreter mode (which is a test-only tool): outputs are exactly
    the reference's."""
    assert not pallas_attention.lowerable()  # the tier-1 suite is CPU
    r = np.random.RandomState(2)
    q = r.randn(2, 1, 2, 8).astype(np.float32)
    k = r.randn(2, 12, 2, 8).astype(np.float32)
    v = r.randn(2, 12, 2, 8).astype(np.float32)
    lengths = np.array([5, 12], np.int32)
    got = np.asarray(pallas_attention.decode_attention(q, k, v, lengths))
    want = np.asarray(pallas_attention._decode_reference(q, k, v, lengths))
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="q_len=1"):
        pallas_attention.decode_attention(q.repeat(2, axis=1), k, v)


# ---------------------------------------------------------------------------
# GenerationEngine: paged greedy decode pinned against the dense forward
# ---------------------------------------------------------------------------
def test_engine_decode_pinned_to_dense_forward(lm, engine):
    """Greedy tokens IDENTICAL to the no-cache dense loop, logprobs
    within float tolerance — across a generation that crosses several
    KV-block boundaries (block_size 4; positions 3..21)."""
    prompt = [5, 9, 2]
    max_new = 18
    want_toks, want_lps = _greedy_reference(
        lm, engine.params, prompt, max_new
    )
    got_toks, got_lps = _run_stream(engine, prompt, max_new)
    assert got_toks == want_toks
    np.testing.assert_allclose(got_lps, want_lps, atol=1e-5)


def test_engine_concurrent_slots_are_independent(lm, engine):
    """Three interleaved streams (different prompts/lengths) each match
    their solo dense reference — the fixed-shape batched decode step
    never cross-talks slots."""
    specs = [([5, 9, 2, 7], 10), ([1, 2], 6), ([30, 31, 32, 33, 34], 8)]
    refs = [
        _greedy_reference(lm, engine.params, p, n)[0] for p, n in specs
    ]
    slots, got = [], {}
    for p, n in specs:
        slot, tok, _ = engine.admit(p, n)
        slots.append(slot)
        got[slot] = [tok]
    need = {s: n for s, (_, n) in zip(slots, specs)}
    while any(len(got[s]) < need[s] for s in slots):
        out = engine.step()
        for s, (tok, _) in out.items():
            got[s].append(tok)
            if len(got[s]) >= need[s]:
                engine.finish(s)
    for s, ref in zip(slots, refs):
        assert got[s] == ref


def test_engine_no_recompiles_after_warmup(engine):
    before = engine.jit_cache_size()
    assert before == len(engine.buckets) + 2
    _run_stream(engine, [3, 1], 5)  # bucket 8
    _run_stream(engine, list(range(1, 12)), 4)  # bucket 32
    engine.score_tokens([3, 1], [5, 6])
    assert engine.jit_cache_size() == before


def test_evict_then_reprefill_continues_exactly(lm, engine):
    """evict() mid-stream, re-prefill prompt + tokens-so-far, keep
    decoding: the final sequence is identical to the undisturbed run
    (greedy determinism — the router's resume contract)."""
    prompt = [7, 3, 11]
    max_new = 12
    want, _ = _greedy_reference(lm, engine.params, prompt, max_new)
    slot, tok, _ = engine.admit(prompt, max_new)
    toks = [tok]
    for _ in range(4):
        out = engine.step()
        toks.append(out[slot][0])
    engine.evict(slot)
    assert engine.pool.used() == 0
    # resume: the already-generated tokens become prompt suffix; the
    # re-prefill's first output token continues the sequence
    slot2, tok2, _ = engine.admit(prompt + toks, max_new - len(toks))
    toks.append(tok2)
    while len(toks) < max_new:
        out = engine.step()
        toks.append(out[slot2][0])
    engine.finish(slot2)
    assert toks == want


# ---------------------------------------------------------------------------
# KV-block accounting (serve/kv_cache.py)
# ---------------------------------------------------------------------------
def test_kv_pool_exact_accounting_and_double_free():
    pool = KVBlockPool(2, 2, 8, num_blocks=6, block_size=4)
    a = pool.alloc(2)
    b = pool.alloc(3)
    assert pool.used() == 5 and pool.free_blocks() == 1
    with pytest.raises(KVBudgetExceeded):
        pool.alloc(2)  # all-or-nothing: 2 > 1 free
    assert pool.used() == 5  # the failed alloc took nothing
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)  # double free is a bug, loudly
    pool.free(b)
    assert pool.used() == 0
    assert pool.allocated_total == pool.freed_total == 5


def test_engine_admission_sheds_on_kv_budget(lm):
    """Worst-case reservation at reserve() time: when the arena cannot
    cover prompt+max_new the stream sheds (429) BEFORE touching a
    slot — and a no-free-slot admit leaves the caller's blocks alone."""
    eng = GenerationEngine(
        lm, prefill_buckets=(8,), max_streams=1, kv_blocks=4,
        kv_block_size=4, seed=0,
    )
    eng.warmup()
    blocks = eng.reserve(2, 6)  # 8 positions -> 2 blocks
    with pytest.raises(KVBudgetExceeded):
        eng.reserve(4, 12)  # needs 4 blocks, only 2 left
    slot, _, _ = eng.admit([1, 2], 6, blocks=blocks)
    b2 = eng.reserve(2, 6)
    with pytest.raises(RuntimeError, match="no free decode slot"):
        eng.admit([3, 4], 6, blocks=b2)
    # ownership of b2 stayed with the caller — release balances exactly
    eng.release(b2)
    eng.finish(slot)
    assert eng.pool.used() == 0
    assert eng.pool.allocated_total == eng.pool.freed_total > 0


# ---------------------------------------------------------------------------
# StreamBatcher: continuous batching + hot-swap pinning
# ---------------------------------------------------------------------------
def test_stream_batcher_continuous_join_and_exit(lm, engine):
    """More streams than decode slots: short streams exit and the
    queued stream joins mid-flight (no generation barrier), every
    stream's tokens identical to its solo run."""
    specs = [([5, 9, 2], 14), ([1, 2], 4), ([8, 8, 8], 4), ([4, 4], 5)]
    refs = [
        _greedy_reference(lm, engine.params, p, n)[0] for p, n in specs
    ]
    sb = StreamBatcher(engine, max_queue=8)
    try:
        streams = [sb.submit_stream(p, n) for p, n in specs]
        finals = [st.result(timeout=60.0) for st in streams]
        assert all(f["event"] == "done" for f in finals)
        for f, ref in zip(finals, refs):
            assert f["tokens"] == ref
            assert f["finish_reason"] == "length"
    finally:
        sb.stop(drain=True, timeout=30.0)
    assert engine.pool.used() == 0


def test_stream_batcher_sheds_queue_full(lm):
    eng = GenerationEngine(
        lm, prefill_buckets=(8,), max_streams=1, kv_blocks=30,
        kv_block_size=4, seed=0,
    )
    eng.warmup()
    sb = StreamBatcher(eng, max_queue=1)
    try:
        first = sb.submit_stream([1, 2], 16)
        # backlog: one slot busy; the queue takes ONE more, then sheds
        seen_shed = False
        backlog = []
        for _ in range(6):
            try:
                backlog.append(sb.submit_stream([3, 4], 16))
            except QueueFull:
                seen_shed = True
        assert seen_shed
        assert first.result(timeout=60.0)["event"] == "done"
        m = sb.metrics.render()
        assert "sparknet_gen_streams_shed_total" in m
    finally:
        sb.stop(drain=True, timeout=60.0)
    assert eng.pool.used() == 0
    assert eng.pool.allocated_total == eng.pool.freed_total


def test_hot_swap_pins_inflight_streams_to_old_engine(lm):
    """The promote contract's zero-drop half: after the engine
    attribute is swapped, the in-flight stream keeps decoding on the
    engine that admitted it (tokens from the OLD weights), while new
    streams admit to the new engine (tokens from the NEW weights)."""
    eng_a = GenerationEngine(
        lm, prefill_buckets=(8,), max_streams=2, kv_blocks=30,
        kv_block_size=4, seed=0,
    )
    eng_a.warmup()
    eng_b = GenerationEngine(
        lm, prefill_buckets=(8,), max_streams=2, kv_blocks=30,
        kv_block_size=4, seed=123,  # different init -> different tokens
    )
    eng_b.warmup()
    prompt, max_new = [5, 9, 2], 16
    want_a, _ = _greedy_reference(lm, eng_a.params, prompt, max_new)
    want_b, _ = _greedy_reference(lm, eng_b.params, prompt, max_new)
    assert want_a != want_b  # the swap is observable
    sb = StreamBatcher(eng_a, max_queue=8)
    try:
        inflight = sb.submit_stream(prompt, max_new)
        # wait for admission (first token emitted), then hot-swap
        first = next(inflight.iter_events(timeout=60.0))
        assert first["event"] == "token"
        sb.engine = eng_b  # Replica.swap_engine is this attribute store
        after = sb.submit_stream(prompt, max_new)
        got_inflight = inflight.result(timeout=60.0)
        got_after = after.result(timeout=60.0)
        assert got_inflight["tokens"] == want_a  # finished where admitted
        assert got_after["tokens"] == want_b  # admitted to the new engine
    finally:
        sb.stop(drain=True, timeout=60.0)
    assert eng_a.pool.used() == 0 and eng_b.pool.used() == 0


# ---------------------------------------------------------------------------
# Stream fleet: kill -> resume, canary promote/rollback
# ---------------------------------------------------------------------------
def _make_factory(lm, weights_default=None):
    def make_engine(weights=None):
        return GenerationEngine(
            lm,
            weights=weights if weights is not None else weights_default,
            prefill_buckets=(8, T), max_streams=3, kv_blocks=30,
            kv_block_size=4, seed=0,
        )

    return make_engine


def test_router_stream_resume_after_replica_kill(lm):
    """A replica hard-killed mid-stream: the router ejects it and
    resumes the stream on the sibling via re-prefill — the client sees
    one uninterrupted, token-identical stream and never an error."""
    pool = ReplicaPool(
        _make_factory(lm), replicas=2, max_queue=8, stream=True
    )
    router = Router(pool, max_inflight=8)
    try:
        prompt, max_new = [5, 9, 2, 7], 20
        undisturbed = list(router.submit_stream(prompt, max_new))
        assert undisturbed[-1]["event"] == "done"

        gen = router.submit_stream(prompt, max_new)
        first = next(gen)
        assert first["event"] == "token"
        victim = next(
            rep for rep in pool.replicas
            if rep.batcher.active_count() > 0
        )
        victim.kill()
        events = [first] + list(gen)
        assert events[-1]["event"] == "done"
        assert events[-1]["tokens"] == undisturbed[-1]["tokens"]
        assert pool.replicas[victim.index].state == "ejected"
        assert "sparknet_gen_resumes_total 1" in pool.registry.render()
        # the respawned replica serves again (respawn REPLACES the
        # Replica object — read back through the pool)
        pool.respawn(victim.index)
        assert pool.replicas[victim.index].state == "live"
        again = list(router.submit_stream(prompt, max_new))
        assert again[-1]["tokens"] == undisturbed[-1]["tokens"]
    finally:
        router.close()
    for rep in pool.replicas:
        assert rep.engine.pool.used() == 0


def test_stream_delivery_promote_and_rollback(lm, tmp_path):
    """The full gauntlet on streams: a good publish (same weights)
    promotes with a token-identical probe and zero stream errors; a
    noise-poisoned publish under a FORGED passing verdict diverges in
    per-token logprobs, rolls back named + quarantined, incumbent
    held."""
    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.serve import DeliveryController
    from sparknet_tpu.serve import publish as publish_mod

    solver = Solver(
        parse_solver_prototxt(
            'base_lr: 0.1 lr_policy: "fixed" momentum: 0.9 '
            "weight_decay: 0.0 average_loss: 20"
        ),
        net=lm,
    )
    state = solver.init_state(seed=0)
    boot_model, _ = checkpoint.snapshot(
        solver, state, str(tmp_path / "boot")
    )
    pub_dir = str(tmp_path / "publish")
    pool = ReplicaPool(
        _make_factory(lm, weights_default=boot_model),
        replicas=2, max_queue=8, stream=True,
    )
    router = Router(pool, max_inflight=8, canary_frac=1.0)
    ctl = DeliveryController(
        pool, router, pub_dir, cache_dir=str(tmp_path / "cache"),
        decision_requests=3, divergence_max=1e-3,
    )
    try:
        prompt, max_new = [5, 9, 2, 7], 8

        def probe():
            evs = list(router.submit_stream(prompt, max_new))
            assert evs[-1]["event"] == "done", evs[-1]
            return evs[-1]["tokens"]

        expected = probe()

        def drive(pred):
            for _ in range(600):
                if pred():
                    return
                ctl.poll_once()
                # finished streams feed the canary mirror window
                probe()
            raise AssertionError(ctl.status())

        # good publish: the engine-init weights re-published
        verdict = {"passing": True, "reason": "test verdict"}
        good = publish_mod.publish_snapshot(solver, state, pub_dir, verdict)
        good_id = os.path.basename(
            checkpoint.manifest_path_for(good[1])
        )[: -len(".manifest.json")]
        drive(lambda: ctl.promotions == 1)
        assert pool.incumbent_id == good_id
        assert probe() == expected  # token-identical across the swap

        # poisoned publish under a forged verdict: the canary's
        # teacher-forced logprobs diverge -> rollback, incumbent held
        rng = np.random.RandomState(3)
        bad_params = jax.tree_util.tree_map(
            lambda a: np.asarray(a)
            + rng.normal(0.0, 0.5, np.shape(a)).astype(
                np.asarray(a).dtype
            ),
            jax.device_get(state.params),
        )
        bad_state = state._replace(
            params=jax.device_put(bad_params),
            iter=np.asarray(int(state.iter) + 1, np.int32),
        )
        publish_mod.publish_snapshot(
            solver, bad_state, pub_dir,
            {"passing": True, "reason": "FORGED (test)"},
        )
        drive(lambda: ctl.rollbacks == 1)
        decision = ctl.last_decision
        assert decision["action"] == "rolled_back"
        assert decision["quarantined"]
        assert decision["window"]["max_divergence"] > 1e-3
        assert probe() == expected  # incumbent held
    finally:
        router.close()
