"""The sequence models' loss as one operation (``ops/lm_loss.py``): the
Pallas kernels (``ops/pallas_lm_loss.py``, in interpreter mode here) against
the ``log_softmax`` oracle ``lm_loss._xla_nll_sum`` — loss, ``dx`` and
``dhead`` —, through ``HybridMoELM.loss_fn`` with a tied and an untied head,
and what the operation tells of itself: its path (``loss_path``) and its scope
(``LMHead:head``, which ``lfm2_head_device_ms`` / ``head_device_ms`` read).

Float32 comparisons run under ``default_matmul_precision("highest")``: what is
left is the order of the sums over the vocabulary's blocks, a few float32
roundings.  In bfloat16 the kernels round what the oracle rounds (the
product's operands) and one thing more, ``exp(l - lse) - onehot`` as an
operand of the gradient products, which the TPU's default precision makes of
the oracle's float32 cotangent too but the CPU does not: the bound is a few
of the oracle's own bfloat16 errors against float32.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import obs
from sparknet_tpu.models.hybrid_lm import HybridMoELM
from sparknet_tpu.obs.trace import Tracer
from sparknet_tpu.ops import lm_loss, pallas_lm_loss

F32 = jnp.float32
WIDTH, BLOCK_ROWS, BLOCK_VOCAB = 128, 32, 128


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def operands(rows, vocab, vocab_first, seed=0):
    """Hidden rows, a head of std 0.1 and targets that fall on the last
    column of the vocabulary (in the tail block where it is ragged), on a
    block's first and last column and on column 0."""
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(key, 0), (rows, WIDTH))
    head = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 1),
        (vocab, WIDTH) if vocab_first else (WIDTH, vocab))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (rows,), 0, vocab)
    pinned = jnp.asarray(
        [vocab - 1, BLOCK_VOCAB, BLOCK_VOCAB - 1, 0, 2 * BLOCK_VOCAB])
    return x, head, targets.at[:5].set(jnp.minimum(pinned, vocab - 1))


def oracle(x, head, targets, dtype, vocab_first, weights=None):
    """``(loss, dx, dhead)`` of today's arithmetic."""
    if weights is None:
        f = lambda x, h: lm_loss._xla_nll_sum(  # noqa: E731
            x, h, targets, jnp.dtype(dtype), vocab_first)
    else:
        def f(x, h):
            logits = jax.lax.dot_general(
                x.astype(dtype), h.astype(dtype),
                (((1,), (1 if vocab_first else 0,)), ((), ())),
                preferred_element_type=F32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
            return -jnp.sum(weights * picked)
    loss, (dx, dhead) = jax.value_and_grad(f, argnums=(0, 1))(x, head)
    return loss, dx, dhead


def kernels(x, head, targets, dtype, vocab_first, weights=None):
    def f(x, h):
        nll = pallas_lm_loss.nll_rows(
            x, h, targets, dtype, vocab_first=vocab_first,
            block_rows=BLOCK_ROWS, block_vocab=BLOCK_VOCAB)
        return jnp.sum(nll if weights is None else weights * nll)
    loss, (dx, dhead) = jax.value_and_grad(f, argnums=(0, 1))(x, head)
    return loss, dx, dhead


# a vocabulary of whole lanes (two blocks) and one with a ragged tail (300 =
# 2 x 128 + 44); rows of one block and of three; the head as an untied one
# lies, (E, vocab), and as a tied embedding does, (vocab, E)
@pytest.mark.parametrize("vocab_first", [False, True])
@pytest.mark.parametrize("rows", [32, 96])
@pytest.mark.parametrize("vocab", [256, 300])
def test_kernels_match_the_oracle_in_float32(vocab, rows, vocab_first):
    x, head, targets = operands(rows, vocab, vocab_first)
    with jax.default_matmul_precision("highest"):
        want = oracle(x, head, targets, F32, vocab_first)
        got = kernels(x, head, targets, F32, vocab_first)
    assert got[2].shape == head.shape and got[2].dtype == F32
    for g, w in zip(got, want):
        assert rel(g, w) < 2e-6


@pytest.mark.parametrize("vocab_first", [False, True])
@pytest.mark.parametrize("rows", [32, 96])
@pytest.mark.parametrize("vocab", [256, 300])
def test_kernels_in_bfloat16_within_the_oracles_own_error(
        vocab, rows, vocab_first):
    x, head, targets = operands(rows, vocab, vocab_first)
    exact = oracle(x, head, targets, F32, vocab_first)
    want = oracle(x, head, targets, jnp.bfloat16, vocab_first)
    got = kernels(x, head, targets, jnp.bfloat16, vocab_first)
    assert got[1].dtype == F32 and got[2].dtype == F32  # to the solver
    assert rel(got[0], want[0]) < 1e-6  # the same rounded operands
    for g, w, e in zip(got[1:], want[1:], exact[1:]):
        assert rel(g, e) < 2.5 * rel(w, e)
        assert rel(g, w) < 4e-3  # g's rounding to bfloat16: 2^-9 an entry


def test_each_row_takes_its_own_cotangent():
    """A weighted sum of the rows' losses: the backward kernel scales a row
    of ``exp(l - lse) - onehot`` by that row's cotangent."""
    x, head, targets = operands(64, 300, True, seed=1)
    weights = jax.random.uniform(jax.random.key(7), (64,)) + 0.5
    with jax.default_matmul_precision("highest"):
        want = oracle(x, head, targets, F32, True, weights)
        got = kernels(x, head, targets, F32, True, weights)
    for g, w in zip(got, want):
        assert rel(g, w) < 2e-6


# -- through the model -------------------------------------------------------
VOCAB = 384  # three blocks of whole lanes: what ``nll_sum`` hands the kernels
MODEL = {
    "model_type": "lfm2_moe", "vocab_size": VOCAB, "hidden_size": WIDTH,
    "num_hidden_layers": 2, "layer_types": ["conv", "full_attention"],
    "num_dense_layers": 1, "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "norm_eps": 1e-5, "conv_L_cache": 3, "conv_bias": False,
    "intermediate_size": 48, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0, "use_expert_bias": True,
    "experts_held": [0, 4],
}


def model_and_batch(tied, compute_dtype=None):
    model = HybridMoELM({**MODEL, "tie_word_embeddings": tied})
    model.set_compute_dtype(compute_dtype)
    params, stats = model.init(0)
    params = {g: [b * 5.0 if b.ndim > 1 else b for b in blobs]
              for g, blobs in params.items()}  # std 0.1: every term matters
    tokens = jax.random.randint(jax.random.key(3), (2, 33), 0, VOCAB)
    return model, params, stats, {
        "tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def loss_and_gradients(model, params, stats, batch):
    return jax.value_and_grad(
        lambda p: model.loss_fn(p, stats, batch)[0])(params)


@pytest.fixture
def through_the_kernels(monkeypatch):
    """``lm_loss.nll_sum`` takes the kernels (in interpreter mode off the
    TPU), three blocks of the vocabulary."""
    monkeypatch.setattr(lm_loss, "lowerable", lambda: True)
    monkeypatch.setattr(pallas_lm_loss, "BLOCK_VOCAB", BLOCK_VOCAB)


@pytest.mark.parametrize("tied", [True, False])
def test_loss_fn_through_the_kernels_matches_the_xla_path(monkeypatch, tied):
    model, params, stats, batch = model_and_batch(tied)
    with jax.default_matmul_precision("highest"):
        want, want_grads = loss_and_gradients(model, params, stats, batch)
        monkeypatch.setattr(lm_loss, "lowerable", lambda: True)
        monkeypatch.setattr(pallas_lm_loss, "BLOCK_VOCAB", BLOCK_VOCAB)
        got, grads = loss_and_gradients(model, params, stats, batch)
    assert ("head" in params) == (not tied)
    assert rel(got, want) < 2e-6
    for group in want_grads:
        for g, w in zip(grads[group], want_grads[group]):
            assert g.dtype == F32 and rel(g, w) < 2e-5, group


def test_a_tied_embeddings_gradient_is_gather_part_plus_head_part(
        through_the_kernels):
    """The tied model's gradient of the embedding against the untied model's
    two, with the untied head set to the embedding's transpose."""
    tied, params, stats, batch = model_and_batch(True)
    untied = HybridMoELM({**MODEL, "tie_word_embeddings": False})
    with jax.default_matmul_precision("highest"):
        _, grads = loss_and_gradients(tied, params, stats, batch)
        _, parts = loss_and_gradients(
            untied, {**params, "head": [params["embed"][0].T]}, stats, batch)
    gather, head = parts["embed"][0], parts["head"][0]
    assert rel(gather, grads["embed"][0]) > 0.1  # neither part is the whole
    assert rel(head.T, grads["embed"][0]) > 0.1
    assert rel(grads["embed"][0], gather + head.T) < 2e-5


def test_loss_fn_in_bfloat16_through_the_kernels(monkeypatch):
    model, params, stats, batch = model_and_batch(True, jnp.bfloat16)
    want, want_grads = loss_and_gradients(model, params, stats, batch)
    monkeypatch.setattr(lm_loss, "lowerable", lambda: True)
    monkeypatch.setattr(pallas_lm_loss, "BLOCK_VOCAB", BLOCK_VOCAB)
    got, grads = loss_and_gradients(model, params, stats, batch)
    assert rel(got, want) < 1e-5
    for group in want_grads:
        for g, w in zip(grads[group], want_grads[group]):
            assert g.dtype == F32 and rel(g, w) < 1e-2, group


# -- what the operation tells of itself ------------------------------------------
def traced_loss_path(rows, width, vocab, dtype, vocab_first=False):
    """The ``loss_path`` instants of one trace (nothing runs)."""
    shape = jax.ShapeDtypeStruct
    head = (vocab, width) if vocab_first else (width, vocab)
    tracer = obs.install_tracer(Tracer())
    try:
        jax.eval_shape(
            lambda x, h, t: lm_loss.nll_sum(
                x, h, t, dtype, vocab_first=vocab_first),
            shape((2, rows // 2, width), F32), shape(head, F32),
            shape((2, rows // 2), jnp.int32))
    finally:
        obs.uninstall_tracer()
    return [e["args"] for e in tracer.events() if e["name"] == "loss_path"]


# lfm2moe-train-8k's window and its step check, tier-1's exact comparisons in
# float32; then what ``nll_sum`` does not hand the kernels:
# qwen3next-train-8k's window (18,992 = 148 lanes + 48: PERF.md section 6,
# PR 32), a width of a quarter lane, rows not in whole blocks, a dtype
@pytest.mark.parametrize(
    "rows, width, vocab, dtype, vocab_first, path, blocks", [
        (16384, 2048, 8192, "bfloat16", True, "pallas", (512, 1024)),
        (2048, 2048, 8192, "bfloat16", True, "pallas", (512, 1024)),
        (64, 128, 384, "float32", False, "pallas", (64, 384)),
        (16384, 2048, 18992, "bfloat16", False, "xla", (512, 1024)),
        (64, 128, 300, "float32", False, "xla", (64, 384)),
        (64, 32, 128, "float32", True, "xla", (64, 128)),
        (16384 + 64, 2048, 8192, "bfloat16", True, "xla", (512, 1024)),
        (16384, 2048, 8192, "float16", True, "xla", (512, 1024)),
    ])
def test_loss_path_is_told_once_a_trace(
        monkeypatch, rows, width, vocab, dtype, vocab_first, path, blocks):
    monkeypatch.setattr(lm_loss, "lowerable", lambda: True)
    (told,) = traced_loss_path(rows, width, vocab, dtype, vocab_first)
    assert told["path"] == path
    assert told["why"] == ("" if path == "pallas" else pallas_lm_loss.ACCEPTS)
    assert (told["rows"], told["width"], told["vocab"], told["dtype"]) == (
        rows, width, vocab, dtype)
    assert told["vocab_first"] == vocab_first
    assert (told["block_rows"], told["block_vocab"]) == blocks


def test_loss_path_off_the_tpu_is_xla_and_says_why():
    (told,) = traced_loss_path(16384, 2048, 8192, "bfloat16", True)
    assert told["path"] == "xla"
    assert told["why"] == f"no Pallas lowering on {jax.default_backend()}"


def test_the_loss_operations_carry_the_head_scope(through_the_kernels):
    """``lfm2_head_device_ms`` and ``head_device_ms`` read the ``LMHead`` type
    and nothing else: the kernels and the gradient products sit under it,
    forward outside ``transpose(`` and backward inside (``custom_vjp``
    carries the scope)."""
    model, params, stats, batch = model_and_batch(True)
    text = jax.jit(lambda p: loss_and_gradients(model, p, stats, batch)).lower(
        params).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    forward = [n for n in op_names if "lm_loss_forward" in n.split("/")]
    backward = [n for n in op_names if "lm_loss_backward" in n.split("/")]
    assert forward and backward
    assert all("jvp(LMHead:head)" in n.split("/") for n in forward)
    assert all("transpose(jvp(LMHead:head))" in n.split("/") for n in backward)
    # ... and XLA's two gradient products of the backward beside its kernel
    assert any(n.endswith("/checkpoint/dot_general")
               and "transpose(jvp(LMHead:head))" in n.split("/")
               for n in op_names)
    # the head's recomputation keeps ``lse``: the forward kernel runs once
    assert not any("rematted_computation" in n.split("/") for n in forward)
