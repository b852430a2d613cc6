"""Kernels of the main path compiled for the v5e at the widths the benchmark
runs them at, without a chip: the TPU's compiler is installed here and
compiles for a chip that is described, not attached.  This catches what
interpreter mode cannot — a block shape, a layout or a VMEM budget that
Mosaic refuses — at no chip time.  Nothing runs; results are tested elsewhere
(``tests/test_hybrid_lm.py``, ``tests/test_attention.py``).

The topology is described inside a fixture, never while a module is imported:
only one process at a time may load the TPU's library, and every xdist worker
imports every test file.  All such tests stay in this one file.
"""

import os

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparknet_tpu.models import hybrid_lm
from sparknet_tpu.ops import (
    lm_loss,
    pallas_attention,
    pallas_delta_rule,
    pallas_grouped_matmul,
    pallas_lm_loss,
    sparse_attention,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# qwen3next-train-8k: 2 sequences of 8,192 tokens, 32 heads of 128, chunks of
# 64, bfloat16; and its check's rule in float32 (one sequence)
@pytest.mark.parametrize("batch, dtype", [(2, "bfloat16"), (1, "float32")])
@pytest.mark.parametrize("backward", [False, True])
def test_delta_rule_kernels_compile_for_the_v5e(
        one_chip, monkeypatch, batch, dtype, backward):
    monkeypatch.setattr(pallas_delta_rule, "lowerable", lambda: True)
    t, h, d, chunk, cd = 8192, 32, 128, 64, jnp.dtype(dtype)
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    inputs = (shape(batch, t, h, d),) * 3 + (shape(batch, t, h),) * 2

    def forward(*xs):
        return pallas_delta_rule.within_chunks(*xs, chunk, cd)

    def gradients(*xs):
        outs, vjp = jax.vjp(forward, *xs)
        return vjp(outs)

    compiled = jax.jit(gradients if backward else forward).lower(
        *inputs).compile()
    name = "delta_rule_within_chunks" + ("_backward" if backward else "")
    assert name in compiled.as_text()


# the flash kernels: qwen3next-train-8k's gated-attention layer (2 sequences
# of 8,192 tokens, 16 query heads on 2 K/V heads of 256, bfloat16, the blocks
# ``causal_gqa_attention`` gives them) and its check in float32 (T = 1,024);
# the byte LM's (``models/transformer_lm.py``: heads of 128 in ``flash_
# attention``'s default blocks) at T = 4,096, which the whole-head kernels
# these replaced were refused at (PR 21), and heads-first at a head of 64
@pytest.mark.parametrize(
    "batch, t, hq, hkv, d, dtype, block_q, out_dtype, backward", [
        (2, 8192, 16, 2, 256, "bfloat16", 256, "float32", True),
        (2, 1024, 16, 2, 256, "float32", 128, "float32", False),
        (2, 4096, 8, 8, 128, "bfloat16", 128, None, True),
        (2, 4096, 8, 8, 64, "bfloat16", 128, None, True),
    ])
def test_flash_attention_kernels_compile_for_the_v5e(
        one_chip, batch, t, hq, hkv, d, dtype, block_q, out_dtype, backward):
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (batch, t, h, d), jnp.dtype(dtype), sharding=one_chip)

    def forward(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=block_q, interpret=False,
            out_dtype=out_dtype)

    def gradients(q, k, v):
        out, vjp = jax.vjp(forward, q, k, v)
        return vjp(out)

    compiled = jax.jit(gradients if backward else forward).lower(
        shape(hq), shape(hkv), shape(hkv)).compile()
    names = ["flash_attention_forward"] + (
        ["flash_attention_backward"] if backward else [])
    assert all(name in compiled.as_text() for name in names)


# the same kernels under a WINDOW, as ``ops/attention.
# causal_gqa_attention`` hands laguna-train-8k's sliding layers to them: 2
# sequences of 8,192 tokens, 64 query heads on 8 K/V heads of 128, a window
# of 512, the blocks it gives them in bfloat16 (output handed over in
# bfloat16) and in its check's float32.  What is settled here: Mosaic takes
# the band's index maps (first block the window reaches, clamped to the
# last) and the window's mask beside the diagonal's, and the one-pass
# backward's dk / dv over the sequence's 8,192 keys fit VMEM
@pytest.mark.parametrize("t, hq, window, dtype, out_dtype", [
    (8192, 64, 512, "bfloat16", None), (8192, 64, 512, "float32", "float32"),
    # and the full layers' causal kernels in its step check's float32: a
    # group of 6 heads, whose MiB of queries is 341 rows of 128 lanes
    (1536, 48, None, "float32", "float32")])
def test_flash_attention_kernels_under_a_window_compile_for_the_v5e(
        one_chip, monkeypatch, t, hq, window, dtype, out_dtype):
    from sparknet_tpu.ops import attention

    monkeypatch.setattr(attention, "lowerable", lambda: True)
    calls = []
    real = pallas_attention.flash_attention
    monkeypatch.setattr(pallas_attention, "flash_attention",
                        lambda *a, **kw: calls.append(kw) or real(
                            *a, **kw, interpret=False))
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (2, t, h, 128), jnp.dtype(dtype), sharding=one_chip)

    def gradients(q, k, v):
        out, vjp = jax.vjp(lambda *a: attention.causal_gqa_attention(
            *a, compute_dtype=jnp.dtype(dtype), window=window,
            out_dtype=out_dtype), q, k, v)
        return vjp(out)

    text = jax.jit(gradients).lower(
        shape(hq), shape(8), shape(8)).compile().as_text()
    assert all(name in text for name in (
        "flash_attention_forward", "flash_attention_backward"))
    assert calls[0]["window"] == window
    assert calls[0]["block_q"] % 128 == 0


# the same kernels WITH a keep-mask (``masked_flash_attention``), as
# ``ops/sparse_attention.masked_attention`` hands keye2-train-16k's layer to
# them: one sequence, 32 query heads on 4 K/V heads of 128, the selection as
# ``(1, T, T / 32)`` words, blocks of 512 x 1,024 in bfloat16 and 256 x 1,024
# in its checks' float32; at T = 16,384 a key block is two bits of every
# word side by side, at the checks' 4,096 eight.  What is settled here: the
# blocks fit VMEM beside the one-pass backward's dk / dv over 16,384 keys
# (16 MiB, its budget), and Mosaic takes the unpack and the backward's 32-bit
# transpose of the unpacked tile
@pytest.mark.parametrize("dtype, block_q", [("bfloat16", 512), ("float32", 256)])
@pytest.mark.parametrize("t", [16384, 4096])
def test_flash_attention_kernels_under_a_keep_mask_compile_for_the_v5e(
        one_chip, t, dtype, block_q):
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, t, h, 128), jnp.dtype(dtype), sharding=one_chip)
    bits = jax.ShapeDtypeStruct((1, t, t // 32), jnp.uint32, sharding=one_chip)

    def forward(q, k, v, bits):
        return pallas_attention.masked_flash_attention(
            q, k, v, bits, block_q=block_q, block_k=1024, interpret=False,
            scale=1.0, out_dtype=jnp.float32)

    def gradients(q, k, v, bits):
        out, vjp = jax.vjp(lambda *qkv: forward(*qkv, bits), q, k, v)
        return vjp(out)

    text = jax.jit(gradients).lower(
        shape(32), shape(4), shape(4), bits).compile().as_text()
    assert all(name in text for name in (
        "flash_attention_forward", "flash_attention_backward"))


# the same kernels WITH a second score term (``mla_flash_attention``),
# as ``ops/attention.causal_mla_attention`` hands kanana2-train-8k's layers to
# them: 2 sequences of 8,192 tokens, 32 heads of 128 + 64 against values of
# 128, the one rope key ``(2, T, 64)``, blocks of 1,024 x 1,024 in bfloat16,
# and its checks' float32 at T = 1,024 (one block).  What is settled here: Mosaic takes the
# rope blocks of 64 lanes (whole where the array ends) and the 64-deep and
# 64-wide products, and the compiled gradient holds no float32 ``(heads,
# queries, keys)`` scores
@pytest.mark.parametrize("t, dtype, block_q", [
    (8192, "bfloat16", 1024), (1024, "float32", 1024)])
def test_flash_attention_kernels_with_a_rope_term_compile_for_the_v5e(
        one_chip, t, dtype, block_q):
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        (2, t, *s), jnp.dtype(dtype), sharding=one_chip)

    def forward(q_nope, q_rope, k_nope, k_rope, v):
        return pallas_attention.mla_flash_attention(
            q_nope, q_rope, k_nope, k_rope, v, block_q=block_q, block_k=1024,
            interpret=False, scale=1.0, out_dtype=jnp.float32)

    def gradients(*xs):
        out, vjp = jax.vjp(forward, *xs)
        return vjp(out)

    text = jax.jit(gradients).lower(
        shape(32, 128), shape(32, 64), shape(32, 128), shape(64),
        shape(32, 128)).compile().as_text()
    assert all(name in text for name in (
        "flash_attention_forward", "flash_attention_backward"))
    assert not re.search(rf"f32\[(2,)?32,{t},{t}\]", text)


# past the one-pass backward's budget (a K/V head's dk / dv over 32,768 keys
# of 128, 32 MiB, as a long ring shard may hold) the dq and dk/dv passes,
# which hold no more than a block of keys, still compile
def test_the_two_pass_backward_compiles_for_the_v5e_past_the_budget(
        one_chip):
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 32768, h, 128), jnp.bfloat16, sharding=one_chip)

    def gradients(q, k, v):
        out, vjp = jax.vjp(lambda *a: pallas_attention.flash_attention(
            *a, causal=True, block_q=512, interpret=False), q, k, v)
        return vjp(out)

    text = jax.jit(gradients).lower(
        shape(8), shape(2), shape(2)).compile().as_text()
    assert "flash_attention_dkv" in text and "flash_attention_dq" in text
    assert "flash_attention_backward" not in text


def test_alignment_loss_with_its_gradient_holds_no_float32_head_scores(
        one_chip, monkeypatch):
    """keye2-train-16k's alignment loss with its gradient (``ops/
    sparse_attention.alignment_loss``: one sequence of 16,384, 32 / 4 heads of
    128, 16 index heads of 64, bfloat16) compiles for the v5e as the chip
    runs it, through the kernel (``ops/pallas_alignment.py``, blocks of 512 x
    512), in 160 MiB of temporaries: the index heads' and the weights' copies
    heads first and the gradients handed back (the XLA form it replaced read
    174, autodiff through the blocks 671; PERF.md section 6).  One float32
    array of ``(512, 16, keys)`` in HBM, the index scores a head or their
    cotangent, is 512 MiB at the widest run: what the ceiling catches if a
    change brings it back."""
    from sparknet_tpu.ops import attention, pallas_alignment

    for module in (attention, pallas_alignment):  # the path, no interpreter
        monkeypatch.setattr(module, "lowerable", lambda: True)
    t = 16384
    shape = lambda s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (1, t, *s), dtype, sharding=one_chip)

    def loss(qi, w, ki, q, k, lse, bits):
        return sparse_attention.alignment_loss(qi, w, ki, q, k, lse, bits) / t

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape((16, 64)), shape((16,), jnp.float32), shape((64,)),
        shape((32, 128)), shape((4, 128)), shape((32,), jnp.float32),
        shape((t // 32,), jnp.uint32)).compile()
    assert "alignment_gradient" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# the sequence models' loss, forward + backward in bfloat16 at rows x width x
# vocabulary: lfm2moe-train-8k's window (a tied head, read as the embedding
# lies), qwen3next-train-8k's (18,992 = 18 blocks of 1,024 + 560: the last
# block hangs over the edge; ``nll_rows`` takes it, ``lm_loss.nll_sum`` does
# not hand it over) and the step check's 2 x 1,024 tokens
@pytest.mark.parametrize("rows, width, vocab, vocab_first", [
    (16384, 2048, 8192, True),
    (16384, 2048, 18992, False),
    (2048, 2048, 8192, True),
])
def test_lm_loss_kernels_compile_for_the_v5e(
        one_chip, rows, width, vocab, vocab_first):
    shape = lambda s, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)

    def gradients(x, head, targets):
        return jax.value_and_grad(
            lambda x, head: jnp.sum(pallas_lm_loss.nll_rows(
                x, head, targets, jnp.bfloat16, vocab_first=vocab_first,
                interpret=False)), argnums=(0, 1))(x, head)

    compiled = jax.jit(gradients).lower(
        shape((rows, width)),
        shape((vocab, width) if vocab_first else (width, vocab)),
        shape((rows,), jnp.int32)).compile()
    assert all(name in compiled.as_text()
               for name in ("lm_loss_forward", "lm_loss_backward"))


def test_no_reduce_window_over_the_vocabulary(one_chip, monkeypatch):
    """``HybridMoELM.loss_fn``'s head at lfm2moe-train-8k's shape, (2, 8,192)
    tokens x 8,192 columns.  Of ``log_softmax`` over ``(B, T, vocab)`` float32
    logits the v5e's compiler made a ``reduce-window`` of size 16,383 for the
    row maximum, quadratic in the vocabulary: 54.6 ms where the product is 3,
    forward and again in the backward (PERF.md section 6, PR 31 and PR 32).
    The loss hands XLA no such tensor: this guards against its coming back."""
    for module in (lm_loss, pallas_lm_loss):  # the path, and no interpreter
        monkeypatch.setattr(module, "lowerable", lambda: True)
    shape = lambda s, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)

    def head_of_loss_fn(x, embed, targets):
        with jax.named_scope("LMHead:head"):
            return jax.checkpoint(
                lambda x, embed: lm_loss.nll_sum(
                    x, embed, targets, jnp.bfloat16, vocab_first=True),
                policy=hybrid_lm.HEAD_KEEPS)(x, embed)

    text = jax.jit(jax.value_and_grad(head_of_loss_fn, argnums=(0, 1))).lower(
        shape((2, 8192, 2048)), shape((8192, 2048)),
        shape((2, 8192), jnp.int32)).compile().as_text()
    # the recomputation keeps ``lse``: each kernel is one custom call
    for kernel in ("lm_loss_forward", "lm_loss_backward"):
        assert len(re.findall(rf"%{kernel}[.\d]* = .* custom-call\(", text)) == 1
    assert "reduce-window" not in text


# the held experts' grouped products (``ops/pallas_grouped_matmul.py``) with
# their backward, at each sequence cell's grouped-path rows x hidden 2,048 x
# experts of 512 / 1,536 / 768 (qwen3next-, lfm2moe-, keye2-, kanana2-train):
# gate / up (2,048 -> F) and down (F -> 2,048).  What is settled here: the
# blocks fit VMEM, Mosaic takes the transposed operands of ``dlhs`` and
# ``drhs``, and no float32 array of ``(rows, width)`` or ``(n, K, N)`` is a
# temporary (the smallest, 16,384 x 512 x 4 bytes, is 32 MiB)
@pytest.mark.parametrize("rows, f, n", [
    (20480, 512, 32), (16384, 1536, 8), (32768, 768, 16), (24576, 768, 16)])
def test_grouped_matmul_kernels_compile_for_the_v5e(one_chip, rows, f, n):
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)

    def gradients(lhs, rhs, sizes, dout):
        out, vjp = jax.vjp(lambda a, b: pallas_grouped_matmul.grouped_matmul(
            a, b, sizes, interpret=False), lhs, rhs)
        return out, vjp(dout)

    for k, width in ((2048, f), (f, 2048)):
        compiled = jax.jit(gradients).lower(
            shape(rows, k), shape(n, k, width), shape(n, dtype=jnp.int32),
            shape(rows, width, dtype=jnp.float32)).compile()
        text = compiled.as_text()
        assert all(name in text for name in (
            "grouped_matmul", "grouped_matmul_dlhs", "grouped_matmul_drhs"))
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
