"""The next-token loss of a sequence model: ``-sum log_softmax(hidden @
head)[target]`` over every row, as one operation that owns its pass over
the vocabulary.

Where Pallas lowers and ``pallas_lm_loss.accepts`` the shapes, the kernels
of ``ops/pallas_lm_loss.py``: the vocabulary is walked in blocks with a
running maximum and sum, no ``(rows, vocab)`` float32 tensor is ever handed
to XLA to reduce, the backward is the operation's own and what it keeps is
the operands and a float32 ``lse`` a row.  Elsewhere ``_xla_nll_sum``,
``log_softmax`` and ``take_along_axis`` on the operands as they come: the
fallback, and the oracle the kernels are tested against.  No switch picks
between them: an ``obs`` instant names the path at each trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sparknet_tpu import obs
from sparknet_tpu.ops.attention import lowerable  # Pallas, imported when asked

F32 = jnp.float32

# What a ``jax.checkpoint`` around the caller may keep (``policy=jax.
# checkpoint_policies.save_only_these_names(*SAVED)``) so that its
# recomputation does not run the forward kernel a second time.
SAVED = ("lm_loss_lse",)


def _xla_nll_sum(hidden, head, targets, cd, vocab_first):
    """The loss in XLA: float32 logits of ``(..., vocab)``."""
    logits = jax.lax.dot_general(
        hidden.astype(cd), head.astype(cd),
        (((hidden.ndim - 1,), (1 if vocab_first else 0,)), ((), ())),
        preferred_element_type=F32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))


def nll_sum(hidden, head, targets, compute_dtype=None, *,
            vocab_first: bool = False):
    """The summed next-token loss, a float32 scalar.

    ``hidden``: ``(..., E)`` float32, normed; ``head``: ``(E, vocab)`` —
    ``(vocab, E)`` with ``vocab_first``, as a tied embedding lies —;
    ``targets``: ``(...)`` ints below ``vocab``.  The product takes its
    operands in ``compute_dtype`` and accumulates in float32; maximum,
    exponential, sum and logarithm are float32, and so are the gradients.
    The XLA path keeps its logits for the backward pass: a caller that
    cannot afford them recomputes (``jax.checkpoint``, ``SAVED``)."""
    from sparknet_tpu.ops import pallas_lm_loss  # see attention.lowerable

    width = hidden.shape[-1]
    targets = targets.astype(jnp.int32)
    rows, vocab = targets.size, head.shape[0 if vocab_first else 1]
    cd = jnp.dtype(compute_dtype or F32)
    block_rows, block_vocab = pallas_lm_loss.blocks(rows, vocab)
    backend = jax.default_backend()
    if not lowerable():
        why = f"no Pallas lowering on {backend}"
    elif not pallas_lm_loss.accepts(rows, width, vocab, cd):
        why = pallas_lm_loss.ACCEPTS
    else:
        why = ""
    obs.instant("loss_path", cat="kernel", path="xla" if why else "pallas",
                why=why, backend=backend, rows=rows, width=width, vocab=vocab,
                vocab_first=bool(vocab_first), dtype=cd.name,
                block_rows=block_rows, block_vocab=block_vocab)
    if why:
        return _xla_nll_sum(hidden, head, targets, cd, bool(vocab_first))
    return jnp.sum(pallas_lm_loss.nll_rows(
        hidden.reshape(rows, width), head, targets.reshape(rows), cd,
        vocab_first=vocab_first))
