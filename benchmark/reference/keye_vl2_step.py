"""``keye_vl2`` as ``lm_checks.step_program`` differentiates it.

That program (an accepted file) takes ``jax.grad`` of ``cross_entropy(
ref.logits(...))``: one loss.  This model's step minimises two, ``L_LM + L_I``
(``keye_vl2.loss``), so the logits handed over here CARRY the alignment
loss: ``carrying(logits, L_I)`` is ``logits`` in the forward pass, and in the
backward pass sends ``L_I`` the cotangent 1 beside the logits' own.  The
gradient the step program then compares the solver's with is ``jax.grad`` of
``keye_vl2.loss``, every leaf (tier-1 holds the two equal,
``tests/test_keye_cell.py``); the loss it prints is ``L_LM`` alone, which is
why the configuration's ``step_rel_tol`` bounds no ``loss`` (the two losses
are compared apart, ``keye_checks.indexer_learns`` and the forward check).
"""

import jax
import jax.numpy as jnp

from benchmark.reference import keye_vl2
from benchmark.reference.keye_vl2 import adam_step, route  # noqa: F401


@jax.custom_vjp
def carrying(logits, extra):
    return logits


carrying.defvjp(
    lambda logits, extra: (logits, None),
    lambda _, cotangent: (cotangent, jnp.ones((), cotangent.dtype)))


def logits(params, tokens, config, operand_dtype=None, remat=False):
    return carrying(*keye_vl2.logits_and_alignment(
        params, tokens, config, operand_dtype, remat))
