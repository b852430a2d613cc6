"""The comparisons that decide ``correct`` in an ``lm-train-resident`` cell,
each made outside the window, on the objects the window times
(``cell.model``, ``cell.solver``, the trainer's own state) against the
configuration's plain reference (``benchmark/reference/<reference>.py``), at
the published widths.

- ``forward_against_reference``: logits and loss of ``cell.model`` on one
  sequence of the timed length.
- ``step_against_reference``: ONE STEP OF ``cell.solver`` on a step's worth
  of sequences at ``check.step_seq_len``, every leaf: the first gradient (read
  back from ADAM's first moment), the update the solver made of it, and the
  parameters' change, against ``jax.grad`` of the reference's loss and plain
  Adam; and the model's forward pass in float32 under
  ``default_matmul_precision("highest")`` against the same logits (summation
  order only).
- ``float32_parts``: the router and the delta rule alone.

Every function takes the worker-stacked state and slices worker 0 inside its
jit: no second copy of the weights is made beside the training state, and the
state is left as it was.  The numbers behind each verdict go to ``[bench]``
lines through ``cell.log``.

``LM_CHECK_PLANT`` (a comma-separated list of ``PLANTS``) plants a fault in
what a comparison is given, for the readings of PERF.md and the kept test:
such a run has to print ``correct: false``.
"""

import importlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.checks import rel_err

PLANTS = {
    "state_unchanged": "the solver's step returns the state it was given",
    "half_batch": "the solver's step trains on the first sequence alone",
    "bfloat16_update": "the updated parameters are rounded to bfloat16",
    "float8_reference": "the reference's matrix products take float8_e4m3fn "
    "operands (the nearest precision below the stated bfloat16)",
    "bfloat16_router": "the router's operands are rounded to bfloat16",
    "bfloat16_state": "the delta rule's place is taken by the recurrence "
    "with its state rounded to bfloat16 after every token",
}


def planted(cell):
    names = [n for n in os.environ.get("LM_CHECK_PLANT", "").split(",") if n]
    unknown = sorted(set(names) - set(PLANTS))
    if unknown:
        raise SystemExit(f"LM_CHECK_PLANT: unknown {unknown}; {sorted(PLANTS)}")
    for n in names:
        cell.log(f"PLANTED FAULT {n}: {PLANTS[n]}; this run is not correct")
    return set(names)


def zipf_tokens(key, shape, vocab, exponent):
    """Token ids with P(id = r) proportional to ``(r + 1) ** -exponent``, by
    the inverse of the cumulative distribution; on the device."""
    weights = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -exponent
    cdf = jnp.cumsum(weights) / jnp.sum(weights)
    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.searchsorted(cdf, u).clip(0, vocab - 1).astype(jnp.int32)


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def worker0(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def reference_of(cell, plants):
    ref = importlib.import_module(
        "benchmark.reference." + cell.config["reference"])
    dtype = jnp.float8_e4m3fn if "float8_reference" in plants else None
    return ref, dtype


def timed(cell, what, fn, *args):
    """``fn(*args)``, awaited; its seconds (compilation with it) logged."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    cell.log(f"check program '{what}': {time.perf_counter() - t0:.1f} s")
    return out


def within(cell, errors, tol):
    """Whether every quantity the file bounds is inside its bound; one
    without a bound is printed and not judged."""
    over = {k: (float(errors[k]), tol[k]) for k in tol
            if not errors[k] <= tol[k]}
    if over:
        cell.log(f"OUT of bound (reading, bound): {over}")
    return not over


def logit_errors(cell, got, want, label):
    per_token = jax.jit(lambda g, w: (jnp.linalg.norm(g - w, axis=-1)
                                      / jnp.linalg.norm(w, axis=-1)).ravel()
                        )(got, want)
    q = [float(x) for x in jnp.quantile(
        per_token, jnp.array([0.5, 0.9, 0.99, 1.0]))]
    cell.log(f"logits, per token, {label}: relative error median {q[0]:.3g}, "
             f"90% {q[1]:.3g}, 99% {q[2]:.3g}, max {q[3]:.3g}")
    return {"logits": rel_err(got, want), "logits_median": q[0]}


def forward_against_reference(cell, plants):
    """Loss and logits of ``cell.model`` (the timed model in its compute
    dtype: chunked rule, blockwise attention, grouped experts) on one
    sequence of ``check.seq_len`` against the reference."""
    config, spec = cell.config, cell.config["check"]
    ref, ref_dtype = reference_of(cell, plants)
    ids = zipf_tokens(jax.random.key(cell.seed), (1, spec["seq_len"] + 1),
                      config["vocab_size"], cell.traffic["zipf_exponent"])
    tokens, targets = ids[:, :-1], ids[:, 1:]
    with jax.default_matmul_precision("highest"):
        want = timed(cell, "reference forward", jax.jit(
            lambda p, t: ref.logits(worker0(p), t, config, ref_dtype)),
            cell.state.params, tokens)
    got = timed(cell, "model forward", jax.jit(
        lambda p, t: cell.model.forward_logits(worker0(p), t)),
        cell.state.params, tokens)
    errors = logit_errors(cell, got, want, config["compute_dtype"])
    want_loss = float(jax.jit(cross_entropy)(want, targets))
    got_loss = float(jax.jit(cross_entropy)(got, targets))
    errors["loss"] = abs(got_loss - want_loss) / abs(want_loss)
    cell.reference_loss = want_loss
    cell.log(f"forward at T = {spec['seq_len']} against the plain reference, "
             f"relative L2 error: {errors}; reference loss {want_loss:.4f}")
    return {"forward_stated_dtype": within(cell, errors, spec["forward_rel_tol"])}


def leaf_names(params):
    return [f"{g}[{i}]" for g in params for i in range(len(params[g]))]


def sq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def summary(sums, names, matrices):
    """From per-leaf ``(|difference|^2, |wanted|^2)``: the worst matrix leaf,
    the worst vector leaf, and the whole tree taken as one vector."""
    sums = np.asarray(sums, np.float64)
    leaf = np.sqrt(sums[:, 0] / np.maximum(sums[:, 1], 1e-60))
    worst = lambda mask: max(  # noqa: E731
        ((leaf[i], names[i]) for i in range(len(names)) if mask[i]),
        default=(0.0, "-"))
    total = float(np.sqrt(sums[:, 0].sum() / max(sums[:, 1].sum(), 1e-60)))
    return leaf, worst(matrices), worst(~matrices), total


def step_program(solver, ref, config, plants=(), ref_dtype=None):
    """``(stacked state, tokens, targets, rng) -> (reference's loss and
    logits, solver's loss, sums)``: ``jax.grad`` of the plain reference's loss
    (traced under ``highest``), one iteration of ``solver``
    (``Solver._one_iter``, what the round's scan runs) on worker 0's state,
    and per leaf three pairs ``(|difference|^2, |wanted|^2)``, for
    ``gradient``, ``update`` and ``change`` as ``step_against_reference``
    describes them.  One program, and only sums and the logits leave it:
    neither gradient tree nor the new state is ever held beside the training
    state, so the check stays under the window's peak of memory."""
    adam = adam_of(config)
    b1 = adam["beta1"]

    def reference(params, tokens, targets):
        def loss(p):
            # a layer at a time in the backward pass: beside the training
            # state the chip has no room for four layers' residuals
            logits = ref.logits(p, tokens, config, ref_dtype, remat=True)
            return cross_entropy(logits, targets), logits
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, has_aux=True)(params)

    def program(state, tokens, targets, rng):
        before = worker0(state)
        (want_loss, want_logits), want_grads = reference(
            before.params, tokens, targets)
        if "half_batch" in plants:
            tokens, targets = (jnp.repeat(x[:1], x.shape[0], axis=0)
                               for x in (tokens, targets))
        after, loss = solver._one_iter(
            before, {"tokens": tokens, "targets": targets}, rng)
        if "state_unchanged" in plants:
            after = before
        step = before.iter.astype(jnp.float32) + 1.0
        rows = []
        for g in before.params:
            for i, w in enumerate(before.params[g]):
                w1, want_g = after.params[g][i], want_grads[g][i]
                if "bfloat16_update" in plants:
                    w1 = rounded_to_bfloat16(w1)
                m0, v0 = (h[g][i] for h in before.history)
                m1, v1 = (h[g][i] for h in after.history)
                got_g = (m1 - b1 * m0) / (1.0 - b1)
                own_w, _, own_v = ref.adam_step(w, m0, v0, got_g, step, **adam)
                want_w, _, _ = ref.adam_step(w, m0, v0, want_g, step, **adam)
                moved = sq(own_w - w)
                rows.append(jnp.stack([
                    sq(got_g - want_g), sq(want_g),
                    # the parameters' and the second moment's relative
                    # errors, added in quadrature
                    sq(w1 - own_w) + moved * sq(v1 - own_v)
                    / jnp.maximum(sq(own_v), 1e-37), moved,
                    sq(w1 - want_w), sq(want_w - w)]))
        return want_loss, want_logits, loss, jnp.stack(rows)
    return program


def rounded_to_bfloat16(x):
    """Float32 values that bfloat16 holds; a pair of casts the compiler may
    take out (``xla_allow_excess_precision``), this it may not."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def adam_of(config):
    s = config["solver"]
    return dict(lr=s["base_lr"], beta1=s["momentum"], beta2=s["momentum2"],
                delta=s["delta"])


def step_against_reference(cell, plants):
    """One step of ``cell.solver`` from the trainer's state, on
    ``sequences_per_step`` sequences of ``check.step_seq_len`` tokens,
    against the plain step: ``jax.grad`` of the reference's loss, then the
    reference's ``adam_step``.  Per leaf, as relative L2 errors:

    - ``gradient``: the solver's first gradient, read back from the first
      moment it leaves (``(m1 - beta1 m0) / (1 - beta1)``), against the
      reference's.  A moment left unchanged reads 1; half the batch near it.
    - ``update``: the parameters and second moment the solver leaves against
      plain Adam fed that same gradient: the solver's arithmetic alone,
      float32 rounding.  A parameter or moment left unchanged reads 1.
    - ``change``: the parameters' change against the plain step's, end to
      end.  ADAM's first step moves every weight by ``rate * g / (|g| +
      delta')``, the rate times the gradient's sign: this norm counts the
      signs that differ (``2 sqrt(share)``), and reads 1 where nothing moved.

    Each as the worst matrix leaf, the worst vector leaf (``_vectors``) and
    the whole tree as one vector (``_whole``).  Then the forward pass of the
    model in float32 under ``default_matmul_precision("highest")`` (a second
    object of the model's class: the timed one computes in the stated dtype)
    against the same reference logits: summation order only, so a dropped or
    altered term in any layer fails.  Its backward pass is jax's own
    transposition of that forward pass (the program has no hand-written
    one), and tier-1 compares every gradient in float32 at a small size."""
    from sparknet_tpu.utils.rngs import default_train_key

    config, spec = cell.config, cell.config["check"]
    ref, ref_dtype = reference_of(cell, plants)
    batch, t = cell.traffic["sequences_per_step"], spec["step_seq_len"]
    ids = zipf_tokens(jax.random.fold_in(jax.random.key(cell.seed), 1),
                      (batch, t + 1), config["vocab_size"],
                      cell.traffic["zipf_exponent"])
    # every second sequence takes its ids from the other end of the
    # vocabulary: two sequences of one distribution have nearly one
    # gradient, and a step that dropped one of them would read like rounding
    ids = jnp.where(jnp.arange(batch)[:, None] % 2 == 1,
                    config["vocab_size"] - 1 - ids, ids)
    tokens, targets = ids[:, :-1], ids[:, 1:]
    names = leaf_names(cell.state.params)
    matrices = np.array([leaf.ndim > 2 for leaf in  # stacked: one axis more
                         jax.tree_util.tree_leaves(cell.state.params)])
    by_leaf = lambda leaf: dict(zip(  # noqa: E731
        names, (float(f"{x:.3g}") for x in leaf)))

    want_loss, want_logits, got_loss, sums = timed(
        cell, "reference step and solver step",
        jax.jit(step_program(cell.solver, ref, config, plants, ref_dtype)),
        cell.state, tokens, targets, default_train_key(0))
    want_loss = float(want_loss)
    sums = np.asarray(sums, np.float64)
    errors = {"loss": abs(float(got_loss) - want_loss) / want_loss}
    for k, what in enumerate(("gradient", "update", "change")):
        leaf, matrix, vector, total = summary(
            sums[:, 2 * k:2 * k + 2], names, matrices)
        errors[what], errors[what + "_vectors"] = matrix[0], vector[0]
        errors[what + "_whole"] = total
        cell.log(f"solver step, {what}: worst matrix {matrix[1]} "
                 f"{matrix[0]:.3g}, worst vector {vector[1]} {vector[0]:.3g}, "
                 f"the whole tree {total:.3g}; by leaf {by_leaf(leaf)}")
    cell.log(f"one step of the cell's solver ({batch} x {t} tokens, "
             f"{config['compute_dtype']}) against the plain step, relative L2 "
             f"error: {errors}; loss {float(got_loss):.4f}, the reference's "
             f"{want_loss:.4f}")
    verdict = {"step_stated_dtype": within(cell, errors, spec["step_rel_tol"])}

    exact_model = type(cell.model)({**config, "compute_dtype": None})
    with jax.default_matmul_precision("highest"):
        logits = timed(cell, "model forward in float32", jax.jit(
            lambda p, t: exact_model.forward_logits(worker0(p), t)),
            cell.state.params, tokens)
    errors = logit_errors(cell, logits, want_logits, "float32/highest")
    value = float(jax.jit(cross_entropy)(logits, targets))
    errors["loss"] = abs(value - want_loss) / want_loss
    cell.log(f"the model in float32/highest against the plain reference "
             f"({batch} x {t} tokens), relative L2 error: {errors}")
    verdict["step_exact"] = within(cell, errors, spec["exact_rel_tol"])
    return verdict


def router_against_reference(cell, plants):
    """The program's router against the reference's on seeded unit-variance
    rows: relative L2 error of the renormalised top-k weights scattered over
    all experts (an expert picked on one side only counts in full)."""
    from sparknet_tpu.ops import moe

    config, spec = cell.config, cell.config["check"]
    ref, _ = reference_of(cell, plants)
    rows = spec.get("router_rows", 8192)
    x = jax.random.normal(jax.random.fold_in(jax.random.key(cell.seed), 2),
                          (rows, config["hidden_size"]), jnp.float32)
    experts = config["num_experts"]

    def dense(weights, ids):
        return jnp.zeros((rows, experts), jnp.float32).at[
            jnp.arange(rows)[:, None], ids].set(weights)

    def both(stacked, x):
        w = stacked["l0_router"][0][0]
        xs, ws = x, w
        if "bfloat16_router" in plants:
            xs, ws = rounded_to_bfloat16(x), rounded_to_bfloat16(w)
        got = dense(*moe.route(xs, ws, config["num_experts_per_tok"]))
        with jax.default_matmul_precision("highest"):
            want = dense(*ref.route(x, w, config))
        return got, want

    got, want = jax.jit(both)(cell.state.params, x)
    return rel_err(got, want)


def delta_rule_against_recurrence(cell, plants):
    """The program's chunked rule against the reference's token-by-token
    recurrence, on seeded inputs shaped as a DeltaNet layer makes them (unit
    keys, scaled unit queries, decays ``-A softplus(.)``): in float32 under
    ``highest`` (summation order: this is where a decay or a state in a lower
    precision shows) and in the configuration's compute dtype (its operands'
    rounding hides both; the band guards the algebra on the timed path).
    Returns the two relative L2 errors."""
    from sparknet_tpu.ops.delta_rule import gated_delta_rule

    config, spec = cell.config, cell.config["check"]
    ref, _ = reference_of(cell, plants)
    b, t, h, d = spec.get("delta_rule_shape", (
        1, spec["seq_len"], config["linear_num_value_heads"],
        config["linear_value_head_dim"]))
    keys = jax.random.split(
        jax.random.fold_in(jax.random.key(cell.seed), 3), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, d)))
    v = jax.random.normal(keys[2], (b, t, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (b, t, h)))
    # A log-uniform over (1e-3, 16): half the heads forget within a token (as
    # the initialiser's U(0, 16) makes nearly all of them), half keep a
    # memory of hundreds to thousands of tokens, where the state's and the
    # decay's precision show
    a = jnp.exp(jax.random.uniform(
        keys[4], (h,), minval=jnp.log(1e-3), maxval=jnp.log(16.0)))
    g = -a * jax.nn.softplus(jax.random.normal(keys[5], (b, t, h)) + 1.0)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.delta_rule_recurrent)(q, k, v, g, beta)
        if "bfloat16_state" in plants:
            exact = stated = jax.jit(lambda *xs: ref.delta_rule_recurrent(
                *xs, state_dtype=jnp.bfloat16))(q, k, v, g, beta)
        else:
            exact = jax.jit(gated_delta_rule)(q, k, v, g, beta)
    if "bfloat16_state" not in plants:
        stated = jax.jit(lambda *xs: gated_delta_rule(
            *xs, compute_dtype=jnp.dtype(config["compute_dtype"])))(
                q, k, v, g, beta)
    return rel_err(exact, want), rel_err(stated, want)


def float32_parts(cell, plants):
    """What must stay float32 in the stated dtype's path: the router and the
    delta rule's decay and state."""
    spec = cell.config["check"]
    t0 = time.perf_counter()
    router = router_against_reference(cell, plants)
    exact, stated = delta_rule_against_recurrence(cell, plants)
    cell.log(f"router against the reference's, relative L2 error {router:.3g} "
             f"(bound {spec['router_rel_tol']}); chunked delta rule against "
             f"the recurrence: float32/highest {exact:.3g} (bound "
             f"{spec['delta_rule_exact_rel_tol']}), "
             f"{cell.config['compute_dtype']} {stated:.3g} (bound "
             f"{spec['delta_rule_rel_tol']}); "
             f"{time.perf_counter() - t0:.1f} s")
    return {
        "router_in_float32": router <= spec["router_rel_tol"],
        "delta_rule_exact": exact <= spec["delta_rule_exact_rel_tol"],
        "delta_rule_in_band": stated <= spec["delta_rule_rel_tol"],
    }


def routing(cell, tokens, when):
    """Where one step's tokens go, per layer (the program's gauges, set by
    ``apps/lm_app.set_routing_gauges``), against the expectation and against
    the rows the grouped expert path holds."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.ops import moe

    config = cell.config
    gauges = lm_app.set_routing_gauges(cell.model, cell.state.params, tokens)
    held = config["experts_held"][1]
    expected = config["num_experts_per_tok"] * held / config["num_experts"]
    rows = moe.fast_rows_for(
        int(tokens.size), config["num_experts_per_tok"],
        config["num_experts"], held)
    most = max(gauges["held_assignments_per_token"]) * tokens.size
    cell.log(f"routing of one step's {tokens.size} tokens {when}, by layer: "
             f"{ {k: [round(float(x), 4) for x in v] for k, v in gauges.items()} }"
             f"; expected {expected} assignments a token; the grouped path "
             f"holds {rows} rows, the fullest layer sends {most:.0f}"
             + ("" if most <= rows else
                ": OVER, that layer runs in token chunks"))
    return gauges


PARTS = {"forward": forward_against_reference, "step": step_against_reference,
         "float32": float32_parts}


def main(argv=None):
    """``python3 -m benchmark.lm_checks --workload <cell> --seed <n> [--plant
    a,b [--parts step]] ...``: the cell's comparisons alone, on the chip,
    once for every ``--plant`` group (none: once, unplanted), without the
    partition, the rounds and the window (``--rehearse``: on the CPU at the
    files' tiny sizes).  For PERF.md's second readings: a
    planted group has to print a verdict with a ``False`` in it."""
    import argparse
    import json

    from benchmark import files
    from sparknet_tpu.utils import devices as device_policy

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--plant", action="append", default=None,
                    help="a comma-separated group of PLANTS; [:parts] after "
                    "it limits the group to those of " + ",".join(PARTS))
    args = ap.parse_args(argv)
    work, config, traffic = files.cell(args.workload, args.rehearse)
    if args.rehearse:
        device_policy.force_virtual_cpu_devices(work["chips"])
    else:
        device_policy.enable_compile_cache()
    kind = importlib.import_module(
        "benchmark.kinds." + traffic["kind"].replace("-", "_"))
    cell = kind.Cell(work, config, traffic, args.seed,
                     lambda m: print(f"[bench] {m}", flush=True))
    for group in args.plant or [""]:
        group, _, parts = group.partition(":")
        os.environ["LM_CHECK_PLANT"] = group
        plants, verdict = planted(cell), {}
        for part in parts.split(",") if parts else PARTS:
            verdict.update(PARTS[part](cell, plants))
        print(json.dumps({"planted": sorted(plants), "verdict": verdict,
                          "correct": all(verdict.values())}), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
