"""The comparisons of an ``lm-train-resident-lfm2`` cell that
``lm_checks.py`` does not have: what must stay float32 in the stated
dtype's path of the LFM2 model.

- ``router_against_reference``: the program's router as the model describes
  it (``ops/moe.route``: sigmoid scores, top-k on ``scores + expert_bias``,
  weights gathered from the unbiased scores, renormalised) against the
  reference's, on seeded rows with the first routed layer's own weight and
  a seeded bias (the state's starts at zero): selection and weights
  together.
- ``short_conv_against_taps``: the program's gated short convolution
  (``ops/short_conv.gated_short_conv``) against the reference's tap loop at
  ``check.seq_len``, in float32 (summation order only) and on operands
  rounded to the stated dtype (a band).

- ``routing`` / ``held_load_in_window``: where the tokens go with the
  selection biases the state carries (``lm_checks.routing`` reads the
  parameters alone), and what the window's last step sent the held experts.

The forward, step and ``step_exact`` comparisons are ``lm_checks``' own: they
run before the first round, when every selection bias is still zero, which
is what a forward pass without ``stats`` selects on.
``LM_CHECK_PLANT`` takes ``lm_checks.PLANTS`` and the four of ``PLANTS``
here; such a run has to print ``correct: false``.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import lm_checks
from benchmark.checks import rel_err

PLANTS = {
    "biased_weights": "the router's weights are gathered from the BIASED "
    "scores (scores + expert_bias) in place of the unbiased ones",
    "dropped_tap": "the short convolution loses its tap on the current token",
    "swapped_gates": "the short convolution's two gates change places",
    "bfloat16_conv": "the short convolution's operands are rounded to "
    "bfloat16 in the float32 comparison",
}
# with those of ``lm_checks.PLANTS`` that mean something in this cell
ALL_PLANTS = {
    **{k: lm_checks.PLANTS[k] for k in (
        "state_unchanged", "half_batch", "bfloat16_update",
        "float8_reference", "bfloat16_router")},
    **PLANTS,
}


def planted(cell):
    plants = ALL_PLANTS
    names = [n for n in os.environ.get("LM_CHECK_PLANT", "").split(",") if n]
    unknown = sorted(set(names) - set(plants))
    if unknown:
        raise SystemExit(f"LM_CHECK_PLANT: unknown {unknown}; {sorted(plants)}")
    for n in names:
        cell.log(f"PLANTED FAULT {n}: {plants[n]}; this run is not correct")
    return set(names)


def router_against_reference(cell, plants):
    """Relative L2 error of the top-k weights scattered over all experts (an
    expert picked on one side only counts in full), the share of rows whose
    selection is the reference's, and the share of rows in which the bias
    changes the selection (it has to be in play)."""
    from sparknet_tpu.ops import moe

    config, spec = cell.config, cell.config["check"]
    ref, _ = lm_checks.reference_of(cell, plants)
    described = cell.model.config
    rows = spec.get("router_rows", 8192)
    x = jax.random.normal(jax.random.fold_in(jax.random.key(cell.seed), 2),
                          (rows, config["hidden_size"]), jnp.float32)
    experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    group = f"l{cell.model.routed_layers[0]}_router"
    # the size the rule gives a bias within ten steps of the cell's rate
    bias = spec["router_bias_std"] * jax.random.normal(
        jax.random.fold_in(jax.random.key(cell.seed), 4), (experts,),
        jnp.float32)

    def dense(weights, ids):
        return jnp.zeros((rows, experts), jnp.float32).at[
            jnp.arange(rows)[:, None], ids].set(weights)

    def both(stacked, x):
        w = stacked[group][0][0]
        xs, ws = x, w
        if "bfloat16_router" in plants:
            xs, ws = (lm_checks.rounded_to_bfloat16(a) for a in (x, w))
        weights, ids = moe.route(
            xs, ws, top_k, scores=described["router_scores"], bias=bias,
            scale=described["routed_scaling_factor"],
            eps=described["topk_eps"])
        with jax.default_matmul_precision("highest"):
            want_w, want_ids, scores = ref.route(x, w, config, bias)
        if "biased_weights" in plants:
            weights = jnp.take_along_axis(scores + bias, ids, axis=-1)
            weights = weights / (
                jnp.sum(weights, -1, keepdims=True) + described["topk_eps"])
        same = jnp.all(jnp.sort(ids, -1) == jnp.sort(want_ids, -1), axis=-1)
        _, unbiased = jax.lax.top_k(scores, top_k)
        moved = jnp.any(
            jnp.sort(unbiased, -1) != jnp.sort(want_ids, -1), axis=-1)
        return (dense(weights, ids), dense(want_w, want_ids),
                jnp.mean(same), jnp.mean(moved))

    got, want, same, moved = jax.jit(both)(cell.state.params, x)
    return rel_err(got, want), float(same), float(moved)


def short_conv_against_taps(cell, plants):
    """The program's ``C * conv(B * u)`` against the reference's, one tap at
    a time, on seeded unit-variance ``[B | C | u]`` and the first conv
    layer's own taps: in float32 and on operands rounded to the
    configuration's compute dtype (as the layer's ``in_proj`` hands them
    over).  Returns the two relative L2 errors."""
    from sparknet_tpu.ops.short_conv import gated_short_conv

    config, spec = cell.config, cell.config["check"]
    ref, _ = lm_checks.reference_of(cell, plants)
    b, t, e = spec.get("short_conv_shape",
                       (1, spec["seq_len"], config["hidden_size"]))
    bcu = jax.random.normal(jax.random.fold_in(jax.random.key(cell.seed), 3),
                            (b, t, 3 * e), jnp.float32)
    layer = config["layer_types"].index("conv")
    cd = jnp.dtype(config["compute_dtype"])

    def both(stacked, bcu):
        w = stacked[f"l{layer}_mixer"][1][0]
        want = ref.gated_conv_core(bcu, w)
        given, taps = bcu, w
        if "dropped_tap" in plants:
            taps = w.at[:, -1].set(0.0)
        if "swapped_gates" in plants:
            given = jnp.concatenate(
                [bcu[..., e:2 * e], bcu[..., :e], bcu[..., 2 * e:]], axis=-1)
        exact = gated_short_conv(
            lm_checks.rounded_to_bfloat16(given)
            if "bfloat16_conv" in plants else given, taps)
        stated = gated_short_conv(given.astype(cd), taps)
        return exact, stated, want

    with jax.default_matmul_precision("highest"):
        exact, stated, want = jax.jit(both)(cell.state.params, bcu)
    return rel_err(exact, want), rel_err(stated, want)


def float32_parts(cell, plants):
    spec = cell.config["check"]
    t0 = time.perf_counter()
    router, same, moved = router_against_reference(cell, plants)
    exact, stated = short_conv_against_taps(cell, plants)
    cell.log(f"router against the reference's, relative L2 error {router:.3g} "
             f"(bound {spec['router_rel_tol']}), the same selection in "
             f"{same:.4%} of the rows, the bias changes the selection of "
             f"{moved:.2%} of them; gated short convolution against the tap "
             f"loop: float32 {exact:.3g} (bound "
             f"{spec['short_conv_exact_rel_tol']}), "
             f"{cell.config['compute_dtype']} operands {stated:.3g} (bound "
             f"{spec['short_conv_rel_tol']}); "
             f"{time.perf_counter() - t0:.1f} s")
    return {
        "router_in_float32": router <= spec["router_rel_tol"] and moved > 0,
        "short_conv_exact": exact <= spec["short_conv_exact_rel_tol"],
        "short_conv_in_band": stated <= spec["short_conv_rel_tol"],
    }


def routing(cell, tokens, when):
    """``lm_checks.routing`` with the selection biases the state carries:
    where one step's tokens go, per routed layer, against the expectation
    and the rows of the grouped expert path; and how far the rule has moved
    the held experts' biases from the others'."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.ops import moe

    config = cell.config
    gauges = lm_app.set_routing_gauges(
        cell.model, cell.state.params, tokens, cell.state.stats)
    lo, held = config["experts_held"]
    expected = config["num_experts_per_tok"] * held / config["num_experts"]
    rows = moe.fast_rows_for(
        int(tokens.size), config["num_experts_per_tok"],
        config["num_experts"], held)
    most = max(gauges["held_assignments_per_token"]) * tokens.size
    lead = []
    for group in cell.model.biased_routers:
        bias = np.asarray(cell.state.stats[group][0])[0]
        others = np.delete(bias, np.arange(lo, lo + held))
        lead.append(round(float(bias[lo:lo + held].mean() - others.mean()), 4))
    cell.log(f"routing of one step's {tokens.size} tokens {when}, by layer: "
             f"{ {k: [round(float(x), 4) for x in v] for k, v in gauges.items()} }"
             f"; expected {expected} assignments a token; the held experts' "
             f"mean selection bias over the others' {lead}; the grouped path "
             f"holds {rows} rows, the fullest layer sends {most:.0f}"
             + ("" if most <= rows else
                ": OVER, that layer runs in token chunks"))
    return gauges


def held_load_in_window(cell):
    """What the last training step sent the held experts, per routed layer,
    from the loads that step left in the state (``stats``, worker 0): after
    the window, its last step.  Assignments a token, as a share of the
    expectation ``top_k * held / experts``; the verdict needs every layer
    inside ``check.held_load_band``."""
    config, spec = cell.config, cell.config["check"]
    lo, held = config["experts_held"]
    top_k = config["num_experts_per_tok"]
    expected = top_k * held / config["num_experts"]
    per_token = []
    for group in cell.model.biased_routers:
        load = np.asarray(cell.state.stats[group][1])[0]
        per_token.append(
            float(top_k * load[lo:lo + held].sum() / max(load.sum(), 1.0)))
    low, high = spec["held_load_band"]
    ok = bool(per_token) and all(
        low * expected <= x <= high * expected for x in per_token)
    cell.log(f"the window's last step sent the held experts "
             f"{[round(x, 4) for x in per_token]} assignments a token, by "
             f"layer; expected {expected}, band {low} to {high} times it: "
             f"{'ok' if ok else 'OUT'}")
    return {"held_load_in_window": ok}


PARTS = {**lm_checks.PARTS, "float32": float32_parts}


def main(argv=None):
    """``python3 -m benchmark.lfm2_checks --workload <cell> --seed <n>
    [--plant a,b[:parts]] ...``: ``lm_checks.main`` with this cell's plants
    and parts: the comparisons alone, once for every ``--plant`` group
    (none: once, unplanted), without the partition, the rounds and the
    window (``--rehearse``: on the CPU at the files' tiny sizes).  A planted
    group has to print a verdict with a ``false`` in it."""
    import argparse
    import importlib
    import json

    from benchmark import files
    from sparknet_tpu.utils import devices as device_policy

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--plant", action="append", default=None,
                    help="a comma-separated group of plants; [:parts] after "
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
