"""The comparisons of an ``lm-train-resident-laguna`` cell: ``lm_checks``'
forward, step and ``step_exact`` against ``benchmark/reference/laguna.py``,
LFM2's router comparison, held-load verdict and routing lines
(``lfm2_checks``: the router is the same kind, a sigmoid with a selection
bias; here with the scaling factor 2.5 and 1e-20), all on the cell's own
objects.

What is this file's own:

- ``window_kernels``: the windowed attention on the timed path
  (``ops/attention.causal_gqa_attention(.., window=)``: the band's flash
  kernels on the TPU) against the reference's masked softmax
  (``reference/laguna.attention_core``, full rows with the window as a mask)
  at the timed shapes of a sliding layer, on seeded unit-variance inputs:
  the output and the three gradients, in float32 at the highest precision
  (summation order only: one key of the window more or less shows) and in
  the stated bfloat16 (a band).
- the planted faults of the attention, its positions and this family's
  feed-forward (``PLANTS``), each planted in the REFERENCE as
  ``kanana_checks`` plants its own: ``planted_reference`` makes a copy of
  the reference's module with one of its small functions replaced, under a
  name of its own, and the comparison, which is symmetric, has to read the
  program, which has none of them, as not correct.  ``LM_CHECK_PLANT``
  takes those and the six of ``lm_checks`` / ``lfm2_checks`` that mean
  something here.
"""

import importlib
import importlib.util
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import lfm2_checks, lm_checks

PLANTS = {
    "window_511": "the reference's sliding layers see one key fewer than "
    "sliding_window (511 of 512)",
    "window_513": "the reference's sliding layers see one key more than "
    "sliding_window (513 of 512)",
    "full_causal_sliding": "the reference's sliding layers see every key "
    "before the query (full causal attention)",
    "plain_rotary_full": "the reference's full layers turn by plain rotary "
    "frequencies in place of YaRN's (no attention factor either)",
    "whole_head_rotary_full": "the reference's full layers turn the whole "
    "head by YaRN in place of its first half",
    "per_head_scalar_gate": "the reference gates each head by one scalar, "
    "sigmoid of its gate's first column",
    "full_heads_sliding": "the reference's sliding layers use as many query "
    "heads as the full layers (the first of their heads)",
    "no_routed_scaling": "the reference's routed weights lose "
    "moe_routed_scaling_factor",
    "bfloat16_reference": "the reference's products take bfloat16 operands "
    "(the nearest precision below float32: the exact comparisons' own)",
}
# with those of the accepted cells' that mean something in this one
ALL_PLANTS = {
    **{k: lm_checks.PLANTS[k] for k in (
        "state_unchanged", "half_batch", "bfloat16_update",
        "float8_reference", "bfloat16_router")},
    "biased_weights": lfm2_checks.PLANTS["biased_weights"],
    **PLANTS,
}
PLANTED_NAME = "laguna_planted"


def planted(cell):
    names = [n for n in os.environ.get("LM_CHECK_PLANT", "").split(",") if n]
    unknown = sorted(set(names) - set(ALL_PLANTS))
    if unknown:
        raise SystemExit(
            f"LM_CHECK_PLANT: unknown {unknown}; {sorted(ALL_PLANTS)}")
    for n in names:
        cell.log(f"PLANTED FAULT {n}: {ALL_PLANTS[n]}; this run is not correct")
    return set(names)


def planted_reference(plants):
    """``benchmark.reference.laguna`` itself where no fault of ``PLANTS`` is
    planted; else a second copy of that module, importable as
    ``benchmark.reference.laguna_planted``, with the faults in it."""
    from benchmark.reference import laguna

    if not plants & set(PLANTS):
        return laguna
    spec = importlib.util.spec_from_file_location(
        "benchmark.reference." + PLANTED_NAME, laguna.__file__)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    for n, more in (("window_511", -1), ("window_513", 1),
                    ("full_causal_sliding", None)):
        if n in plants:  # one key fewer, one more, or every key before
            ref.window_of = lambda config, kind, more=more: (
                laguna.window_of(config, kind) + more
                if more and laguna.window_of(config, kind) else None)
    if "plain_rotary_full" in plants:
        ref.rope_of = lambda config, kind: laguna.rope_of(config, kind)[:2] + (
            None,)
    if "whole_head_rotary_full" in plants:
        def whole_head(config, kind):
            theta, dim, yarn = laguna.rope_of(config, kind)
            if yarn is None:
                return theta, dim, yarn
            p, d = config["rope_parameters"][kind], config["head_dim"]
            return theta, d, (laguna.yarn_frequencies(
                theta, d, p["factor"], p["original_max_position_embeddings"],
                p["beta_fast"], p["beta_slow"]), yarn[1])
        ref.rope_of = whole_head
    if "per_head_scalar_gate" in plants:
        ref.output_gate = lambda attn, gate: attn * jax.nn.sigmoid(
            gate[..., :1])
    if "full_heads_sliding" in plants:
        ref.heads_of = lambda config, i: min(
            config["num_attention_heads_per_layer"])
    if "no_routed_scaling" in plants:
        ref.route = lambda x, w, config, bias=None: laguna.route(
            x, w, {**config, "moe_routed_scaling_factor": 1.0}, bias)
    if "bfloat16_reference" in plants:
        ref.logits = lambda p, t, c, operand_dtype=None, **kw: laguna.logits(
            p, t, c, operand_dtype or jnp.bfloat16, **kw)
    sys.modules[spec.name] = ref
    return ref


def view(cell, plants=frozenset()):
    """The cell as the accepted comparisons are to see it: the same objects,
    the reference's planted copy where a fault of ``PLANTS`` is planted."""
    seen = types.SimpleNamespace(**vars(cell))
    if set(plants) & set(PLANTS):
        planted_reference(set(plants))
        seen.config = {**cell.config, "reference": PLANTED_NAME}
    return seen


def forward(cell, plants):
    return lm_checks.forward_against_reference(view(cell, plants), plants)


def step(cell, plants):
    return lm_checks.step_against_reference(view(cell, plants), plants)


def window_kernels(cell, plants):
    """``causal_gqa_attention`` with the sliding layers' window, as a
    sliding layer calls it, against ``reference/laguna.attention_core`` on
    seeded ``(B, T, H, D)`` queries and ``(B, T, Hkv, D)`` keys and values
    (``check.window_shape``; the timed ones by default): relative L2 errors
    of the output and of ``dq``, ``dk``, ``dv`` under one seeded cotangent,
    in float32 at the highest precision and in the compute dtype."""
    from sparknet_tpu.ops.attention import causal_gqa_attention

    config, spec = cell.config, cell.config["check"]
    ref = planted_reference(set(plants))
    sliding = [i for i, t in enumerate(config["layer_types"])
               if t == "sliding_attention"]
    b, t, h, hkv, d = spec.get("window_shape", (
        cell.batch, cell.seq_len, config["num_attention_heads_per_layer"][
            sliding[0]], config["num_key_value_heads"], config["head_dim"]))
    keys = jax.random.split(jax.random.fold_in(jax.random.key(cell.seed), 5), 4)
    q = jax.random.normal(keys[0], (b, t, h, d), jnp.float32)
    k, v = (jax.random.normal(key, (b, t, hkv, d), jnp.float32)
            for key in keys[1:3])
    ct = jax.random.normal(keys[3], (b, t, h, d), jnp.float32)
    window = config["sliding_window"]
    ref_window = ref.window_of(config, "sliding_attention")

    def with_grads(fn, ct, *xs):
        out, vjp = jax.vjp(fn, *xs)
        return (out, *vjp(ct))

    def reference(q, k, v, ct):
        if "bfloat16_reference" in plants:
            q, k, v = (lm_checks.rounded_to_bfloat16(a) for a in (q, k, v))
        with jax.default_matmul_precision("highest"):
            return with_grads(lambda *a: ref.attention_core(*a, ref_window),
                              ct, q, k, v)

    def program(dtype, q, k, v, ct):
        return with_grads(lambda *a: causal_gqa_attention(
            *a, compute_dtype=dtype, window=window), ct, q, k, v)

    def compared(q, k, v, ct):
        """``(|got - want|^2, |want|^2)`` of each output, the float32 path
        then the stated one: ONE program, whose outputs are its temporaries,
        so that beside the training state the chip holds its inputs alone"""
        want = reference(q, k, v, ct)
        with jax.default_matmul_precision("highest"):
            exact = program(None, q, k, v, ct)
        stated = program(jnp.dtype(config["compute_dtype"]), q, k, v, ct)
        return [[(lm_checks.sq(g - w), lm_checks.sq(w))
                 for g, w in zip(got, want)] for got in (exact, stated)]

    t0 = time.perf_counter()
    names = ("out", "dq", "dk", "dv")
    sums = jax.device_get(jax.jit(compared)(q, k, v, ct))
    errors = {label: {n: float(np.sqrt(d / max(w, 1e-30)))
                      for n, (d, w) in zip(names, pairs)}
              for label, pairs in zip(("float32", "stated"), sums)}
    cell.log(f"windowed attention (window {window}) at B {b}, T {t}, {h} / "
             f"{hkv} heads of {d} against the reference's masked softmax "
             f"(window {ref_window}), relative L2 error of the output and "
             f"the three gradients: float32/highest {errors['float32']}, "
             f"{config['compute_dtype']} {errors['stated']}; "
             f"{time.perf_counter() - t0:.1f} s")
    return {
        "window_kernels_exact": lm_checks.within(
            cell, errors["float32"], spec["window_exact_rel_tol"]),
        "window_kernels_in_band": lm_checks.within(
            cell, errors["stated"], spec["window_rel_tol"]),
    }


def router(cell, plants):
    """The router alone, as the model describes it (sigmoid in float32,
    top-8 on ``scores + bias``, ``w / (sum + 1e-20)``, times 2.5), against
    the reference's, a seeded bias in play."""
    spec = cell.config["check"]
    t0 = time.perf_counter()
    error, same, moved = lfm2_checks.router_against_reference(
        view(cell, plants), plants)
    cell.log(f"router against the reference's, relative L2 error {error:.3g} "
             f"(bound {spec['router_rel_tol']}), the same selection in "
             f"{same:.4%} of the rows, the bias changes the selection of "
             f"{moved:.2%} of them; {time.perf_counter() - t0:.1f} s")
    return {"router_in_float32": error <= spec["router_rel_tol"] and moved > 0}


PARTS = {"forward": forward, "step": step, "float32": router,
         "window": window_kernels}


def main(argv=None):
    """``python3 -m benchmark.laguna_checks --workload <cell> --seed <n>
    [--plant a,b[:parts]] ...``: the cell's comparisons alone, once for
    every ``--plant`` group (none: once, unplanted), without the partition,
    the rounds and the window (``--rehearse``: on the CPU at the files' tiny
    sizes).  A planted group has to print a verdict with a ``false`` in
    it."""
    import argparse
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
    sys.exit(main())
