"""The comparisons of an ``lm-train-resident-keye`` cell that ``lm_checks.py``
does not have: the learned selection of the sparse attention, the attention
over it, and the separation of the two losses.  All at T >= 2 x ``topk``: at
a length under ``topk`` nothing is dropped and a dense attention would pass.

- ``selection_against_reference``: the program's indexer and selection
  (``HybridMoELM._dsa_select``) on the first layer's own input at
  ``check.seq_len``, against the reference's index scores and ``lax.top_k``:
  the share of the program's selected pairs that the float32 reference also
  selects, in float32 (equality up to near-ties) and in the stated dtype (a
  band), and the number selected, which is exact.
- ``sparse_attention_exact``: the masked attention and the alignment loss in
  float32, GIVEN the reference's selection, against the reference's.
- ``indexer_learns``: at ``check.step_seq_len``, the gradient of the step's
  loss on every leaf of the indexer is nonzero and is the alignment loss's
  (against ``jax.grad`` of the reference's ``L_I``), and the language-model
  loss's gradient on them is exactly zero.
- ``held_load``: the assignments a token the held experts receive, after the
  window, every layer against ``check.held_load_band``.

The forward, step and ``step_exact`` comparisons and the router's are
``lm_checks``' own; the step runs on ``step_view(cell)``, whose reference is
``keye_vl2_step`` (the two losses as one gradient).  ``LM_CHECK_PLANT`` takes
the four of ``lm_checks.PLANTS`` that mean something here and the six of
``PLANTS``; such a run has to print ``correct: false``.
"""

import os
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import lm_checks
from benchmark.checks import rel_err

PLANTS = {
    "dense_attention": "the attention is given every causal key in place of "
    "the selection",
    "half_topk": "the program selects topk / 2 keys a query",
    "unweighted_heads": "the reference sums its index heads unweighted (w = 1)",
    "no_relu": "the reference's index scores lose their ReLU",
    "lm_loss_reaches_indexer": "a thousandth of the alignment loss is added "
    "to the language-model loss, whose gradient then reaches the indexer",
    "no_alignment_loss": "the step's loss is the language-model loss alone",
}
# with those of ``lm_checks.PLANTS`` that mean something in this cell (a step
# is one sequence: there is no half of a batch to drop)
ALL_PLANTS = {
    **{k: lm_checks.PLANTS[k] for k in (
        "state_unchanged", "bfloat16_update", "float8_reference",
        "bfloat16_router")},
    **PLANTS,
}


def planted(cell):
    names = [n for n in os.environ.get("LM_CHECK_PLANT", "").split(",") if n]
    unknown = sorted(set(names) - set(ALL_PLANTS))
    if unknown:
        raise SystemExit(
            f"LM_CHECK_PLANT: unknown {unknown}; {sorted(ALL_PLANTS)}")
    for n in names:
        cell.log(f"PLANTED FAULT {n}: {ALL_PLANTS[n]}; this run is not correct")
    return set(names)


class Ahead:
    """``build()`` on a thread of its own, begun now or when the one
    ``after`` it has ended: jax's compile releases the interpreter, so this
    cell's own programs (and its round, ``ParameterAveragingTrainer.
    compile_round``) compile, one after another, while the accepted
    comparisons compile and run theirs.  ``result()`` waits for it."""

    def __init__(self, build, after=None):
        self.value = self.error = None
        self.seconds = 0.0

        def work():
            if after is not None:
                after.thread.join()
            t0 = time.perf_counter()
            try:
                self.value = build()
            except BaseException as e:  # handed to the caller's thread
                self.error = e
            self.seconds = time.perf_counter() - t0

        self.thread = threading.Thread(target=work, daemon=True)
        self.thread.start()

    def result(self):
        self.thread.join()
        if self.error is not None:
            raise self.error
        return self.value


def run_compiled(cell, what, ahead, *args):
    """``lm_checks.timed`` for a program compiled ahead."""
    t0 = time.perf_counter()
    compiled = ahead.result()
    waited = time.perf_counter() - t0
    out = lm_checks.timed(cell, what, compiled, *args)
    cell.log(f"'{what}' compiled in {ahead.seconds:.1f} s on a thread of its "
             f"own, awaited for {waited:.1f} s")
    return out


def step_view(cell):
    """The cell as ``lm_checks.step_against_reference`` is to see it: the
    same objects, the reference that carries both losses."""
    view = types.SimpleNamespace(**vars(cell))
    view.config = {**cell.config, "reference": cell.config["reference"] + "_step"}
    return view


def exact_model(cell):
    """A second object of the model's class, float32 throughout."""
    return type(cell.model)({**cell.config, "compute_dtype": None})


def first_layer_input(cell, params, tokens, ref):
    """``RMSNorm(embed[tokens]; n1)`` of layer 0, float32: what the first
    layer's indexer and attention read."""
    x = params["embed"][0][tokens]
    return ref.rms_norm(x, params["l0_n1"][0], cell.config["rms_norm_eps"])


def reference_rows(cell, ref, u, blobs, plants, fn):
    """``fn(first, keep (B, Q, T), planted keep)`` over the reference's
    selection, a block of queries at a time (its scores a full row each,
    ``lax.top_k``); the second selection is the first unless a fault is
    planted in the reference's indexer."""
    config = cell.config
    topk = config["sa_config"]["topk"]
    t, block = u.shape[1], min(ref.QUERY_BLOCK, u.shape[1])
    assert t % block == 0, (t, block)
    faulty = bool({"unweighted_heads", "no_relu"} & plants)
    qi, ki, w = ref.indexer(u, blobs, config)
    _, _, w_planted = ref.indexer(
        u, blobs, config, weighted="unweighted_heads" not in plants)

    def one(xs):
        first, qb, wb, wp = xs
        keep = ref.selection(ref.index_scores(qb, wb, ki), first, topk)
        if not faulty:
            return fn(first, keep, keep)
        scores = ref.index_scores(qb, wp, ki, relu="no_relu" not in plants)
        return fn(first, keep, ref.selection(scores, first, topk))

    cut = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape(x.shape[0], -1, block, *x.shape[2:]), 1, 0)
    return jax.lax.map(
        one, (jnp.arange(0, t, block), cut(qi), cut(w), cut(w_planted)))


def compile_ahead(program, args, after=None):
    return Ahead(lambda: program.lower(*args).compile(), after)


def ahead_of(cell, plants, name, builder):
    """``(Ahead, args)`` of the part's program: the one ``start`` began, or
    one begun here."""
    started = getattr(cell, "ahead", {}).pop(name, None)
    if started is not None:
        return started
    program, args = builder(cell, plants)
    return compile_ahead(program, args), args


def start(cell, plants, batch):
    """Begin compiling, ONE at a time beside the caller's own compiles and in
    the order they are needed, this cell's two check programs, the probe
    ``selection_gauges`` runs (one forward pass for the routing's and the
    indexer's gauges, compiled once for every call) and the trainer's round
    for batches like ``batch``.  (All four at once, on the chip's 13 cores,
    slowed every compile by half and the accepted checks' with them: a
    set-up of 603 s where one after another compiles 747, PERF.md section
    6.)"""
    from sparknet_tpu.apps import lm_app

    cell.ahead, last = {}, None
    for name, builder in (("selection", selection_program),
                          ("indexer", indexer_program)):
        program, args = builder(cell, plants)
        last = compile_ahead(program, args, last)
        cell.ahead[name] = last, args
    probe_args = (cell.state.params, cell.state.stats or {},
                  batch["tokens"][0, 0])
    cell.probe = compile_ahead(
        lm_app.selection_probe(cell.model), probe_args, last)
    cell.round_ahead = Ahead(
        lambda: cell.trainer.compile_round(cell.state, batch), cell.probe)


def selection_program(cell, plants):
    """The program behind ``selection_against_reference`` and
    ``sparse_attention_exact`` on one sequence of ``check.seq_len`` tokens,
    and its arguments: ``(jitted, args)``."""
    from sparknet_tpu.ops import sparse_attention as sa

    config, spec = cell.config, cell.config["check"]
    ref, _ = lm_checks.reference_of(cell, plants)
    t = spec["seq_len"]
    tokens = lm_checks.zipf_tokens(
        jax.random.fold_in(jax.random.key(cell.seed), 5), (1, t),
        config["vocab_size"], cell.traffic["zipf_exponent"])
    stated, exact = cell.model, exact_model(cell)
    if "half_topk" in plants:
        halved = {**config, "sa_config": {
            **config["sa_config"], "topk": config["sa_config"]["topk"] // 2}}
        stated = type(cell.model)(halved)
        exact = type(cell.model)({**halved, "compute_dtype": None})
    cd = jnp.dtype(config["compute_dtype"])
    block = min(ref.QUERY_BLOCK, t)
    words = sa.words_of(t)

    def program(stacked, tokens):
        params = lm_checks.worker0(stacked)
        blobs = params["l0_mixer"]
        u = first_layer_input(cell, params, tokens, ref)
        bits = {"stated": stated._dsa_select(0, u.astype(cd), blobs[6:])[3],
                "exact": exact._dsa_select(0, u, blobs[6:])[3]}

        def counts(first, clean, keep):
            rows = lambda b: sa.unpack_mask(  # noqa: E731
                jax.lax.dynamic_slice_in_dim(b, first, block, 1), t)
            out = {"reference": jnp.sum(keep), "bits": sa.pack_mask(clean, words)}
            for name, b in bits.items():
                mine = rows(b)
                out[name] = jnp.sum(mine)
                out[name + "_shared"] = jnp.sum(mine & keep)
            return out

        found = reference_rows(cell, ref, u, blobs, plants, counts)
        given = jnp.moveaxis(found.pop("bits"), 0, 1).reshape(1, t, words)
        sums = {k: jnp.sum(v) for k, v in found.items()}
        # the attention and the alignment loss, float32, on the REFERENCE's
        # selection
        want_out, want_align = ref.sparse_attention(u, blobs, config)
        if "dense_attention" in plants:
            given = sa.causal_mask_bits(1, t)
        got_out, q, k, lse = exact._dsa_attention(u, blobs[:6], given)
        qi, ki, w = exact._dsa_indexer(u, blobs[6:])
        got_align = sa.alignment_loss(
            qi, w, ki, q, k, lse, given, block_q=exact.config["index_block"]) / t
        return sums, (got_out, want_out), (got_align, want_align)

    def at_highest(stacked, tokens):  # inside: a thread has its own context
        with jax.default_matmul_precision("highest"):
            return program(stacked, tokens)

    return jax.jit(at_highest), (cell.state.params, tokens)


def selection_and_attention(cell, plants):
    """The readings of ``selection_against_reference`` and
    ``sparse_attention_exact``."""
    ahead, args = ahead_of(cell, plants, "selection", selection_program)
    sums, outs, aligns = run_compiled(
        cell, "selection and attention over it", ahead, *args)
    sums = {k: int(v) for k, v in sums.items()}
    return {
        "selected": {k: sums[k] for k in ("reference", "stated", "exact")},
        "stated_share": sums["stated_shared"] / max(sums["stated"], 1),
        "exact_share": sums["exact_shared"] / max(sums["exact"], 1),
        "attention": rel_err(*outs),
        "alignment": abs(float(aligns[0]) - float(aligns[1]))
        / abs(float(aligns[1])),
        "alignment_loss": float(aligns[1]),
    }


def selection_parts(cell, plants):
    spec = cell.config["check"]
    r = selection_and_attention(cell, plants)
    n = r["selected"]
    cell.log(f"selection at T = {spec['seq_len']} against the reference's "
             f"(float32 scores, lax.top_k a row): pairs selected {n}; of the "
             f"program's, the reference selects {r['exact_share']:.6%} in "
             f"float32 (at least {spec['selection_exact_min_share']}) and "
             f"{r['stated_share']:.4%} in {cell.config['compute_dtype']} (at "
             f"least {spec['selection_min_share']}); the masked attention in "
             f"float32 GIVEN the reference's selection, relative L2 error "
             f"{r['attention']:.3g} (bound {spec['sparse_attention_rel_tol']}), "
             f"the layer's alignment loss {r['alignment']:.3g} (bound "
             f"{spec['alignment_rel_tol']}; the reference's "
             f"{r['alignment_loss']:.5f})")
    return {
        "selection_against_reference": (
            n["stated"] == n["exact"] == n["reference"]
            and r["exact_share"] >= spec["selection_exact_min_share"]
            and r["stated_share"] >= spec["selection_min_share"]),
        "sparse_attention_exact": (
            r["attention"] <= spec["sparse_attention_rel_tol"]
            and r["alignment"] <= spec["alignment_rel_tol"]),
    }


def indexer_program(cell, plants):
    """On one sequence of ``check.step_seq_len``: the gradients, on the
    indexer's leaves alone, of the step's loss and of the language-model
    loss (``HybridMoELM.loss_fn`` and its ``aux``), and of the reference's
    alignment loss, as sums of squares a leaf: ``(jitted, args)``."""
    config, spec = cell.config, cell.config["check"]
    ref, _ = lm_checks.reference_of(cell, plants)
    t = spec["step_seq_len"]
    ids = lm_checks.zipf_tokens(
        jax.random.fold_in(jax.random.key(cell.seed), 6), (1, t + 1),
        config["vocab_size"], cell.traffic["zipf_exponent"])
    batch = {"tokens": ids[:, :-1], "targets": ids[:, 1:]}
    mixers = [f"l{i}_mixer" for i in range(config["num_hidden_layers"])]

    def program(stacked, batch):
        params = lm_checks.worker0(stacked)
        with_indexer = lambda leaves: {**params, **{  # noqa: E731
            g: list(params[g][:6]) + list(leaves[g]) for g in mixers}}
        indexer = {g: list(params[g][6:]) for g in mixers}

        def losses(leaves):
            loss, (aux, _) = cell.model.loss_fn(with_indexer(leaves), {}, batch)
            lm = aux["lm_loss"]
            if "lm_loss_reaches_indexer" in plants:
                lm = lm + 1e-3 * aux["indexer_loss"]
            return (lm if "no_alignment_loss" in plants else loss), lm

        step = jax.grad(lambda x: losses(x)[0])(indexer)
        lm = jax.grad(lambda x: losses(x)[1])(indexer)
        with jax.default_matmul_precision("highest"):
            want = jax.grad(lambda x: ref.losses(
                with_indexer(x), batch["tokens"], batch["targets"], config,
                remat=True)[1])(indexer)
        rows = [jnp.stack([lm_checks.sq(s - w), lm_checks.sq(w),
                           lm_checks.sq(s), jnp.max(jnp.abs(m))])
                for g in mixers for s, m, w in zip(step[g], lm[g], want[g])]
        return jnp.stack(rows)

    return jax.jit(program), (cell.state.params, batch)


def indexer_learns(cell, plants):
    """``indexer_program``'s readings.  Every leaf: the step's gradient
    nonzero and the reference's alignment loss's (a band: the stated dtype
    selects other keys in places), the language-model loss's exactly 0."""
    config, spec = cell.config, cell.config["check"]
    t = spec["step_seq_len"]
    mixers = [f"l{i}_mixer" for i in range(config["num_hidden_layers"])]
    ahead, args = ahead_of(cell, plants, "indexer", indexer_program)
    sums = np.asarray(run_compiled(
        cell, "the indexer's gradients", ahead, *args), np.float64)
    names = [f"{g}[{6 + i}]" for g in mixers for i in range(5)]
    error = np.sqrt(sums[:, 0] / np.maximum(sums[:, 1], 1e-60))
    worst = int(np.argmax(error))
    moved, from_lm = sums[:, 2] > 0, sums[:, 3]
    cell.log(f"the indexer's {len(names)} leaves at T = {t}: the step's "
             f"gradient against jax.grad of the reference's alignment loss, "
             f"relative L2 error by leaf "
             f"{dict(zip(names, (float(f'{x:.3g}') for x in error)))}, worst "
             f"{names[worst]} {error[worst]:.3g} (bound "
             f"{spec['indexer_gradient_rel_tol']}); leaves the step moves "
             f"{int(moved.sum())} of {len(names)}; the language-model loss's "
             f"largest gradient on them {from_lm.max():.3g} (exactly 0)")
    return {"indexer_learns": bool(
        moved.all() and (from_lm == 0.0).all()
        and error.max() <= spec["indexer_gradient_rel_tol"])}


def router_part(cell, plants):
    spec = cell.config["check"]
    t0 = time.perf_counter()
    router = lm_checks.router_against_reference(cell, plants)
    cell.log(f"router against the reference's, relative L2 error {router:.3g} "
             f"(bound {spec['router_rel_tol']}); "
             f"{time.perf_counter() - t0:.1f} s")
    return {"router_in_float32": router <= spec["router_rel_tol"]}


def selection_gauges(cell, tokens, when):
    """One forward pass over one step's tokens (the probe ``start`` compiled,
    through ``apps/lm_app.set_selection_gauges``), on ``[bench]`` lines:
    where the tokens go (``hybrid_lm.routing_gauges``, against the grouped
    path's rows) and the indexer's gauges.  Returns them all."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.models.hybrid_lm import routing_gauges
    from sparknet_tpu.ops import moe

    config = cell.config
    probe = cell.probe.result() if hasattr(cell, "probe") else None
    gauges = lm_app.set_selection_gauges(
        cell.model, cell.state.params, tokens, cell.state.stats, probe=probe)
    gauges.update({k: [float(x) for x in v] for k, v in routing_gauges(
        gauges.pop("held_counts"), tokens.size).items()})
    top_k, experts = config["num_experts_per_tok"], config["num_experts"]
    held = config["experts_held"][1]
    rows = moe.fast_rows_for(tokens.size, top_k, experts, held)
    most = max(gauges["held_assignments_per_token"]) * tokens.size
    cell.log(f"routing of one step's {tokens.size} tokens {when}, by layer: "
             f"held_assignments_per_token "
             f"{[round(x, 4) for x in gauges['held_assignments_per_token']]}, "
             f"held_load_skew "
             f"{[round(x, 2) for x in gauges['held_load_skew']]}; expected "
             f"{top_k * held / experts} assignments a token; the grouped path "
             f"holds {rows} rows, the fullest layer sends {most:.0f}"
             + ("" if most <= rows else
                ": OVER, that layer runs in token chunks"))
    cell.log(f"selection of the same tokens, by layer: "
             f"sparknet_lm_indexer_loss "
             f"{[round(x, 5) for x in gauges['indexer_loss']]}, "
             f"sparknet_lm_selection_mass "
             f"{[round(x, 4) for x in gauges['selection_mass']]} (the share "
             f"of the dense attention's probability the selected keys hold); "
             f"sparknet_kernel_path{{kernel=\"sparse_attention\"}} 0 (XLA)")
    return gauges


def held_load(cell, gauges):
    """From ``selection_gauges``' of the step after the window: the
    assignments a token the held experts would receive, EVERY layer inside
    ``check.held_load_band`` times the expected ``top_k * held / experts``
    (this router has no bias and leaves no load in ``stats``, so the state
    the window left is read by a forward pass over the next step's tokens)."""
    config, spec = cell.config, cell.config["check"]
    expected = (config["num_experts_per_tok"] * config["experts_held"][1]
                / config["num_experts"])
    per_token = [float(x) for x in gauges["held_assignments_per_token"]]
    low, high = spec["held_load_band"]
    ok = bool(per_token) and all(
        low * expected <= x <= high * expected for x in per_token)
    cell.log(f"after the window the held experts receive "
             f"{[round(x, 4) for x in per_token]} assignments a token, by "
             f"layer; expected {expected}, band {low} to {high} times it: "
             f"{'ok' if ok else 'OUT'}")
    return {"held_load_in_window": ok}


PARTS = {
    "forward": lm_checks.forward_against_reference,
    "step": lambda cell, plants: lm_checks.step_against_reference(
        step_view(cell), plants),
    "float32": router_part,
    "selection": selection_parts,
    "indexer": indexer_learns,
}


def main(argv=None):
    """``python3 -m benchmark.keye_checks --workload <cell> --seed <n>
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
