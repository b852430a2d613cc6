"""The comparisons of an ``lm-train-resident-kanana`` cell: ``lm_checks``'
forward, step and ``step_exact`` on the cell's own objects against
``benchmark/reference/kanana2.py``, and LFM2's router comparison, held-load
verdict and routing lines (``lfm2_checks``: the router is the same kind, a
sigmoid with a selection bias; here with the scaling factor 2.448 and 1e-20),
all through ``view(cell)``: the same objects, the configuration under the
names those accepted files read (``num_experts``).

What is this file's own is the planted faults of the latent attention and of
this family's feed-forward (``PLANTS``).  Each is planted in the REFERENCE,
as ``keye_checks`` plants its indexer's: ``planted_reference`` makes a copy
of the reference's module with one of its small functions replaced, under a
name of its own, and the comparison, which is symmetric, has to read the
program, which has none of them, as not correct.  ``LM_CHECK_PLANT`` takes
those and the six of ``lm_checks`` / ``lfm2_checks`` that mean something
here.
"""

import importlib.util
import os
import sys
import time
import types

import jax
import jax.numpy as jnp

from benchmark import lfm2_checks, lm_checks

PLANTS = {
    "scale_128": "the reference scales its scores by qk_nope_head_dim^(-1/2) "
    "(128) in place of qk_head_dim^(-1/2) (192)",
    "rotary_on_nope": "the reference turns the nope parts of q and k too",
    "rotate_half_keys": "the reference's rope KEY is turned by rotate-half, "
    "its queries over adjacent pairs",
    "rope_key_per_head": "every head of the reference reads a rope key of "
    "its own (the one key's columns rolled by two a head: what a wider "
    "projection's further columns would be, another key)",
    "no_latent_norm": "the reference's latent loses its RMSNorm",
    "no_routed_scaling": "the reference's routed weights lose "
    "routed_scaling_factor",
    "gated_shared_expert": "the reference's shared expert is gated by "
    "sigmoid(x . g), g its gate matrix's first column (Qwen3-Next's output "
    "gate, which this model has not)",
}
# with those of the accepted cells' that mean something in this one
ALL_PLANTS = {
    **{k: lm_checks.PLANTS[k] for k in (
        "state_unchanged", "half_batch", "bfloat16_update",
        "float8_reference", "bfloat16_router")},
    "biased_weights": lfm2_checks.PLANTS["biased_weights"],
    **PLANTS,
}
PLANTED_NAME = "kanana2_planted"


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
    """``benchmark.reference.kanana2`` itself where no fault of ``PLANTS`` is
    planted; else a second copy of that module, importable as
    ``benchmark.reference.kanana2_planted``, with the faults in it."""
    from benchmark.reference import kanana2, lfm2_moe

    if not plants & set(PLANTS):
        return kanana2
    spec = importlib.util.spec_from_file_location(
        "benchmark.reference." + PLANTED_NAME, kanana2.__file__)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    pairs = kanana2.rotate_pairs
    if "scale_128" in plants:
        ref.score_scale = lambda config: config["qk_nope_head_dim"] ** -0.5
    if "rotary_on_nope" in plants:
        ref.positions = lambda qn, qr, kn, kr, theta: (
            pairs(qn, theta), pairs(qr, theta), pairs(kn, theta),
            pairs(kr, theta))
    if "rotate_half_keys" in plants:
        # the other families' rotary, as LFM2's reference writes it
        ref.positions = lambda qn, qr, kn, kr, theta: (
            qn, pairs(qr, theta), kn,
            lfm2_moe.rotate_half(kr[:, :, None], theta)[:, :, 0])
    if "rope_key_per_head" in plants:
        ref.rope_key_of = lambda k_rope, head: jnp.roll(
            k_rope, 2 * head, axis=-1)
    if "no_latent_norm" in plants:
        ref.latent_norm = lambda c, w, eps: c
    if "no_routed_scaling" in plants:
        def route(x, w_router, config, bias=None):
            return kanana2.route(
                x, w_router, {**config, "routed_scaling_factor": 1.0}, bias)
        ref.route = route
    if "gated_shared_expert" in plants:
        ref.shared_expert = lambda x, blobs, operand_dtype=None: (
            kanana2.shared_expert(x, blobs, operand_dtype)
            * jax.nn.sigmoid(kanana2.mm(x, blobs[0][:, :1])))
    sys.modules[spec.name] = ref
    return ref


def view(cell, plants=frozenset()):
    """The cell as the accepted comparisons are to see it: the same objects;
    the configuration with the experts' count under the name they read; and
    the reference's planted copy where a fault of ``PLANTS`` is planted."""
    seen = types.SimpleNamespace(**vars(cell))
    seen.config = {**cell.config,
                   "num_experts": cell.config["n_routed_experts"]}
    if set(plants) & set(PLANTS):
        planted_reference(set(plants))
        seen.config["reference"] = PLANTED_NAME
    return seen


def forward(cell, plants):
    return lm_checks.forward_against_reference(view(cell, plants), plants)


def step(cell, plants):
    return lm_checks.step_against_reference(view(cell, plants), plants)


def float32_parts(cell, plants):
    """The router alone, as the model describes it (sigmoid in float32,
    top-6 on ``scores + bias``, ``w / (sum + 1e-20)``, times 2.448), against
    the reference's, a seeded bias in play."""
    spec = cell.config["check"]
    t0 = time.perf_counter()
    router, same, moved = lfm2_checks.router_against_reference(
        view(cell, plants), plants)
    cell.log(f"router against the reference's, relative L2 error {router:.3g} "
             f"(bound {spec['router_rel_tol']}), the same selection in "
             f"{same:.4%} of the rows, the bias changes the selection of "
             f"{moved:.2%} of them; {time.perf_counter() - t0:.1f} s")
    return {"router_in_float32":
            router <= spec["router_rel_tol"] and moved > 0}


def routing(cell, tokens, when):
    return lfm2_checks.routing(view(cell), tokens, when)


def held_load_in_window(cell):
    return lfm2_checks.held_load_in_window(view(cell))


PARTS = {"forward": forward, "step": step, "float32": float32_parts}


def main(argv=None):
    """``python3 -m benchmark.kanana_checks --workload <cell> --seed <n>
    [--plant a,b[:parts]] ...``: ``lm_checks.main`` with this cell's plants
    and parts: the comparisons alone, once for every ``--plant`` group
    (none: once, unplanted), without the partition, the rounds and the
    window (``--rehearse``: on the CPU at the files' tiny sizes).  A planted
    group has to print a verdict with a ``false`` in it."""
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
