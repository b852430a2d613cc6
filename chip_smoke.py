#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children, no flags.  It refuses to run unless jax's first
device is a TPU, then drives the main path once through the entry points a
user would call, at the full width of CaffeNet:

- ``train-1chip``: ``apps.imagenet_app.main`` (ParameterAveragingTrainer,
  RoundFeed, on-device crop/mirror/mean) with ``--model=caffenet
  --workers=1``, batch 256, stored 256x256, crop 227, 1000 classes, on the
  app's own synthetic JPEG shards;
- ``train-4chip``: the same call with ``--workers=4`` when there are four
  devices (otherwise ``skipped: N devices`` — never a pass), asserting state
  and batches sit on four distinct devices and the workers agree after the
  averaging round;
- ``kernels``: every Pallas kernel ``lowerable()`` routes to by default,
  compiled (``interpret=False``), run and compared with its dense/unfused
  reference at the shapes the LM produces today and at one real head shape,
  the flash kernels with a second score term at kanana2-train-8k's heads,
  the sequence models' loss at the two sequence cells' shapes;
- ``lm-train``: ``apps.lm_app.main`` at its default preset, so the
  default-on flash path is reached through a normal entry point.

What it writes (training logs, ``summary.json``) goes under
``chiprun_out/chip_smoke/``.  The last line of stdout is the result,
``{"ok": ..., "device": {"platform", "kind", "count"}}`` and nothing more;
the line before it (``[chip_smoke] summary: {...}``, also ``summary.json``)
holds the per-phase status, compile and wall seconds, rounds and final
loss — information, not claims.  Any failed phase makes the exit code
non-zero; without a TPU it exits 2 and prints no result.
"""

import contextlib
import json
import os
import sys
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(_HERE, "chiprun_out", "chip_smoke")

# (name, B, T, H, D, dtype): (a) what lm_app and `cli serve --generate`
# produce today, (b) one real head shape (B*H = 16)
FULL = {
    "model": "caffenet",
    "train_batch": 256,
    "test_batch": 50,
    "full_size": 256,
    "crop": 227,
    "classes": 1000,
    "tau": 2,
    "rounds": 3,
    "attention_shapes": (
        ("lm", 8, 128, 2, 32, "float32"),
        ("head", 2, 1024, 8, 128, "bfloat16"),
    ),
    # (name, B, T, H, D, R, dtype): the flash kernels with a second score
    # term, kanana2-train-8k's heads of 128 + 64 against values of 128
    "mla_shapes": (("kanana2", 2, 1024, 8, 128, 64, "bfloat16"),),
    "comm_legs": (
        ("fp32", False), ("bf16", False), ("int8", False), ("int8", True),
    ),
    # (name, rows, width, vocabulary, head as (vocab, E)): the loss kernels at
    # the two sequence cells' shapes, lfm2moe-train-8k's tied head and
    # qwen3next-train-8k's untied one with its ragged tail (which the kernels
    # take and lm_loss.nll_sum does not hand them: PERF.md section 6, PR 32)
    "lm_loss_shapes": (
        ("lfm2", 16384, 2048, 8192, True),
        ("qwen3next", 16384, 2048, 18992, False),
    ),
    "lm_rounds": 4,
    "lm_args": (),  # lm_app's default preset, here and for the comm legs
    "interpret": False,
}


def builds_since(mark=0):
    """What the program built since ``mark`` (a length of
    ``obs.programs()``), from its own account: every ``obs.Program`` the
    trainer and the solver build writes one record a signature, and counts
    it in ``sparknet_program_builds_total{program,cache}``."""
    from sparknet_tpu import obs

    rows = obs.programs()[mark:]
    return {
        "compiles": len(rows),
        "compile_s": round(sum(r["compile_s"] for r in rows), 2),
        "trace_lower_s": round(sum(r["trace_lower_s"] for r in rows), 2),
        "cache_hits": sum(r["cache"] == "hit" for r in rows),
        "programs": [r["program"] for r in rows],
    }


def builds_counted():
    """The same count as the program's counter holds it, over all labels."""
    from sparknet_tpu import obs

    tm = obs.enable_training_metrics()
    return int(sum(c.value for c in tm.program_builds.children()))


def _shard_devices(tree):
    """Per leaf, the set of devices holding its addressable shards."""
    import jax

    return [
        frozenset(s.device for s in leaf.addressable_shards)
        for leaf in jax.tree_util.tree_leaves(tree)
    ]


class TrainerSpy:
    """Watches one app run from outside: wraps ``ParameterAveragingTrainer``'s
    ``init_state`` / ``round`` / ``test_and_store_result`` for the duration
    and records what the smoke asserts on.  The apps return only an exit
    code; this reads the trainer they built."""

    def __init__(self):
        from sparknet_tpu import obs

        self.first_build = len(obs.programs())
        self.trainer = None
        self.state = None
        self.init_norm = None
        self.rounds = []  # per round: losses, builds inside round(), seconds
        self.placements = []  # per round: device sets before the round ran
        self.eval_scores = []

    @contextlib.contextmanager
    def installed(self):
        from sparknet_tpu import obs
        from sparknet_tpu.parallel import ParameterAveragingTrainer as T

        orig = T.init_state, T.round, T.test_and_store_result
        spy = self

        def init_state(trainer, *a, **kw):
            state = orig[0](trainer, *a, **kw)
            spy.trainer = trainer
            spy.init_norm = spy.param_stats(state)[0]
            return state

        def round_(trainer, state, batches, *a, **kw):
            import jax
            import numpy as np

            spy.placements.append({
                "params": _shard_devices(state.params),
                "history": _shard_devices(state.history),
                "batches": _shard_devices(batches),
            })
            mark = len(obs.programs())
            t0 = time.perf_counter()
            out = orig[1](trainer, state, batches, *a, **kw)
            losses = np.asarray(jax.block_until_ready(out[1]))
            spy.rounds.append({
                "losses": losses,
                "seconds": time.perf_counter() - t0,
                **builds_since(mark),
            })
            spy.state = out[0]
            return out

        def test_and_store_result(trainer, *a, **kw):
            scores = orig[2](trainer, *a, **kw)
            spy.eval_scores.append(scores)
            return scores

        T.init_state, T.round, T.test_and_store_result = (
            init_state, round_, test_and_store_result,
        )
        try:
            yield self
        finally:
            T.init_state, T.round, T.test_and_store_result = orig

    @staticmethod
    def param_stats(state):
        """(worker 0's squared parameter norm, the largest difference
        between any worker's parameters and worker 0's)."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def stats(params):
            leaves = [
                x.astype(jnp.float32)
                for x in jax.tree_util.tree_leaves(params)
            ]
            norm = sum(jnp.sum(jnp.square(x[0])) for x in leaves)
            spread = jnp.max(
                jnp.stack([jnp.max(jnp.abs(x - x[:1])) for x in leaves])
            )
            return norm, spread

        norm, spread = stats(state.params)
        return float(norm), float(spread)

    def check(self, workers, min_rounds):
        """Assert the run trained; returns the phase's result fields."""
        import numpy as np

        assert self.trainer is not None, "the app built no averaging trainer"
        assert len(self.rounds) >= min_rounds, (
            f"{len(self.rounds)} rounds ran, wanted {min_rounds}"
        )
        for r, rec in enumerate(self.rounds):
            assert rec["losses"].shape[0] == workers, rec["losses"].shape
            assert np.isfinite(rec["losses"]).all(), (
                f"round {r} losses {rec['losses']}"
            )
            if r > 0:
                assert rec["compiles"] == 0, (
                    f"round {r} built {rec['programs']} — only round 0 may"
                )
        round_builds = builds_since(self.first_build)["programs"].count("round")
        assert round_builds == 1, f"the round was built {round_builds}x"
        final_norm, spread = self.param_stats(self.state)
        assert final_norm != self.init_norm, "training left the weights alone"
        assert spread == 0.0, (
            f"workers disagree after averaging: max |p_w - p_0| = {spread}"
        )
        for kind in ("params", "history", "batches"):
            for devices in self.placements[-1][kind]:
                assert len(devices) == workers, (
                    f"a {kind} leaf sits on {len(devices)} device(s), "
                    f"wanted {workers}"
                )
        return {
            "rounds": len(self.rounds),
            "final_loss": round(float(self.rounds[-1]["losses"].mean()), 4),
            "round_s": [round(rec["seconds"], 3) for rec in self.rounds],
            "devices_per_leaf": workers,
            "worker_param_spread": spread,
        }


def phase_train(sizes, workers):
    """The flagship app at the configuration's width on ``workers`` chips."""
    import numpy as np

    from sparknet_tpu.apps import imagenet_app

    spy = TrainerSpy()
    with spy.installed():
        rc = imagenet_app.main([
            f"--model={sizes['model']}",
            f"--workers={workers}",
            f"--rounds={sizes['rounds']}",
            f"--tau={sizes['tau']}",
            "--test_every=1",
            f"--train_batch={sizes['train_batch']}",
            f"--test_batch={sizes['test_batch']}",
            f"--full_size={sizes['full_size']}",
            f"--crop={sizes['crop']}",
            f"--classes={sizes['classes']}",
        ])
    assert rc == 0, f"imagenet_app exited {rc}"
    out = spy.check(workers, min_rounds=sizes["rounds"])
    # the test-score path: once before every round and once at the end
    assert len(spy.eval_scores) == sizes["rounds"] + 1, len(spy.eval_scores)
    for scores in spy.eval_scores:
        assert scores and all(np.isfinite(v) for v in scores.values()), scores
    out["eval_passes"] = len(spy.eval_scores)
    shapes = spy.trainer.solver.net.blob_shapes
    if "fc8" in shapes:  # the synthetic data's 4 labels must not narrow it
        assert shapes["fc8"][1] == sizes["classes"], shapes["fc8"]
        out["fc8_outputs"] = int(shapes["fc8"][1])
    return out


def _rel_err(got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(1e-6, np.max(np.abs(ref))))


def _attention_kernels(name, b, t, h, d, dtype, interpret):
    """flash forward, its backward's dq and dk/dv, and decode at one shape,
    against the dense references in float32 at full matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops import pallas_attention as pa
    from sparknet_tpu.ops.attention import mha_reference

    tol = {"float32": 2e-2, "bfloat16": 5e-2}[dtype]
    rng = np.random.RandomState(0)
    q, k, v = (
        jnp.asarray(rng.randn(b, t, h, d), dtype) for _ in range(3)
    )

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            jnp.square(attn(q, k, v).astype(jnp.float32))
        )

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, interpret=interpret)

    def dense(q, k, v):
        return mha_reference(q, k, v, causal=True)

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        ref_o = jax.jit(dense)(*f32)
        ref_g = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(*f32)
    o = jax.jit(flash)(q, k, v)
    dq, dk, dv = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    errs = {
        "flash_fwd": _rel_err(o, ref_o),
        "flash_bwd_dq": _rel_err(dq, ref_g[0]),
        "flash_bwd_dkv": max(_rel_err(dk, ref_g[1]), _rel_err(dv, ref_g[2])),
    }
    # decode: one new position against an over-allocated context
    q1 = q[:, :1]
    lengths = jnp.asarray(rng.randint(1, t + 1, b), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref_d = jax.jit(pa._decode_reference)(q1.astype(jnp.float32), *f32[1:],
                                              lengths)
    got_d = jax.jit(
        lambda q, k, v, n: pa.decode_attention(q, k, v, n, interpret=interpret)
    )(q1, k, v, lengths)
    errs["decode"] = _rel_err(got_d, ref_d)
    for kernel, err in errs.items():
        assert np.isfinite(err) and err < tol, (
            f"{kernel} at {name} {(b, t, h, d, dtype)}: rel err {err} "
            f">= {tol}"
        )
    return {k: float("%.2e" % e) for k, e in errs.items()}


def _lm_loss_kernels(name, rows, width, vocab, vocab_first, interpret):
    """The sequence models' loss through its kernels in bfloat16, in the
    blocks the program runs them in: loss, dx and dhead against the XLA
    oracle in float32 at full matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops import lm_loss, pallas_lm_loss

    key = jax.random.key(0)
    x = jax.random.normal(jax.random.fold_in(key, 0), (rows, width))
    head = 0.02 * jax.random.normal(
        jax.random.fold_in(key, 1),
        (vocab, width) if vocab_first else (width, vocab))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (rows,), 0, vocab)
    targets = targets.at[0].set(vocab - 1)  # the last column of the last block

    def kernels(x, head):
        return jnp.sum(pallas_lm_loss.nll_rows(
            x, head, targets, jnp.bfloat16, vocab_first=vocab_first,
            interpret=interpret))

    def oracle(x, head):
        return lm_loss._xla_nll_sum(
            x, head, targets, jnp.dtype(jnp.float32), vocab_first)

    grads = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        ref, (ref_dx, ref_dhead) = grads(oracle)(x, head)
    loss, (dx, dhead) = grads(kernels)(x, head)
    errs = {
        "loss": abs(float(loss) - float(ref)) / abs(float(ref)),
        "dx": _rel_err(dx, ref_dx),
        "dhead": _rel_err(dhead, ref_dhead),
    }
    for what, err in errs.items():
        tol = 1e-3 if what == "loss" else 5e-2
        assert np.isfinite(err) and err < tol, (
            f"lm loss {what} at {name} {(rows, width, vocab)}: rel err "
            f"{err} >= {tol}"
        )
    return {k: float("%.2e" % e) for k, e in errs.items()}


def _comm_kernels(legs, lm_args, interpret):
    """fused encode / apply / correction against the unfused closures,
    through the trainer that calls them: the LM's own parameter leaves,
    one worker on one device, a few rounds per leg.  On the CPU the two
    are bit-identical (tests/test_pallas_comm.py); on the chip Mosaic and
    XLA may round a quantized delta differently, so a leg may differ by
    what its payload's precision allows over three rounds, not by more."""
    import argparse

    import jax
    import numpy as np

    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.ops import pallas_comm
    from sparknet_tpu.parallel import (
        ParameterAveragingTrainer,
        make_mesh,
        shard_leading,
    )

    parser = argparse.ArgumentParser()
    lm_app.add_lm_model_args(parser)
    args = parser.parse_known_args(list(lm_args))[0]
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    rng = np.random.RandomState(0)
    tau, batch = 2, 4
    tokens = rng.randint(0, 256, (1, tau, batch, args.seq_len + 1))
    data = {
        "tokens": tokens[..., :-1].astype(np.int32),
        "targets": tokens[..., 1:].astype(np.int32),
    }

    def run(fused, compress, overlap):
        _, solver = lm_app.build_lm_solver(args, sp=1)
        trainer = ParameterAveragingTrainer(
            solver, mesh, compress=compress, overlap_avg=overlap,
            comm_fused=fused,
        )
        assert trainer._comm.fused == fused
        state = trainer.init_state(seed=0)
        for _ in range(3):
            state = trainer.round(state, shard_leading(data, mesh))[0]
        return jax.device_get(trainer.finalize(state).params)

    # the plane hands the kernels interpret=None, the backend rule; on
    # the chip that rule must say "compile"
    assert pallas_comm._resolve_interpret(None) == interpret
    out = {}
    for compress, overlap in legs:
        ref = run(False, compress, overlap)
        got = run(True, compress, overlap)
        err = max(
            _rel_err(g, r)
            for g, r in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(ref),
            )
        )
        leg = f"{compress}_{'overlap' if overlap else 'barriered'}"
        tol = {"fp32": 1e-5, "bf16": 2e-2, "int8": 2e-2}[compress]
        assert np.isfinite(err) and err < tol, f"comm {leg}: rel err {err}"
        out[leg] = float("%.2e" % err)
    return out


def _mla_kernels(name, b, t, h, d, r, dtype, interpret):
    """The flash kernels with a second score term (latent attention: heads
    ``d + r`` wide, the ``r`` against ONE key every head shares, values
    ``d`` wide), forward and the five gradients, against the whole masked
    score matrix in float32 at full matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops import pallas_attention as pa

    tol = {"float32": 2e-2, "bfloat16": 5e-2}[dtype]
    rng = np.random.RandomState(0)
    shapes = ((b, t, h, d), (b, t, h, r), (b, t, h, d), (b, t, r), (b, t, h, d))
    xs = [jnp.asarray(rng.randn(*shape), dtype) for shape in shapes]

    def loss(attn):
        return lambda *xs: jnp.sum(jnp.square(attn(*xs).astype(jnp.float32)))

    def flash(*xs):
        return pa.mla_flash_attention(
            *xs, block_q=min(512, t), interpret=interpret)

    def dense(q_nope, q_rope, k_nope, k_rope, v):
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
             + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope)) * (d + r) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    f32 = [x.astype(jnp.float32) for x in xs]
    every = tuple(range(5))
    with jax.default_matmul_precision("highest"):
        ref_o = jax.jit(dense)(*f32)
        ref_g = jax.jit(jax.grad(loss(dense), argnums=every))(*f32)
    o = jax.jit(flash)(*xs)
    grads = jax.jit(jax.grad(loss(flash), argnums=every))(*xs)
    errs = {"mla_fwd": _rel_err(o, ref_o)}
    for part, got, ref in zip(
            ("q_nope", "q_rope", "k_nope", "k_rope", "v"), grads, ref_g):
        errs["mla_bwd_" + part] = _rel_err(got, ref)
    for kernel, err in errs.items():
        assert np.isfinite(err) and err < tol, (
            f"{kernel} at {name} {(b, t, h, d, r, dtype)}: rel err {err} "
            f">= {tol}"
        )
    return {k: float("%.2e" % e) for k, e in errs.items()}


def phase_kernels(sizes):
    interpret = sizes["interpret"]
    out = {"interpret": interpret}
    for name, b, t, h, d, dtype in sizes["attention_shapes"]:
        out[f"attention_{name}_B{b}_T{t}_H{h}_D{d}_{dtype}"] = (
            _attention_kernels(name, b, t, h, d, dtype, interpret)
        )
    for name, b, t, h, d, r, dtype in sizes["mla_shapes"]:
        out[f"mla_{name}_B{b}_T{t}_H{h}_D{d}+{r}_{dtype}"] = (
            _mla_kernels(name, b, t, h, d, r, dtype, interpret)
        )
    for name, rows, width, vocab, vocab_first in sizes["lm_loss_shapes"]:
        out[f"lm_loss_{name}_{rows}x{width}x{vocab}_bfloat16"] = (
            _lm_loss_kernels(name, rows, width, vocab, vocab_first, interpret)
        )
    out["comm_lm_leaves"] = _comm_kernels(
        sizes["comm_legs"], sizes["lm_args"], interpret
    )
    return out


def phase_lm(sizes):
    """lm_app at its default preset: on the chip the
    Pallas flash kernel is the train step's attention."""
    import jax

    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.ops import pallas_attention

    flash_calls = []
    real_flash = pallas_attention.flash_attention

    def counting_flash(*a, **kw):
        flash_calls.append(1)
        return real_flash(*a, **kw)

    spy = TrainerSpy()
    pallas_attention.flash_attention = counting_flash
    try:
        with spy.installed():
            rc = lm_app.main([
                f"--rounds={sizes['lm_rounds']}", "--log_every=1",
                *sizes["lm_args"],
            ])
    finally:
        pallas_attention.flash_attention = real_flash
    assert rc == 0, f"lm_app exited {rc}"
    workers = spy.trainer.num_workers
    out = spy.check(workers, min_rounds=sizes["lm_rounds"])
    on_kernel = bool(flash_calls)
    assert on_kernel == pallas_attention.lowerable(), (
        f"flash kernel traced {len(flash_calls)}x on {jax.default_backend()}"
    )
    out["workers"] = workers
    out["flash_kernel"] = on_kernel
    return out


def run(sizes, out_dir=OUT_DIR):
    """Run every phase at ``sizes`` on the devices jax has; returns
    ``(ok, phases)``.  ``main`` decides whether those devices count."""
    import jax

    os.makedirs(out_dir, exist_ok=True)
    n = jax.device_count()
    plan = [
        ("train-1chip", lambda: phase_train(sizes, 1)),
        ("train-4chip",
         (lambda: phase_train(sizes, 4)) if n >= 4 else None),
        ("kernels", lambda: phase_kernels(sizes)),
        ("lm-train", lambda: phase_lm(sizes)),
    ]
    phases = {}
    log_dir = os.environ.get("SPARKNET_LOG_DIR")
    os.environ["SPARKNET_LOG_DIR"] = out_dir  # TrainingLog files land here
    try:
        _run_plan(plan, phases, n)
    finally:
        if log_dir is None:
            del os.environ["SPARKNET_LOG_DIR"]
        else:
            os.environ["SPARKNET_LOG_DIR"] = log_dir
    ok = all(
        p["status"] == "ran" or p["status"].startswith("skipped")
        for p in phases.values()
    )
    return ok, phases


def _run_plan(plan, phases, n):
    from sparknet_tpu import obs

    for name, fn in plan:
        if fn is None:
            phases[name] = {"status": f"skipped: {n} devices"}
            print(f"[chip_smoke] {name}: {phases[name]['status']}", flush=True)
            continue
        print(f"[chip_smoke] {name}: start", flush=True)
        mark, counted = len(obs.programs()), builds_counted()
        t0 = time.perf_counter()
        try:
            result = {"status": "ran", **fn()}
        except Exception as e:  # a failed phase fails the smoke, later
            # phases still run: one chip call should say all that is broken
            traceback.print_exc()
            result = {"status": "failed", "error": f"{type(e).__name__}: {e}"[:400]}
        result.update(builds_since(mark))
        # the program's counter and its records are one account
        result["builds_counted"] = builds_counted() - counted
        result["wall_s"] = round(time.perf_counter() - t0, 2)
        phases[name] = result
        print(f"[chip_smoke] {name}: {json.dumps(result)}", flush=True)


def main() -> int:
    from importlib import metadata

    from sparknet_tpu.utils.devices import enable_compile_cache, require_chip

    try:
        devices = require_chip()
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    import jax
    import jaxlib

    from sparknet_tpu import runtime

    cache_dir = enable_compile_cache()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    versions = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": metadata.version("libtpu"),
    }
    native = "native" if runtime.native_available() else "python fallback"
    print(f"[chip_smoke] device {device} versions {versions}")
    print(f"[chip_smoke] host pipeline: {native}; compile cache: {cache_dir}")
    t0 = time.perf_counter()
    ok, phases = run(FULL)
    summary = json.dumps({
        "ok": ok,
        "device": device,
        "versions": versions,
        "host_pipeline": native,
        "compile_cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t0, 1),
        "phases": phases,
        "claim": None,
    })
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        f.write(summary + "\n")
    print(f"[chip_smoke] summary: {summary}")
    # the result line: these two keys and no others, the last line of stdout
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
