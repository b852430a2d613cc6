"""Teacher-net convergence run — the discriminating convergence artifact.

The separable-synthetic-CIFAR runs saturate at 100% (any correct update
rule gets there); this task cannot be gamed that way: labels are the
argmax of a FIXED randomly-initialized cifar10_quick teacher network's
per-class-standardized logits on uniform-noise images.  The mapping is a
deterministic nonlinear function of the input — learnable, but only by
actually fitting the teacher's decision surface — so the student lands
meaningfully between chance (10%) and 100%, and a broken optimizer,
averaging rule, or LR schedule shows up as a depressed curve.

Runs the reference ``cifar10_full`` schedule (lr 0.001 fixed, momentum
0.9, 60k iterations, batch 100 — ``caffe/examples/cifar10/
cifar10_full_solver.prototxt``) twice: bf16 compute (the framework
default) and f32 (reference numerics), same data and seeds, logging both
curves to the reference-format ``training_log_<ts>_teacher.txt``.
``tests/test_convergence.py::test_committed_teacher_log`` asserts the
committed artifact's stated expectations.

The dataset lives device-resident (one ~37 MB upload) and minibatches
are gathered on device each round, so the host link carries nothing
in the loop.

Usage: python tools/run_teacher_convergence.py [--iters N] [--n N]
"""

import argparse
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def make_teacher_labels(images, batch=500, seed=123):
    """argmax of per-class-standardized logits of a random-init
    cifar10_quick net (standardization balances the classes without
    changing 'labels are a fixed function of x')."""
    import jax
    import numpy as np

    from sparknet_tpu import models
    from sparknet_tpu.net import JaxNet

    netp = models.deploy_variant(models.load_model("cifar10_quick"),
                                 batch=batch)
    net = JaxNet(netp, phase="TEST")
    params, stats = net.init(seed)
    fwd = jax.jit(lambda x: net.forward(params, stats, {"data": x})["prob"])
    n = images.shape[0]
    logits = []
    for i in range(0, n, batch):
        chunk = images[i:i + batch]
        real = chunk.shape[0]
        if real < batch:  # tile the tail up to the fixed jit shape
            reps = -(-batch // real)
            chunk = np.tile(chunk, (reps, 1, 1, 1))[:batch]
        logits.append(np.asarray(fwd(chunk))[:real])
    z = np.concatenate(logits)
    z = (z - z.mean(axis=0)) / (z.std(axis=0) + 1e-8)
    return z.argmax(axis=1).astype(np.float32)


def run_curve(tag, dtype, Xtr, Ytr, Xte, Yte, iters, log, tau=500):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu import models
    from sparknet_tpu.solver import Solver

    solver = Solver(
        models.load_model_solver("cifar10_full"), compute_dtype=dtype
    )
    batch = solver.net.blob_shapes[solver.net.feed_blobs[0]][0]
    state = solver.init_state(seed=0)

    dXtr = jax.device_put(jnp.asarray(Xtr))
    dYtr = jax.device_put(jnp.asarray(Ytr))
    n = Xtr.shape[0]

    # device-side sequential-cursor gather: round r covers iterations
    # [r*tau, (r+1)*tau), each taking the next contiguous batch window
    # with epoch wrap (MinibatchSampler semantics)
    def gather(start_iter, tau):
        idx = (jnp.arange(tau)[:, None] * batch
               + jnp.arange(batch)[None, :]
               + start_iter * batch) % n
        return {"data": dXtr[idx], "label": dYtr[idx]}

    gather = jax.jit(gather, static_argnums=(1,))

    test_batches = {
        "data": jax.device_put(
            jnp.asarray(Xte.reshape(-1, batch, *Xte.shape[1:]))
        ),
        "label": jax.device_put(jnp.asarray(Yte.reshape(-1, batch))),
    }
    n_test_batches = test_batches["label"].shape[0]

    accs = []
    t0 = time.time()
    for r in range(iters // tau):
        state, losses = solver.step(state, gather(r * tau, tau))
        if (r + 1) % 10 == 0 or r == iters // tau - 1:
            scores = solver.test_and_store_result(state, test_batches)
            acc = scores["accuracy"] / n_test_batches
            accs.append(acc)
            log.log(
                f"[{tag}] iter {(r + 1) * tau} smoothed_loss "
                f"{float(np.asarray(losses)[-1]):.4f} accuracy {acc:.4f}"
            )
    log.log(f"[{tag}] finished {iters} iters in {time.time() - t0:.1f}s; "
            f"final accuracy {accs[-1]:.4f}")
    return accs


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--iters", type=int, default=60000,
                        help="cifar10_full schedule length")
    parser.add_argument("--n", type=int, default=10000)
    parser.add_argument("--n_test", type=int, default=2000)
    parser.add_argument("--tau", type=int, default=500,
                        help="iterations per jitted dispatch")
    args = parser.parse_args(argv)

    import numpy as np

    from sparknet_tpu.utils.trainlog import TrainingLog

    log = TrainingLog(tag="teacher")
    rng = np.random.RandomState(0)
    X = rng.randint(0, 256, (args.n + args.n_test, 3, 32, 32)).astype(
        np.float32
    )
    Y = make_teacher_labels(X)
    counts = np.bincount(Y.astype(int), minlength=10)
    log.log(
        f"teacher labels over {len(Y)} noise images; class counts "
        f"{counts.tolist()} (majority-class ceiling for a constant "
        f"predictor: {counts.max() / len(Y):.3f})"
    )
    X -= X.mean(axis=0, keepdims=True)  # per-pixel mean, CIFAR-path style
    Xtr, Ytr = X[: args.n], Y[: args.n]
    Xte, Yte = X[args.n:], Y[args.n:]

    acc_bf16 = run_curve("bf16", "bfloat16", Xtr, Ytr, Xte, Yte,
                         args.iters, log, tau=args.tau)
    acc_f32 = run_curve("f32", None, Xtr, Ytr, Xte, Yte, args.iters, log,
                        tau=args.tau)
    log.log(
        f"headline: bf16 {acc_bf16[-1]:.4f} f32 {acc_f32[-1]:.4f} "
        f"gap {abs(acc_bf16[-1] - acc_f32[-1]):.4f} "
        f"(expectation: both in (0.20, 0.95), gap < 0.05, chance 0.10)"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
