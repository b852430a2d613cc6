"""τ-averaging convergence A/B — does dp=8 τ-local SGD with parameter
averaging converge comparably to plain single-worker SGD?  (The one
dynamics question the SparkNet paper is about: τ-step local SGD quality,
``CifarApp.scala:95-136``.)

Three runs on the teacher-net task (labels = a fixed nonlinear function
of noise images — see tools/run_teacher_convergence.py) with MATCHED
TOTAL SAMPLES:

  single     1 worker,  plain SGD, T iterations at batch B
  avg_dp8    8 workers, τ=10 local SGD + pmean(θ) per round, data
             partitioned 8 ways, T/8 iterations per worker
  allreduce  8 workers, synchronous gradient allreduce (global batch
             8B), T/8 steps

Runs on the 8-device virtual CPU mesh (this box has one real chip), so
the student is the small ``cifar10_quick`` net.  Writes the curves to
``training_log_<ts>_dp_ab.txt``;
``tests/test_convergence.py::test_committed_dp_ab_log`` asserts the
committed artifact: averaging within a few points of single-worker.

Usage: python tools/run_dp_ab.py [--total_iters N]
"""

import argparse
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)

DP, TAU = 8, 10


BATCH = 50  # halved from the config's 100: the A/B runs on a 1-core
# CPU host and compares averaging rules at matched samples, where the
# absolute batch size is not the object under test


def _solver(dtype=None):
    from sparknet_tpu import models
    from sparknet_tpu.config import replace_data_layers
    from sparknet_tpu.solver import Solver

    # quick model, fixed-lr leg of its schedule (the A/B compares
    # averaging rules, not schedules)
    sp = models.load_model_solver("cifar10_quick")
    sp.lr_policy = "fixed"
    shapes = [(BATCH, 3, 32, 32), (BATCH,)]
    netp = replace_data_layers(
        models.load_model("cifar10_quick"), shapes, shapes
    )
    return Solver(sp, net_param=netp, compute_dtype=dtype)


def _eval_acc(solver, state_host, test_batches, n_test_batches):
    scores = solver.test_and_store_result(state_host, test_batches)
    return scores["accuracy"] / n_test_batches


def run_single(Xtr, Ytr, test_batches, ntb, total_iters, log):
    import jax
    import numpy as np

    solver = _solver()
    batch = solver.net.blob_shapes[solver.net.feed_blobs[0]][0]
    state = solver.init_state(seed=0)
    n = Xtr.shape[0]
    t0 = time.time()
    chunk = 50  # iterations per dispatch
    for r in range(total_iters // chunk):
        idx = (np.arange(chunk)[:, None] * batch
               + np.arange(batch)[None, :] + r * chunk * batch) % n
        state, losses = solver.step(
            state, {"data": Xtr[idx], "label": Ytr[idx]}
        )
        if (r + 1) % 8 == 0:
            acc = _eval_acc(solver, state, test_batches, ntb)
            log.log(
                f"[single] iter {(r + 1) * chunk} accuracy {acc:.4f}"
            )
    acc = _eval_acc(solver, state, test_batches, ntb)
    log.log(f"[single] finished {total_iters} iters in "
            f"{time.time() - t0:.1f}s; final accuracy {acc:.4f}")
    return acc


def run_avg(Xtr, Ytr, test_batches, ntb, total_iters, log):
    """dp=8 τ=10 parameter averaging on 8 data partitions."""
    import jax
    import numpy as np

    from sparknet_tpu.parallel import ParameterAveragingTrainer
    from sparknet_tpu.parallel.mesh import make_mesh
    from sparknet_tpu.parallel.trainers import shard_leading

    solver = _solver()
    batch = solver.net.blob_shapes[solver.net.feed_blobs[0]][0]
    mesh = make_mesh({"dp": DP})
    trainer = ParameterAveragingTrainer(solver, mesh)
    state = trainer.init_state(seed=0)
    n = Xtr.shape[0]
    part = n // DP
    rounds = total_iters // (DP * TAU)
    t0 = time.time()
    for r in range(rounds):
        data, labels = [], []
        for w in range(DP):
            idx = (np.arange(TAU)[:, None] * batch
                   + np.arange(batch)[None, :]
                   + r * TAU * batch) % part + w * part
            data.append(Xtr[idx])
            labels.append(Ytr[idx])
        batches = {
            "data": np.stack(data), "label": np.stack(labels)
        }
        state, losses = trainer.round(state, shard_leading(batches, mesh))
        if (r + 1) % 5 == 0 or r == rounds - 1:
            host = jax.tree_util.tree_map(
                lambda b: (lambda a: a[0] if a.ndim else a)(np.asarray(b)),
                state,
            )
            acc = _eval_acc(solver, host, test_batches, ntb)
            log.log(
                f"[avg_dp8] round {r + 1} "
                f"(iter-equiv {(r + 1) * DP * TAU}) accuracy {acc:.4f}"
            )
    log.log(f"[avg_dp8] finished {rounds} rounds (tau={TAU}, dp={DP}) in "
            f"{time.time() - t0:.1f}s; final accuracy {acc:.4f}")
    return acc


def run_allreduce(Xtr, Ytr, test_batches, ntb, total_iters, log):
    """dp=8 synchronous gradient allreduce: global batch 8B."""
    import jax
    import numpy as np

    from sparknet_tpu.parallel import AllReduceTrainer
    from sparknet_tpu.parallel.mesh import make_mesh

    solver = _solver()
    batch = solver.net.blob_shapes[solver.net.feed_blobs[0]][0]
    mesh = make_mesh({"dp": DP})
    trainer = AllReduceTrainer(solver, mesh)
    state = trainer.init_state(seed=0)
    n = Xtr.shape[0]
    gbatch = batch * DP
    steps = total_iters // DP
    chunk = 10
    t0 = time.time()
    for r in range(steps // chunk):
        idx = (np.arange(chunk)[:, None] * gbatch
               + np.arange(gbatch)[None, :] + r * chunk * gbatch) % n
        state, losses = trainer.step(
            state, {"data": Xtr[idx], "label": Ytr[idx]}
        )
        if (r + 1) % 5 == 0 or r == steps // chunk - 1:
            host = jax.tree_util.tree_map(lambda b: np.asarray(b), state)
            acc = _eval_acc(solver, host, test_batches, ntb)
            log.log(
                f"[allreduce] step {(r + 1) * chunk} "
                f"(iter-equiv {(r + 1) * chunk * DP}) accuracy {acc:.4f}"
            )
    log.log(f"[allreduce] finished {steps} global steps in "
            f"{time.time() - t0:.1f}s; final accuracy {acc:.4f}")
    return acc


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--total_iters", type=int, default=2400)
    parser.add_argument("--n", type=int, default=6000)
    parser.add_argument("--n_test", type=int, default=1000)
    args = parser.parse_args(argv)

    import numpy as np

    from sparknet_tpu.utils.trainlog import TrainingLog
    from tools.run_teacher_convergence import make_teacher_labels

    log = TrainingLog(tag="dp_ab")
    rng = np.random.RandomState(0)
    X = rng.randint(0, 256, (args.n + args.n_test, 3, 32, 32)).astype(
        np.float32
    )
    Y = make_teacher_labels(X, batch=200)
    counts = np.bincount(Y.astype(int), minlength=10)
    log.log(
        f"teacher labels over {len(Y)} noise images; class counts "
        f"{counts.tolist()} (majority ceiling {counts.max() / len(Y):.3f})"
    )
    X -= X.mean(axis=0, keepdims=True)
    Xtr, Ytr = X[: args.n], Y[: args.n]
    Xte, Yte = X[args.n:], Y[args.n:]

    solver = _solver()
    batch = solver.net.blob_shapes[solver.net.feed_blobs[0]][0]
    ntb = args.n_test // batch
    test_batches = {
        "data": Xte[: ntb * batch].reshape(ntb, batch, 3, 32, 32),
        "label": Yte[: ntb * batch].reshape(ntb, batch),
    }

    T = args.total_iters
    log.log(
        f"matched-samples A/B: T={T} iterations at batch {batch} "
        f"({T * batch} samples each run); dp={DP} tau={TAU}"
    )
    acc_single = run_single(Xtr, Ytr, test_batches, ntb, T, log)
    acc_avg = run_avg(Xtr, Ytr, test_batches, ntb, T, log)
    acc_ar = run_allreduce(Xtr, Ytr, test_batches, ntb, T, log)
    log.log(
        f"headline: single {acc_single:.4f} avg_dp8 {acc_avg:.4f} "
        f"allreduce {acc_ar:.4f} avg-vs-single gap "
        f"{abs(acc_avg - acc_single):.4f} (chance 0.10)"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
