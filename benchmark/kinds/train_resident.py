"""Traffic kind ``train-resident``: tau-averaging rounds over a data
partition that lives in the chip's memory.

The cell drives the objects ``apps/imagenet_app.py`` builds, in the order it
builds them: ``models.load_model`` + ``replace_data_layers``, ``Solver`` with
the on-device crop/mirror/mean transform, ``make_mesh({"dp": W})``,
``ParameterAveragingTrainer`` at its defaults, ``trainer.init_state(seed)``.
The app itself cannot be timed (it evaluates, logs and exits by round count,
and reads the loss after every round), so the loop is the harness's copy.

What a deployment that caches its shard in HBM does per round is what this
does: draw a contiguous window of tau minibatches from the worker's resident
partition (``MinibatchSampler``'s rule) and hand it to ``trainer.round``.  The
round donates its batch, so the window is a fresh copy made on the device
(one HBM read and write of the window a round; it shows in the trace as the
program ``jit_take``, 1.5 ms a round for CaffeNet).
"""

import collections
import math
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from benchmark import checks, flops


# a ``next_round`` that took longer than this waited for the feed
FEED_WAIT_S = 0.05


def mean_image(seed, size):
    """The mean image a deployment computes from its data, here seeded.  It
    is the same in every run whatever ``--seed`` is: the program embeds it in
    the compiled round, so a mean that moved would miss the compile cache."""
    rng = np.random.RandomState(seed)
    return rng.uniform(100.0, 150.0, (3, size, size)).astype(np.float32)


class Cell:
    def __init__(self, work, config, traffic, seed, log):
        from sparknet_tpu import config as cfg, models
        from sparknet_tpu.data import transforms
        from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh
        from sparknet_tpu.solver import Solver

        self.work, self.config, self.traffic = work, config, traffic
        self.seed, self.log = seed, log
        self.workers = traffic["workers"]
        if self.workers != work["chips"]:
            raise SystemExit(
                f"{work['name']}: traffic has {self.workers} workers, "
                f"the cell {work['chips']} chips"
            )
        self.tau = traffic["tau"]
        self.batch = config["batch_per_worker"]
        self.crop, self.stored = config["crop"], config["stored_size"]
        self.flops_per_image = flops.train_flops_per_image(config)
        self.devices = jax.devices()[: self.workers]
        self.mesh = make_mesh({"dp": self.workers}, devices=self.devices)

        model = config["program_model"]
        shapes = [(self.batch, 3, self.crop, self.crop), (self.batch,)]
        self.net_param = cfg.replace_data_layers(
            models.load_model(model, classes=config["classes"]), shapes, shapes
        )
        self.solver_param = models.load_model_solver(model).copy()
        self.mean = mean_image(traffic["mean_seed"], self.stored)
        self.solver = Solver(
            self.solver_param,
            net_param=self.net_param,
            compute_dtype=config["compute_dtype"],
            train_transform=transforms.train_transform(
                self.mean, self.crop, mirror=config["mirror"]
            ),
        )
        self.trainer = ParameterAveragingTrainer(self.solver, self.mesh)
        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self.trainer.init_state(seed=seed))
        log(f"init_state {time.perf_counter() - t0:.2f} s")
        self.next_r = 0
        self.first_losses = None

    # -- data ----------------------------------------------------------
    def seeded_minibatches(self, n):
        """``n`` minibatches of stored frames and labels a worker, made on
        each worker's own device from the seed in one jitted call:
        ``(W, n, B, 3, S, S)`` uint8 and ``(W, n, B)`` float32."""
        b, s, classes = self.batch, self.stored, self.config["classes"]

        def generate(key):
            key = jax.random.fold_in(key, lax.axis_index("dp"))

            def one(i):
                kd, kl = jax.random.split(jax.random.fold_in(key, i))
                data = jax.random.bits(kd, (b, 3, s, s), jnp.uint8)
                label = jax.random.randint(kl, (b,), 0, classes)
                return data, label.astype(jnp.float32)

            data, label = lax.map(one, jnp.arange(n))
            return data[None], label[None]

        dp = P("dp")
        return jax.jit(shard_map(
            generate, mesh=self.mesh, in_specs=P(), out_specs=(dp, dp)
        ))(jax.random.key(self.seed))

    def make_data(self):
        """The resident partition, the program that draws a round's window
        from it, and one window sampler a worker, seeded as the app seeds."""
        tau = self.tau
        if "partition_minibatches" in self.traffic:
            n = self.traffic["partition_minibatches"]
        else:
            minibatch = self.batch * 3 * self.stored * self.stored
            n = int(self.traffic["partition_gib"] * 2**30 // minibatch)
        t0 = time.perf_counter()
        self.data, self.label = jax.block_until_ready(self.seeded_minibatches(n))
        self.log(f"partition of {n} minibatches a worker in "
                 f"{time.perf_counter() - t0:.2f} s")

        def take(data, label, start):
            return {
                "data": lax.dynamic_slice_in_dim(data, start[0], tau, 1),
                "label": lax.dynamic_slice_in_dim(label, start[0], tau, 1),
            }

        dp = P("dp")
        self.take_window = jax.jit(shard_map(
            take, mesh=self.mesh, in_specs=(dp, dp, dp), out_specs=dp
        ))
        self.starts = [
            np.random.RandomState(self.seed + w) for w in range(self.workers)
        ]
        self.last_start = n - tau

    def next_round(self, r):
        start = [rng.randint(0, self.last_start + 1) for rng in self.starts]
        return self.take_window(
            self.data, self.label, np.asarray(start, np.int32)
        )

    def repeatable_batch(self):
        """One fixed round batch, made anew at each call (the round donates
        what it is given); needs no partition."""
        data, label = self.seeded_minibatches(self.tau)
        return {"data": data, "label": label}

    def sample_frames(self, n):
        """``n`` stored frames and labels of worker 0, on the host."""
        shard = self.data.addressable_shards[0].data
        labels = self.label.addressable_shards[0].data
        return np.asarray(shard[0, 0, :n]), np.asarray(labels[0, 0, :n])

    # -- checks, outside the window --------------------------------------
    def check(self):
        """The parts of ``correct`` that do not need the window.  What exists
        only across chips is checked first, before the partition fills the
        memory, so that the peak is the window's and not the check's."""
        out = {}
        if self.workers > 1:
            out.update(checks.averaging_across_workers(self))
        self.make_data()
        out.update(checks.step_against_reference(self))
        return out

    def warm(self):
        """Run the cell's one round shape until it is steady: at least
        ``warm_rounds``, and (host-fed) on until the loop has once waited for
        the feed, so that the window does not start on a prefilled queue.
        Returns the verdicts that need a first round."""
        lo, hi = self.traffic["warm_rounds"], self.traffic["warm_rounds_max"]
        r = self.next_r
        while True:
            t0 = time.perf_counter()
            batch = self.next_round(r)
            waited = time.perf_counter() - t0
            self.state, losses = self.trainer.round(
                self.state, batch, round_index=r
            )
            if self.first_losses is None:
                self.first_losses = np.asarray(losses)
            r += 1
            if r >= hi or (r >= lo and waited > FEED_WAIT_S):
                break
        jax.block_until_ready(self.state)
        self.log(f"warm-up: {r - self.next_r} rounds")
        self.next_r = r
        return {"first_loss_in_band": self.first_loss_in_band()}

    def first_loss_in_band(self):
        """The loss of the very first step, which only the initialiser and
        the data decide, against the band the configuration states."""
        band = self.config["first_loss"]
        center = math.log(self.config["classes"])
        first = float(self.first_losses[0, 0])
        ok = abs(first - center) <= band["rel_tol"] * center
        self.log(f"first step's loss {first:.4f} (first round's mean "
                 f"{float(np.mean(self.first_losses)):.4f}), band ln(classes) = "
                 f"{center:.4f} +-{band['rel_tol']:.0%}: {'ok' if ok else 'OUT'}")
        return ok

    # -- the measured window ---------------------------------------------
    def measure(self, seconds, marks):
        """Rounds until ``seconds`` have passed, two in flight: round r-1's
        losses are awaited after round r is dispatched, so the device never
        waits for the host.  Closed by ``block_until_ready``."""
        pending = collections.deque()
        losses, failed = [], 0
        r0 = r = self.next_r
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            try:
                with marks("feed.next_round"):
                    batch = self.next_round(r)
                with marks("trainer.round"):
                    self.state, out = self.trainer.round(
                        self.state, batch, round_index=r
                    )
            except Exception:  # a round that raised is a failed operation
                traceback.print_exc()
                failed += 1
                r += 1
                break
            pending.append(out)
            r += 1
            if len(pending) > 1:
                with marks("wait.losses"):
                    losses.append(np.asarray(pending.popleft()))
        with marks("drain"):
            jax.block_until_ready(self.state)
            losses.extend(np.asarray(x) for x in pending)
        t1 = time.perf_counter()
        self.next_r = r
        failed += sum(1 for x in losses if not np.all(np.isfinite(x)))
        rounds = len(losses)
        return {
            "seconds": t1 - t0,
            "attempted": r - r0, "failed": failed, "rounds": rounds,
            "tau": self.tau,
            "workers": self.workers,
            "images": rounds * self.tau * self.batch * self.workers,
            "flops_per_round_and_worker": self.tau * self.batch
            * self.flops_per_image,
            "last_loss": float(np.mean(losses[-1])) if losses else None,
        }

    def end_to_end(self, window, peaks, memory_peak_bytes):
        rate = window["images"] / window["seconds"]
        return {
            "images_per_s": rate,
            "mfu": self.flops_per_image * rate
            / (self.workers * peaks["bf16_flops_per_s"]),
            "peak_hbm_gib": memory_peak_bytes / 2**30,
        }

    def close(self):
        pass
