"""Traffic kind ``lm-train-resident``: tau-averaging rounds of a sequence
model over a token partition that lives in the chip's memory.

The cell drives what ``apps/lm_app.py`` builds for ``--model_config``
(``lm_app.build_hybrid_lm_solver``: ``models/hybrid_lm.HybridMoELM`` in a
``Solver(net=..., compute_dtype=...)`` with ADAM), ``make_mesh({"dp": W})``,
``ParameterAveragingTrainer`` at its defaults and ``trainer.init_state``.
Warm-up, the measured loop (``trainer.round``, two in flight) and the
end-to-end arithmetic are ``train_resident.Cell``'s, with a sequence where
that has an image: ``batch`` is the sequences a step, ``flops_per_image`` the
operations to train on one sequence (``benchmark/lm_flops.py``), so
``images_per_s`` counts sequences of ``seq_len`` tokens a second, and a
``[bench]`` line gives tokens a second.

The partition is ``partition_sequences`` rows of ``seq_len + 1`` token ids a
worker, Zipf over the configuration's vocabulary slice, made on the device
from ``--seed``; a round is a contiguous window of ``tau *
sequences_per_step`` rows (``MinibatchSampler``'s rule), tokens and the same
rows shifted by one as targets, copied fresh because the round donates it.
"""

import time

import jax
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from benchmark import lm_checks, lm_flops
from benchmark.kinds import train_resident


# the least window, in rounds of the cell's own time (see ``measure``)
WINDOW_ROUNDS = 2.2


class Cell(train_resident.Cell):
    def __init__(self, work, config, traffic, seed, log):
        from sparknet_tpu.apps import lm_app
        from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh

        # ``train_resident.Cell.first_loss_in_band`` centres its band on
        # ln(classes): here the classes are the vocabulary's rows
        config = {**config, "classes": config["vocab_size"]}
        self.work, self.config, self.traffic = work, config, traffic
        self.seed, self.log = seed, log
        self.workers = traffic["workers"]
        if self.workers != work["chips"]:
            raise SystemExit(
                f"{work['name']}: traffic has {self.workers} workers, "
                f"the cell {work['chips']} chips"
            )
        self.tau = traffic["tau"]
        self.seq_len = traffic["seq_len"]
        self.batch = traffic["sequences_per_step"]
        self.flops_by_type = lm_flops.train_flops_per_sequence_by_type(
            config, self.seq_len)
        self.flops_per_image = sum(self.flops_by_type.values())
        self.devices = jax.devices()[: self.workers]
        self.mesh = make_mesh({"dp": self.workers}, devices=self.devices)
        self.model, self.solver = lm_app.build_hybrid_lm_solver(config)
        self.trainer = ParameterAveragingTrainer(self.solver, self.mesh)
        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self.trainer.init_state(seed=seed))
        log(f"init_state {time.perf_counter() - t0:.2f} s; "
            f"{self.model.num_params()} parameters, "
            f"{self.flops_per_image / 1e12:.4f} TFLOP a sequence trained")
        self.next_r = 0
        self.first_losses = None

    # -- data ----------------------------------------------------------
    def make_data(self):
        n, t = self.traffic["partition_sequences"], self.seq_len
        rows = self.tau * self.batch
        vocab, zipf = self.config["vocab_size"], self.traffic["zipf_exponent"]

        def generate(key):
            key = jax.random.fold_in(key, lax.axis_index("dp"))
            return lm_checks.zipf_tokens(key, (1, n, t + 1), vocab, zipf)

        dp = P("dp")
        t0 = time.perf_counter()
        self.data = jax.block_until_ready(jax.jit(shard_map(
            generate, mesh=self.mesh, in_specs=P(), out_specs=dp
        ))(jax.random.key(self.seed)))
        self.log(f"partition of {n} sequences of {t + 1} ids a worker in "
                 f"{time.perf_counter() - t0:.2f} s")

        def take(data, start):
            window = lax.dynamic_slice_in_dim(data, start[0], rows, 1)
            window = window.reshape(1, self.tau, self.batch, t + 1)
            return {"tokens": window[..., :-1], "targets": window[..., 1:]}

        self.take_window = jax.jit(shard_map(
            take, mesh=self.mesh, in_specs=(dp, dp), out_specs=dp
        ))
        # numpy's generator takes 32 bits; a seed may be any whole number
        self.starts = [
            np.random.RandomState((self.seed + w) % 2**32)
            for w in range(self.workers)
        ]
        self.last_start = n - rows

    def next_round(self, r):
        start = [rng.randint(0, self.last_start + 1) for rng in self.starts]
        return self.take_window(self.data, np.asarray(start, np.int32))

    # -- checks, outside the window --------------------------------------
    def check(self):
        """The comparisons with the plain reference first, while the chip
        holds only the training state; then the partition."""
        plants = lm_checks.planted(self)
        out = lm_checks.forward_against_reference(self, plants)
        out.update(lm_checks.step_against_reference(self, plants))
        out.update(lm_checks.float32_parts(self, plants))
        self.make_data()
        lm_checks.routing(self, self.next_round(0)["tokens"][0, 0],
                          "before the first round")
        return out

    def warm(self):
        """``train_resident``'s warm-up, then one more round, timed."""
        out = super().warm()
        t0 = time.perf_counter()
        self.state, _ = self.trainer.round(
            self.state, self.next_round(self.next_r), round_index=self.next_r)
        jax.block_until_ready(self.state)
        self.round_s = time.perf_counter() - t0
        self.next_r += 1
        self.log(f"one warm round alone: {self.round_s:.3f} s")
        return out

    def measure(self, seconds, marks):
        # the trace's evidence opens at the loop's third round and needs a
        # whole round after it; the loop starts round k once round k - 2 is
        # back, so a window under two rounds' time would end at two
        return super().measure(
            max(seconds, WINDOW_ROUNDS * self.round_s), marks)

    def end_to_end(self, window, peaks, memory_peak_bytes):
        out = super().end_to_end(window, peaks, memory_peak_bytes)
        # the router has trained for every round so far: how near the
        # grouped expert path's rows the held experts' load has drifted
        lm_checks.routing(self, self.next_round(self.next_r)["tokens"][0, 0],
                          f"after round {self.next_r}")
        self.log(f"{out['images_per_s'] * self.seq_len:.1f} tokens a second "
                 f"({out['images_per_s']:.4f} sequences of {self.seq_len} "
                 f"tokens a second, reported as images_per_s)")
        return out
