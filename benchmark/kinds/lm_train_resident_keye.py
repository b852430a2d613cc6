"""Traffic kind ``lm-train-resident-keye``: ``lm-train-resident`` for the
Keye-VL-2.0 configuration.

Everything the window times and everything ``end_to_end`` computes is
``lm_train_resident.Cell``'s (and, under it, ``train_resident.Cell``'s): the
partition in HBM, the window drawn on the device, the rounds.  What differs
is named by this kind because the accepted kind names Qwen3-Next's in its
first lines: the operation count is ``benchmark/keye_flops.py``'s; in place
of the delta rule's two comparisons ``check()`` runs
``benchmark/keye_checks.py``'s (the learned selection against ``lax.top_k``,
the masked attention given the reference's selection, the separation of the
two losses); the step comparison is ``lm_checks``' own on a reference that
carries both losses (``keye_checks.step_view``); one forward pass gives the
routing's and the indexer's gauges for ``[bench]`` lines; one verdict is
taken after the window, the held experts' load; and this cell's own programs
and its round compile on threads of their own beside the accepted checks
(a run's set-up was 13 minutes with one compile after another).  ``__init__`` repeats the parent's, which
calls ``lm_flops`` before anything else and so cannot be called.
"""

import time

import jax
import numpy as np

from benchmark import keye_checks, keye_flops, lm_checks
from benchmark.kinds import lm_train_resident, train_resident


class Cell(lm_train_resident.Cell):
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
        self.flops_by_type = keye_flops.train_flops_per_sequence_by_type(
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
            f"{self.flops_per_image / 1e12:.4f} TFLOP a sequence trained; "
            f"by layer type { {k: round(v / 1e12, 4) for k, v in self.flops_by_type.items() if v} }")
        self.next_r = 0
        self.first_losses = None

    def check(self):
        """The partition first (67 MB), so that this cell's own programs and
        its round can compile on threads of their own
        (``keye_checks.start``) while the comparisons with the plain
        reference compile and run, the accepted ones first."""
        plants = keye_checks.planted(self)
        self.make_data()
        # a window like a round's, drawn without touching the rounds' draws
        like = self.take_window(
            self.data, np.zeros((self.workers,), np.int32))
        keye_checks.start(self, plants, like)
        out = {}
        for part in keye_checks.PARTS.values():
            out.update(part(self, plants))
        first = self.next_round(0)["tokens"][0, 0]
        keye_checks.selection_gauges(self, first, "before the first round")
        self.round_ahead.result()
        self.log(f"the round program compiled in {self.round_ahead.seconds:.1f}"
                 f" s on a thread of its own")
        # ``run.py`` takes ``correct`` from this dict after ``end_to_end``,
        # where the window's own verdict joins it
        self.verdict = out
        return out

    def end_to_end(self, window, peaks, memory_peak_bytes):
        """``train_resident``'s arithmetic, then what the window's training
        left: where the next step's tokens would go (a verdict), and the
        indexer's gauges."""
        out = train_resident.Cell.end_to_end(
            self, window, peaks, memory_peak_bytes)
        after = self.next_round(self.next_r)["tokens"][0, 0]
        self.verdict.update(keye_checks.held_load(
            self, keye_checks.selection_gauges(
                self, after, f"after round {self.next_r}")))
        self.log(f"{out['images_per_s'] * self.seq_len:.1f} tokens a second "
                 f"({out['images_per_s']:.4f} sequences of {self.seq_len} "
                 f"tokens a second, reported as images_per_s)")
        return out
