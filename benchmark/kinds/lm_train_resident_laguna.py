"""Traffic kind ``lm-train-resident-laguna``: ``lm-train-resident`` for the
Laguna-XS.2 configuration.

Everything the window times and everything ``end_to_end`` computes is
``lm_train_resident.Cell``'s (and, under it, ``train_resident.Cell``'s): the
partition in HBM, the window drawn on the device, the rounds.  What differs
is named by this kind: the operation count is ``benchmark/laguna_flops.py``'s
(a layer's own heads, the sliding layers' keys within their window);
``check()`` runs ``benchmark/laguna_checks.py``'s (``lm_checks``' forward,
step and ``step_exact`` against ``reference/laguna.py``, LFM2's router
comparison, the windowed kernels against the reference's masked softmax);
the routing readings take the selection biases from the state's ``stats``;
and one verdict is taken after the window, the held experts' load in its
last step.  ``__init__`` repeats the parent's, which calls ``lm_flops``
before anything else and so cannot be called.
"""

import time

import jax

from benchmark import laguna_checks, laguna_flops, lfm2_checks
from benchmark.kinds import lm_train_resident, train_resident


def parameters_by_part(model):
    """{part: parameters}: each layer's mixer by its kind, dense MLPs, routed
    blocks (router, held experts, shared expert), and embedding, head and
    final norm; a layer's two norms under ``norms``."""
    import numpy as np

    kinds = model.config["mixers"]
    out = {}
    for group, shapes in model._group_blobs:
        layer, _, part = group.partition("_")
        if part == "mixer":
            part = kinds[int(layer[1:])]
        elif part in ("router", "experts", "shared"):
            part = "routed_block"
        elif part in ("n1", "n2"):
            part = "norms"
        elif part != "mlp":
            part = "embedding_head_final_norm"
        out[part] = out.get(part, 0) + int(sum(np.prod(s) for s in shapes))
    return out


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
        self.flops_by_type = laguna_flops.train_flops_per_sequence_by_type(
            config, self.seq_len)
        self.flops_per_image = sum(self.flops_by_type.values())
        self.devices = jax.devices()[: self.workers]
        self.mesh = make_mesh({"dp": self.workers}, devices=self.devices)
        self.model, self.solver = lm_app.build_hybrid_lm_solver(config)
        self.trainer = ParameterAveragingTrainer(self.solver, self.mesh)
        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self.trainer.init_state(seed=seed))
        log(f"init_state {time.perf_counter() - t0:.2f} s; "
            f"{self.model.num_params()} parameters, by part "
            f"{parameters_by_part(self.model)}, "
            f"{self.flops_per_image / 1e12:.4f} TFLOP a sequence trained; "
            f"by layer type { {k: round(v / 1e12, 4) for k, v in self.flops_by_type.items() if v} }")
        self.next_r = 0
        self.first_losses = None

    def check(self):
        """The comparisons with the plain reference first, while the chip
        holds only the training state; then the partition."""
        plants = laguna_checks.planted(self)
        out = {}
        for part in laguna_checks.PARTS.values():
            out.update(part(self, plants))
        self.make_data()
        lfm2_checks.routing(self, self.next_round(0)["tokens"][0, 0],
                            "before the first round")
        # ``run.py`` takes ``correct`` from this dict after ``end_to_end``,
        # where the window's own verdict joins it
        self.verdict = out
        return out

    def end_to_end(self, window, peaks, memory_peak_bytes):
        """``train_resident``'s arithmetic, then the routing the window
        left: the last step's load of the held experts (a verdict), and
        where the next step's tokens would go."""
        out = train_resident.Cell.end_to_end(
            self, window, peaks, memory_peak_bytes)
        self.verdict.update(lfm2_checks.held_load_in_window(self))
        lfm2_checks.routing(
            self, self.next_round(self.next_r)["tokens"][0, 0],
            f"after round {self.next_r}")
        self.log(f"{out['images_per_s'] * self.seq_len:.1f} tokens a second "
                 f"({out['images_per_s']:.4f} sequences of {self.seq_len} "
                 f"tokens a second, reported as images_per_s)")
        return out
