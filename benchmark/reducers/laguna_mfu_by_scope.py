"""``mfu_by_scope`` for the Laguna cell: the analytic operations of the
layers of the listed ``types`` over (their device seconds x the bf16 peak),
as a share: the roofline share, compute side, of whatever kernels implement
those layers.  Device time is ``device_ms_by_scope``'s (forward + backward,
so a recomputed forward pass, the scores the backward kernels form again and
the pairs a windowed kernel meets outside its window are in the time and, by
the convention of ``mfu``, not in the operations); operations are
``benchmark/laguna_flops.py``'s for the configuration and traffic files the
metric's file names, the same count the cell's ``mfu`` uses.  None where the
trace carries no scope or the types took no time (a program without the
model's scopes, as this cell's parent)."""

from benchmark import files, laguna_flops
from benchmark.reducers import device_ms_by_scope


def reduce(ev, config, traffic, types):
    ms = device_ms_by_scope.reduce(
        ev, phases=["forward", "backward"], types=types, per="step")
    if not ms:
        return None
    entry = next(c for c in files.table()["configs"] if c["name"] == config)
    traffic = files.load_json("benchmark", "traffic", traffic + ".json")
    by_type = laguna_flops.train_flops_per_sequence_by_type(
        files.load_json(entry["file"]), traffic["seq_len"])
    flops = traffic["sequences_per_step"] * sum(by_type[t] for t in types)
    return float(flops / (ms / 1e3 * ev["peaks"]["bf16_flops_per_s"]))
