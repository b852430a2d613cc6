"""The window's analytic operations over (device busy seconds x bf16 peak), as a
share: the compute side of the roofline for the whole step taken as one
kernel.  Unlike ``mfu`` it does not fall when the device waits."""

import numpy as np


def reduce(ev):
    peak = ev["peaks"]["bf16_flops_per_s"]
    shares = [
        rounds * ev["flops_per_round"] / (ev["busy_s"][d] * peak)
        for d, rounds in ev["rounds_on_device"].items() if ev["busy_s"][d] > 0
    ]
    return float(np.mean(shares)) if shares else None
