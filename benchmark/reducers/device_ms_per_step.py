"""Device busy time per local step, in ms: the union of the operations'
intervals in the window over the steps the device ran in it (executions of the
round program, whole and part, times tau), averaged over the chips."""

import numpy as np


def reduce(ev):
    per_step = [
        ev["busy_s"][d] / (rounds * ev["tau"])
        for d, rounds in ev["rounds_on_device"].items() if rounds > 0
    ]
    return float(np.mean(per_step) * 1e3) if per_step else None
