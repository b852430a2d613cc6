"""Mean duration, in ms, of the spans named ``span`` that closed inside the
window: the program's own (``obs.span``) or the harness's marks."""

import numpy as np


def reduce(ev, span):
    rows = {**ev["marks"], **ev["spans"]}.get(span)
    if rows is None:
        return None
    t0, t1 = ev["window_ns"]
    rows = rows[(rows[:, 0] >= t0) & (rows[:, 1] <= t1)]
    if not len(rows):
        return None
    return float(np.mean(rows[:, 1] - rows[:, 0]) / 1e6)
