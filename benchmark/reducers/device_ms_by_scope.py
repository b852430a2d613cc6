"""Device time under the scopes the round program names, in ms: the median
over the whole executions of the round program the trace covers, per local
step (``per: "step"``, over tau) or per round, averaged over the chips.

``phases`` are of ``transform``, ``forward``, ``backward``, ``update``,
``average``, ``unscoped``; ``types`` are layer types (``Convolution``), read
from the ``<Type>:<name>`` scopes.  None where the trace holds no whole
execution or the executable carries no scope (``benchmark/scopes.py`` says
which on a ``[bench]`` line)."""

import numpy as np

from benchmark import scopes


def reduce(ev, phases=None, types=None, per="step"):
    tables = scopes.table(ev)
    if not tables:
        return None
    over = ev["tau"] if per == "step" else 1
    medians = [
        np.median(scopes.per_execution(scoped, phases, types))
        for scoped in tables.values() if scoped
    ]
    return float(np.mean(medians) / over / 1e6) if medians else None
