"""Time of the collective operations per round on the first chip, in ms: the
union of the synchronous ones' intervals and of the asynchronous ones' spans
from start to done; with ``exposed`` only the part during which no other
operation runs there."""

import numpy as np

from benchmark import evidence, xplane


def collective_mask(events):
    return np.array(
        [bool(xplane.COLLECTIVE.match(xplane.opcode(n))) for n in events.names],
        bool,
    )


def reduce(ev, exposed=False):
    d = ev["devices"][0]
    ops, spans = evidence.ops_of(ev, d), ev["trace"].devices[d]["async"]
    is_coll = collective_mask(ops)
    coll = np.concatenate([
        ops.select(is_coll).intervals(),
        spans.select(collective_mask(spans)).intervals(),
    ])
    if not len(coll) or ev["rounds_on_device"][d] <= 0:
        return None
    coll = xplane.merged(coll, ev["window_ns"])
    if exposed:
        others = ops.select(xplane.leaf_mask(ops) & ~is_coll)
        coll = xplane.minus(coll, others.intervals())
    return float(xplane.covered(coll) / 1e6 / ev["rounds_on_device"][d])
