"""What the program's own account says of its set-up and its memory: the
build records and memory marks ``sparknet_tpu.obs`` keeps in THIS process
whether or not a sink is installed (``obs.programs()``, ``obs.memory_marks()``)
and its gauge ``sparknet_device_memory_bytes{kind}``, read when the reader runs,
after the window.  Not ``ev``: the harness's tracer is installed around the
window only, and a program is built before it.

A program that keeps no such account (the parent of the PR that added it), a
backend that reports no memory (the CPU) or a record that is not there reads
as nothing: the reader returns None and the metric is left out."""


PER = {"B": 1, "MiB": 2**20, "GiB": 2**30}


def _account():
    try:
        from sparknet_tpu import obs
    except ImportError:
        return None
    return obs if hasattr(obs, "programs") else None


def _newest(rows, key, value):
    return next((r for r in reversed(rows) if r.get(key) == value), None)


def record(obs, program, field, per="B"):
    """``field`` of the newest record named ``program`` (the window's shape,
    where a check has built a smaller one first), in seconds or ``per``."""
    row = _newest(obs.programs(), "program", program)
    if row is None or row.get(field) is None:
        return None
    return row[field] / PER[per]


def cache_hit_share(obs):
    """Records served by the persistent cache over all records of the run."""
    rows = obs.programs()
    if not rows:
        return None
    return sum(r["cache"] == "hit" for r in rows) / len(rows)


def _init_state_marks(obs):
    marks = obs.memory_marks()
    done = _newest(marks, "at", "init_state")
    if done is None:
        return None
    entered = _newest(marks[: marks.index(done)], "at", "init_state:enter")
    return entered and (entered, done)


def init_state_s(obs):
    """Host seconds from ``trainer.init_state``'s entry to its return."""
    pair = _init_state_marks(obs)
    return pair and pair[1]["t_s"] - pair[0]["t_s"]


def state(obs, per="B"):
    """What ``init_state`` left on the fullest chip: ``in_use`` at its return
    less ``in_use`` at its entry."""
    pair = _init_state_marks(obs)
    if not pair or pair[0]["in_use"] is None or pair[1]["in_use"] is None:
        return None
    return (pair[1]["in_use"] - pair[0]["in_use"]) / PER[per]


def gauge(obs, kind, per="B"):
    """The program's ``sparknet_device_memory_bytes{kind}`` as a scrape
    would read it now (the peaks are maxima over the process)."""
    tm = obs.training_metrics() or obs.enable_training_metrics()
    value = tm.device_memory.labels(kind).value
    return value / PER[per] if value else None


_READERS = {
    "record": record, "cache_hit_share": cache_hit_share,
    "init_state_s": init_state_s, "state": state, "gauge": gauge,
}


def reduce(ev, what, **args):
    obs = _account()
    if obs is None:
        return None
    value = _READERS[what](obs, **args)
    return None if value is None else float(value)
