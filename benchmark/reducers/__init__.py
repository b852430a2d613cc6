"""Readers of per-layer metrics.  Each module has ``reduce(ev, **args)``:
``ev`` is what ``benchmark.evidence.collect`` returned, ``args`` the metric's
own file (``benchmark/layer_metrics/<metric>.json``, key ``args``).  A reader
that finds nothing to read returns None and the metric is left out."""
