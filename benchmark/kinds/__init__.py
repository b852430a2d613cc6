"""Traffic kinds.  A traffic file names its kind; ``run.py`` imports
``benchmark.kinds.<kind with _ for ->`` and knows nothing else about a cell:

``Cell(work, config, traffic, seed, log)``, then ``check()`` and ``warm()``
(each returns ``{verdict: bool}``), ``measure(seconds, marks)`` (the window:
``attempted``, ``failed``, ``seconds`` and what the readers want),
``end_to_end(window, peaks, memory_peak_bytes)`` and ``close()``; ``devices``
are the chips it uses."""
