"""Framework-aware static analysis: the hot-path invariant linter.

The repo's perf story (PIPELINE's 0.97 overlap, PROFILE's live hidden
fractions, COMM's overlapped collectives, SERVE's zero recompiles)
rests on invariants no runtime test names when they break: no implicit
host<->device syncs in steady-state rounds, no reuse of donated
buffers, disciplined threading across the modules that spawn
producer/comm/watchdog/server threads, and emitter/folder agreement on
every metric and span name.  This package enforces them statically —
each checker is a small AST visitor emitting the shared
:class:`findings.Finding` shape — and ``tools/lint.py --check`` runs
the set against a committed allowlist as a tier-1 guard (the dynamic
half is ``tests/test_parallel.py``'s steady rounds under an armed
transfer guard).

Checkers
--------
- ``sync_check``     — sync-in-hot-path: ``.item()``, ``float()``/
  ``int()`` on non-shape values, ``np.asarray``/``np.array``,
  ``jax.device_get``, ``block_until_ready`` inside the registered
  round-loop/producer/comm scopes (``hotpaths.HOT_PATHS``) and inside
  any function spawned as a thread target.
- ``donation_check`` — donation discipline: a name used again after
  being passed in a donated position of a ``jax.jit(...,
  donate_argnums=...)`` callable (including across loop iterations,
  the classic reuse bug).
- ``thread_check``   — thread hygiene: anonymous threads, implicit
  daemon policy, un-timeouted ``join()`` outside shutdown paths, bare
  or swallowed ``except`` in thread targets, and a cross-module lock
  acquisition-order graph with cycle detection.
- ``registry_audit`` — trace/metrics registry drift: every emitted
  ``sparknet_*`` metric name and phase-cat ``span(...)`` literal must
  appear in the canonical sets (``analysis.registry``) consumed by
  ``tools/trace_report.py`` and ARCHITECTURE.md, and vice versa.

Suppression marker grammar (see ARCHITECTURE.md "Static analysis &
sanitizers"): an inline ``# sparknet: <rule>-ok(<reason>)`` comment on
any line of the flagged statement suppresses that checker's finding
there — ``sync-ok``, ``donation-ok``, ``thread-ok``, ``join-ok``,
``except-ok``, ``lock-ok``.  The reason is mandatory; an empty one is
itself a finding.  Suppressed sites stay enumerable
(``Report.suppressed``).
"""

from sparknet_tpu.analysis.findings import Finding, Report  # noqa: F401
from sparknet_tpu.analysis.runner import scan_package, scan_source  # noqa: F401
