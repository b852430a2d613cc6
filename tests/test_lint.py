"""Hot-path invariant linter (``sparknet_tpu/analysis`` +
``tools/lint.py``): must-flag / must-pass fixture pairs per checker,
the suppression-marker grammar, the allowlist baseline semantics, and
the whole-repo ``--check`` tier-1 smoke.

Every checker gets at least one fixture that PROVES it still bites —
a gate that silently stopped flagging is worse than no gate.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from sparknet_tpu.analysis import runner
from sparknet_tpu.analysis.findings import Markers
from sparknet_tpu.analysis.hotpaths import HOT_PATHS

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan(src, hot=frozenset(), **kw):
    return runner.scan_source(textwrap.dedent(src), hot_scopes=hot, **kw)


def _checkers(rep):
    return {f.checker for f in rep.findings}


# ----------------------------------------------------------------------
# sync-in-hot-path
# ----------------------------------------------------------------------

class TestSyncChecker:
    def test_flags_every_listed_sync_kind_in_hot_scope(self):
        rep = _scan(
            """
            import jax
            import numpy as np

            def round_loop(state, losses, arr):
                a = losses.item()
                b = float(losses)
                c = int(losses)
                d = np.asarray(arr)
                e = np.array(arr)
                f = jax.device_get(arr)
                jax.block_until_ready(arr)
                arr.block_until_ready()
                return a, b, c, d, e, f
            """,
            hot={"round_loop"},
        )
        msgs = [f.message for f in rep.findings]
        assert len(msgs) == 8, msgs
        for token in (".item()", "float()", "int()", "np.asarray",
                      "np.array", "jax.device_get", "block_until_ready"):
            assert any(token in m for m in msgs), token

    def test_method_call_reductions_are_not_benign(self):
        """`float(losses.max())` is a scalar D2H sync — a leaf-name
        match on 'max' must not whitelist METHOD calls."""
        rep = _scan(
            """
            def round_loop(losses, x):
                a = float(losses.max())
                b = float(x.sum())
                c = int(x.min())
                return a, b, c
            """,
            hot={"round_loop"},
        )
        assert len(rep.findings) == 3, [f.message for f in rep.findings]

    def test_device_comparison_inside_float_is_not_benign(self):
        """float(x > 0.5) on a device value is a sync; a shape
        comparison is not."""
        rep = _scan(
            """
            def round_loop(state, losses):
                a = float(state.loss > 0.5)          # device compare
                ok = int(losses.shape[-1] == 2)      # shape compare
                return a, ok
            """,
            hot={"round_loop"},
        )
        msgs = [f.message for f in rep.findings]
        assert len(msgs) == 1 and "float()" in msgs[0], msgs

    def test_cold_scope_and_benign_reads_pass(self):
        rep = _scan(
            """
            import jax
            import numpy as np

            def setup(arr):          # NOT a hot scope: syncing is free
                return np.asarray(jax.device_get(arr))

            def round_loop(losses, r):
                tau = int(losses.shape[-1])      # shape read: no sync
                n = float(len(losses))           # len: no sync
                k = int(r.start or 0)            # slice metadata
                return tau + n + k
            """,
            hot={"round_loop"},
        )
        assert not rep.findings, [f.message for f in rep.findings]

    def test_suppression_marker_with_reason(self):
        rep = _scan(
            """
            import jax

            def round_loop(dev):
                # sparknet: sync-ok(recycle handback, overlapped)
                jax.block_until_ready(dev)
            """,
            hot={"round_loop"},
        )
        assert not rep.findings
        assert len(rep.suppressed) == 1
        assert rep.suppressed[0].reason == "recycle handback, overlapped"

    def test_marker_reason_may_contain_parentheses(self):
        """The reason captures to the line's LAST ')': '(num_workers,)
        verdict read' must survive intact into the inventory."""
        rep = _scan(
            """
            import jax

            def round_loop(bad):
                # sparknet: sync-ok(one tiny (num_workers,) verdict read)
                jax.device_get(bad)
            """,
            hot={"round_loop"},
        )
        assert not rep.findings
        assert rep.suppressed[0].reason == (
            "one tiny (num_workers,) verdict read"
        )

    def test_trailing_marker_does_not_bless_the_next_line(self):
        """A same-line marker covers ITS statement only — the next
        line's unannotated sync must still flag."""
        rep = _scan(
            """
            import jax

            def round_loop(dev, losses):
                jax.block_until_ready(dev)  # sparknet: sync-ok(handback)
                return losses.item()
            """,
            hot={"round_loop"},
        )
        assert len(rep.findings) == 1, [f.message for f in rep.findings]
        assert ".item()" in rep.findings[0].message
        assert len(rep.suppressed) == 1  # the annotated line still is

    def test_empty_marker_reason_is_its_own_finding(self):
        rep = _scan(
            """
            import jax

            def round_loop(dev):
                jax.block_until_ready(dev)  # sparknet: sync-ok()
            """,
            hot={"round_loop"},
        )
        # the sync still flags AND the empty marker flags
        assert any(f.checker == "sync-in-hot-path" for f in rep.findings)
        assert any(f.checker == "marker" for f in rep.findings)

    def test_unknown_marker_rule_flags(self):
        rep = _scan(
            """
            x = 1  # sparknet: sink-ok(typo'd rule)
            """,
        )
        assert any(
            f.checker == "marker" and "sink" in f.message
            for f in rep.findings
        )

    def test_thread_target_is_hot_by_construction(self):
        rep = _scan(
            """
            import threading
            import numpy as np

            def producer():
                return np.asarray(shared)

            t = threading.Thread(target=producer, name="p", daemon=True)
            """,
        )
        assert any(
            f.checker == "sync-in-hot-path"
            and f.scope == "producer" for f in rep.findings
        )


# ----------------------------------------------------------------------
# donation-discipline
# ----------------------------------------------------------------------

class TestDonationChecker:
    def test_straight_line_reuse_flags(self):
        rep = _scan(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(0, 1))

            def loop(state, batch):
                out = step(state, batch)
                return batch.sum()        # reuse after donation
            """,
        )
        assert any(
            f.checker == "donation-discipline" and "'batch'" in f.message
            for f in rep.findings
        ), [f.message for f in rep.findings]

    def test_loop_carried_reuse_flags(self):
        """The classic bug: batch placed once OUTSIDE the loop, donated
        every iteration — iteration 2 feeds a deleted buffer."""
        rep = _scan(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(0, 1))

            def loop(state, batch, n):
                for r in range(n):
                    state = step(state, batch)
                return state
            """,
        )
        assert any(
            f.checker == "donation-discipline" and "'batch'" in f.message
            for f in rep.findings
        ), [f.message for f in rep.findings]

    def test_rebuilt_per_iteration_passes(self):
        """The RoundFeed pattern: a fresh batch per round is clean, and
        the carried state is re-stored by the assignment."""
        rep = _scan(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(0, 1))

            def loop(state, feed, n):
                for r in range(n):
                    batch = feed(r)
                    state = step(state, batch)
                return state
            """,
        )
        assert not [
            f for f in rep.findings
            if f.checker == "donation-discipline"
        ], [f.message for f in rep.findings]

    def test_branch_local_donation_does_not_poison_the_other_branch(self):
        rep = _scan(
            """
            import jax

            step = jax.jit(lambda s: s, donate_argnums=(0,))

            def loop(state, audit):
                if audit:
                    state = step(state)
                else:
                    out = state.sum()     # other branch: state alive
                return state              # re-stored on both paths
            """,
        )
        assert not [
            f for f in rep.findings
            if f.checker == "donation-discipline"
        ], [f.message for f in rep.findings]

    def test_known_framework_donators_apply_cross_module(self):
        """`self._round` donates (state, batches) by registry even in a
        module that never constructs the jit."""
        rep = _scan(
            """
            def drive(trainer, state, batches):
                state, losses = trainer._round(state, batches, None, None)
                return batches            # donated position 1
            """,
        )
        assert any(
            f.checker == "donation-discipline" and "'batches'" in f.message
            for f in rep.findings
        )

    def test_donation_marker_suppresses(self):
        rep = _scan(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(1,))

            def loop(state, batch):
                out = step(state, batch)
                # sparknet: donation-ok(host numpy batch: jit places a fresh buffer and donates THAT)
                return batch.sum()
            """,
        )
        assert not [
            f for f in rep.findings
            if f.checker == "donation-discipline"
        ]
        assert any(
            s.checker == "donation-discipline" for s in rep.suppressed
        )


# ----------------------------------------------------------------------
# thread-hygiene
# ----------------------------------------------------------------------

class TestThreadChecker:
    def test_anonymous_and_implicit_daemon_flag(self):
        rep = _scan(
            """
            import threading

            def spawn(fn):
                t = threading.Thread(target=fn)
                t.start()
                return t
            """,
        )
        cs = _checkers(rep)
        assert "thread-hygiene/thread-anonymous" in cs
        assert "thread-hygiene/thread-daemon" in cs

    def test_named_explicit_daemon_passes(self):
        rep = _scan(
            """
            import threading

            def spawn(fn):
                return threading.Thread(
                    target=fn, name="feed-producer", daemon=True
                )
            """,
        )
        assert not rep.findings, [f.message for f in rep.findings]

    def test_untimeouted_join_outside_shutdown_flags(self):
        rep = _scan(
            """
            def await_result(worker):
                worker.join()             # mid-round wait, unbounded
            """,
        )
        assert "thread-hygiene/join-no-timeout" in _checkers(rep)

    def test_join_in_shutdown_path_or_with_timeout_passes(self):
        rep = _scan(
            """
            def stop(worker):
                worker.join()             # shutdown path: allowed

            def poll(worker):
                worker.join(timeout=5.0)  # bounded: allowed
                sep = ", ".join(["a"])    # str.join: not a thread join
                return sep
            """,
        )
        assert not rep.findings, [f.message for f in rep.findings]

    def test_join_marker_suppresses(self):
        rep = _scan(
            """
            def await_collective(p):
                # sparknet: join-ok(bounded by the in-flight collective)
                p.join()
            """,
        )
        assert not rep.findings
        assert any(s.checker.endswith("join-no-timeout")
                   for s in rep.suppressed)

    def test_bare_except_and_thread_target_swallow_flag(self):
        rep = _scan(
            """
            import threading

            def worker():
                try:
                    step()
                except Exception:
                    pass                  # swallowed in a thread target

            def anywhere():
                try:
                    step()
                except:                   # bare: flags everywhere
                    raise

            t = threading.Thread(target=worker, name="w", daemon=True)
            """,
        )
        cs = _checkers(rep)
        assert "thread-hygiene/except-swallow" in cs
        assert "thread-hygiene/except-bare" in cs

    def test_recorded_error_and_retry_continue_pass(self):
        """The Prefetcher._run pattern (record for the consumer) and
        the polite-put retry (`except Full: continue`) are clean."""
        rep = _scan(
            """
            import queue
            import threading

            def worker(holder, q):
                try:
                    step()
                except BaseException as e:
                    holder["error"] = e   # surfaced on next __next__
                while True:
                    try:
                        q.put(1, timeout=0.1)
                        break
                    except queue.Full:
                        continue

            t = threading.Thread(target=worker, name="w", daemon=True)
            """,
        )
        assert not rep.findings, [f.message for f in rep.findings]

    def test_seeded_lock_order_cycle_flags(self):
        rep = _scan(
            """
            class A:
                def ab(self):
                    with self._alock:
                        with self._block:
                            work()

                def ba(self):
                    with self._block:
                        with self._alock:
                            work()
            """,
        )
        assert "thread-hygiene/lock-order-cycle" in _checkers(rep)
        msg = next(
            f.message for f in rep.findings
            if f.checker == "thread-hygiene/lock-order-cycle"
        )
        assert "_alock" in msg and "_block" in msg

    def test_consistent_lock_order_passes(self):
        rep = _scan(
            """
            class A:
                def ab(self):
                    with self._alock:
                        with self._block:
                            work()

                def also_ab(self):
                    with self._alock:
                        with self._block:
                            other()
            """,
        )
        assert "thread-hygiene/lock-order-cycle" not in _checkers(rep)

    def test_call_propagated_cycle_flags(self):
        """One level of intra-module call propagation: `with A: self.m()`
        where m acquires B, against a direct B->A nesting elsewhere."""
        rep = _scan(
            """
            class A:
                def outer(self):
                    with self._alock:
                        self.helper()

                def helper(self):
                    with self._block:
                        work()

                def inverted(self):
                    with self._block:
                        with self._alock:
                            work()
            """,
        )
        assert "thread-hygiene/lock-order-cycle" in _checkers(rep)


# ----------------------------------------------------------------------
# registry-audit
# ----------------------------------------------------------------------

class TestRegistryAudit:
    def test_unregistered_metric_and_span_flag(self):
        rep = _scan(
            """
            def setup(registry, obs):
                c = registry.counter("sparknet_bogus_total", "nope")
                with obs.span("warp_drive"):
                    pass
            """,
            audit_registry=True,
        )
        msgs = [f.message for f in rep.findings
                if f.checker == "registry-audit"]
        assert any("sparknet_bogus_total" in m for m in msgs), msgs
        assert any("warp_drive" in m for m in msgs), msgs

    def test_canonical_names_pass_and_label_drift_flags(self):
        rep = _scan(
            """
            def setup(registry, obs):
                registry.counter("sparknet_rounds_total", "ok")
                registry.counter(
                    "sparknet_faults_total", "drifted", labels=("oops",)
                )
                with obs.span("execute"):
                    pass
                with obs.span("cache_read", cat="cache"):
                    pass
            """,
            audit_registry=True,
        )
        msgs = [f.message for f in rep.findings
                if f.checker == "registry-audit"]
        assert not any("sparknet_rounds_total" in m for m in msgs), msgs
        assert not any("'execute'" in m for m in msgs), msgs
        assert not any("cache_read" in m for m in msgs), msgs
        assert any(
            "sparknet_faults_total" in m and "label drift" in m
            for m in msgs
        ), msgs

    def test_label_drift_on_second_emitter_not_hidden_by_first(self):
        """A canon-conforming first emitter must not mask a drifted
        re-registration of the same name elsewhere."""
        rep = _scan(
            """
            def good(registry):
                registry.counter(
                    "sparknet_faults_total", "ok", labels=("kind",)
                )

            def drifted(registry):
                registry.counter("sparknet_faults_total", "bad")
            """,
            audit_registry=True,
        )
        assert any(
            "label drift" in f.message for f in rep.findings
            if f.checker == "registry-audit"
        ), [f.message for f in rep.findings]

    def test_package_emitters_match_canon_exactly(self):
        """The real repo: every emitted sparknet_* metric and span
        literal is canonical AND every canonical name is emitted —
        drift in either direction fails (this is the audit that keeps
        trace_report/docs and the emitters in one world)."""
        rep = runner.scan_package(_REPO, with_docs=False)
        audit = [f for f in rep.findings if f.checker == "registry-audit"]
        assert not audit, [f.message for f in audit]

    def test_docs_reference_complete(self):
        """ARCHITECTURE.md's telemetry reference must name every canonical
        metric and phase (the docs leg of the audit)."""
        rep = runner.scan_package(_REPO, with_docs=True)
        docs = [
            f for f in rep.findings
            if f.checker == "registry-audit" and f.scope == "<docs>"
        ]
        assert not docs, [f.message for f in docs]


# ----------------------------------------------------------------------
# runner / baseline / CLI
# ----------------------------------------------------------------------

class TestRunnerAndCLI:
    def test_hot_path_registry_names_real_scopes(self):
        """Every (module, qualname) in HOT_PATHS must exist — a rename
        that silently empties the hot set would disarm the checker."""
        import ast

        from sparknet_tpu.analysis import astutil

        for rel, quals in HOT_PATHS.items():
            path = os.path.join(_REPO, "sparknet_tpu", rel)
            assert os.path.exists(path), rel
            with open(path) as f:
                tree = ast.parse(f.read())
            funcs = set(astutil.collect_functions(tree))
            missing = set(quals) - funcs
            assert not missing, (rel, sorted(missing))

    def test_finding_keys_are_line_number_free_and_ordinal_unique(self):
        rep = _scan(
            """
            import numpy as np

            def round_loop(a, b):
                x = np.asarray(a)
                y = np.asarray(b)
                return x, y
            """,
            hot={"round_loop"},
        )
        keys = [f.key for f in rep.findings]
        assert len(keys) == len(set(keys)) == 2
        for k in keys:
            assert ":5:" not in k and ":6:" not in k  # no line numbers

    def test_donation_keys_are_line_number_free_too(self):
        """The donation message must not embed the donation line — an
        allowlisted donation finding has to survive edits above it."""
        rep = _scan(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(1,))

            def loop(state, batch):
                out = step(state, batch)
                return batch.sum()
            """,
        )
        don = [f for f in rep.findings
               if f.checker == "donation-discipline"]
        assert don and not any(
            ch.isdigit() for f in don for ch in f.key
        ), [f.key for f in don]

    def test_allowlist_waives_exact_keys_and_reports_stale(self, tmp_path):
        rep = _scan(
            """
            import numpy as np

            def round_loop(a):
                return np.asarray(a)
            """,
            hot={"round_loop"},
        )
        key = rep.findings[0].key
        entries = [
            {"key": key, "reason": "fixture baseline"},
            {"key": "sync-in-hot-path:gone.py:f:ancient", "reason": "x"},
        ]
        new, waived, stale = runner.apply_allowlist(rep, entries)
        assert not new and len(waived) == 1
        assert stale == ["sync-in-hot-path:gone.py:f:ancient"]

    def test_allowlist_entries_require_reasons(self, tmp_path):
        p = tmp_path / "allow.json"
        p.write_text(json.dumps([{"key": "k"}]))
        with pytest.raises(ValueError):
            runner.load_allowlist(str(p))

    def test_whole_repo_check_passes_tier1(self):
        """THE tier-1 guard: ``tools/lint.py --check`` over the repo
        exits 0 against the committed allowlist — and that allowlist
        stays tiny (<= 5 justified entries, the ISSUE 9 bar)."""
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "lint.py"),
             "--check"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "PYTHONPATH": _REPO},
        )
        assert out.returncode == 0, out.stdout + out.stderr
        with open(os.path.join(_REPO, "tools", "lint_allowlist.json")) as f:
            allow = json.load(f)
        assert len(allow) <= 5, allow
        for e in allow:
            assert str(e.get("reason", "")).strip(), e

    def test_cli_fails_on_new_finding(self, tmp_path):
        """Seed a hot-path violation into a scratch package copy and
        prove --check exits 1 naming it."""
        pkg = tmp_path / "sparknet_tpu"
        (pkg / "data").mkdir(parents=True)
        (pkg / "__init__.py").write_text("")
        (pkg / "data" / "__init__.py").write_text("")
        (pkg / "data" / "round_feed.py").write_text(textwrap.dedent(
            """
            import numpy as np

            class RoundFeed:
                def next_round(self, r, losses):
                    return float(losses)
            """
        ))
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "lint.py"),
             "--check", "--root", str(tmp_path), "--no-docs",
             "--allowlist", str(tmp_path / "none.json")],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "PYTHONPATH": _REPO},
        )
        assert out.returncode == 1, out.stdout + out.stderr
        assert "float()" in out.stdout and "next_round" in out.stdout

    def test_cli_show_suppressed_enumerates_annotated_sites(self):
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "lint.py"),
             "--json"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "PYTHONPATH": _REPO},
        )
        assert out.returncode == 0, out.stdout + out.stderr
        rep = json.loads(out.stdout)
        sync_sites = [
            s for s in rep["suppressed"]
            if s["checker"] == "sync-in-hot-path"
        ]
        # the framework's audited deliberate-sync inventory is there
        paths = {s["path"] for s in sync_sites}
        assert "sparknet_tpu/utils/timers.py" in paths
        assert "sparknet_tpu/data/round_feed.py" in paths
        assert all(s["reason"].strip() for s in sync_sites)
