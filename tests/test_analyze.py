"""pilosa-lint gate: the full-package sweep pins ZERO unsuppressed
findings (tier-1 — pure AST, no device, milliseconds), and each of the
six passes is proven against a seeded violation reproducing the
historical bug class it encodes (ISSUE 8; the PR-6 unlocked
``row_ids()``, the PR-5/6 generation hand-audits, the PR-6
free-running-batch-shape recompile convoy, the [ingest]
config-restore rounds, and the metric-family live-check gap)."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

from tools.analyze import core
from tools.analyze import passes_config, passes_device, passes_locks, \
    passes_metrics, passes_mutation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "pilosa_tpu")


def _analyze(src: str, path: str, passes) -> list:
    sf = core.SourceFile.parse(path, textwrap.dedent(src))
    return core.analyze_sources([sf], passes=passes)


def _active(findings, rule=None):
    return [f for f in findings if not f.suppressed
            and (rule is None or f.rule == rule)]


# --------------------------------------------------------------- the gate


class TestZeroFindingBaseline:
    def test_package_sweep_is_clean(self):
        """THE gate: all six passes over pilosa_tpu/ — zero
        unsuppressed findings on the committed tree."""
        findings = core.analyze_paths([PKG])
        bad = _active(findings)
        assert not bad, "unsuppressed findings:\n" + "\n".join(
            f.render() for f in bad)

    def test_every_suppression_carries_a_reason(self):
        findings = core.analyze_paths([PKG])
        for f in findings:
            if f.suppressed:
                assert f.reason and f.reason.strip(), f.render()

    def test_cli_exits_zero_on_clean_tree(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "pilosa_tpu"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 finding(s)" in proc.stdout

    def test_cli_json_mode(self):
        import json

        proc = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--json",
             "pilosa_tpu"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        doc = json.loads(proc.stdout)
        assert doc["unsuppressed"] == 0
        assert all({"rule", "path", "line", "message"} <= set(f)
                   for f in doc["findings"])


# ------------------------------------------------------ P1 lock-discipline


class TestLockDiscipline:
    PASSES = (passes_locks.LockDisciplinePass(),)

    def test_pr6_unlocked_row_ids_fires(self):
        """The historical bug verbatim: PR 6 round 1 shipped
        ``row_ids()`` iterating ``_rows`` without the fragment lock —
        the background compactor mutates ``_rows``/``_delta``
        mid-read ("dictionary changed size during iteration")."""
        findings = _analyze("""
            class Fragment:
                def row_ids(self):
                    return sorted(r for r, a in self._rows.items()
                                  if a.any())
        """, "models/fragment.py", self.PASSES)
        assert _active(findings, "lock-discipline"), findings

    def test_locked_row_ids_is_clean(self):
        findings = _analyze("""
            class Fragment:
                def row_ids(self):
                    with self._lock:
                        return sorted(self._rows)
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)

    def test_locked_helper_contract_is_honored(self):
        findings = _analyze("""
            class Fragment:
                def _bit_off_locked(self, row):
                    return self._rows.get(row)
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)

    def test_cross_object_access_requires_owner_lock(self):
        src = """
            def sweep(frag):
                return list(frag._rows)
        """
        findings = _analyze(src, "parallel/executor.py", self.PASSES)
        assert _active(findings, "lock-discipline")
        findings = _analyze("""
            def sweep(frag):
                with frag._lock:
                    return list(frag._rows)
        """, "parallel/executor.py", self.PASSES)
        assert not _active(findings)

    def test_monotone_token_reads_are_exempt_writes_are_not(self):
        # reads of the monotone ints are the lock-free stamp path
        findings = _analyze("""
            def stamp(fr):
                return (fr._uid, fr._gen, fr._delta_seq)
        """, "parallel/executor.py", self.PASSES)
        assert not _active(findings)
        findings = _analyze("""
            def corrupt(fr):
                fr._gen += 1
        """, "parallel/executor.py", self.PASSES)
        assert _active(findings, "lock-discipline")

    def test_module_global_counters(self):
        findings = _analyze("""
            _counters = {"tape.executions": 0}
            def bump(name):
                _counters[name] += 1
        """, "ops/tape.py", self.PASSES)
        assert _active(findings, "lock-discipline")
        findings = _analyze("""
            _counters = {"tape.executions": 0}
            def bump(name):
                with _lock:
                    _counters[name] += 1
        """, "ops/tape.py", self.PASSES)
        assert not _active(findings)


# ----------------------------------------------------- P2 generation-audit


class TestGenerationAudit:
    PASSES = (passes_mutation.GenerationAuditPass(),)

    def test_mutation_without_bump_fires(self):
        """The PR-5 hand-audit class: a mutation path that never
        bumps leaves stale result-cache entries servable forever."""
        findings = _analyze("""
            class Fragment:
                def clear_row(self, row):
                    with self._lock:
                        arr = self._rows.pop(row, None)
                        return arr is not None
        """, "models/fragment.py", self.PASSES)
        assert _active(findings, "generation-audit"), findings

    def test_direct_bump_is_clean(self):
        findings = _analyze("""
            class Fragment:
                def clear_row(self, row):
                    with self._lock:
                        self._rows.pop(row, None)
                        self._gen += 1
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)

    def test_transitive_bump_through_helper_is_clean(self):
        findings = _analyze("""
            class Fragment:
                def _flush(self):
                    self._rows[0] = None
                    self._gen += 1
                def snapshot(self):
                    self._flush()
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)

    def test_delta_write_without_seq_bump_fires(self):
        findings = _analyze("""
            class Fragment:
                def set_bit(self, row, off):
                    self._delta_or_new().add_bit(row, off, False, 0)
        """, "models/fragment.py", self.PASSES)
        assert _active(findings, "generation-audit")
        findings = _analyze("""
            class Fragment:
                def set_bit(self, row, off):
                    self._delta_seq += 1
                    self._delta_or_new().add_bit(
                        row, off, False, self._delta_seq)
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)

    def test_real_fragment_regression_is_caught(self):
        """Anti-rot for the pass itself: strip the ``_gen`` bump out
        of the LIVE fragment.py's ``clear_value`` and the sweep must
        fire — proof the audit holds the real file, not just
        fixtures."""
        with open(os.path.join(PKG, "models", "fragment.py")) as fh:
            src = fh.read()
        assert src.count("self._bump_gen()") >= 5
        # clear_value: the one-bump method with no transitive bump
        broken = src.replace(
            "self._wal_append(_WAL_REC.pack(_WAL_CLEAR, "
            "bsi_ops.EXISTS_PLANE, off))\n                "
            "self._op_n += 1\n                self._bump_gen()",
            "self._wal_append(_WAL_REC.pack(_WAL_CLEAR, "
            "bsi_ops.EXISTS_PLANE, off))\n                "
            "self._op_n += 1")
        assert broken != src, "edit anchor drifted"
        sf = core.SourceFile.parse("models/fragment.py", broken)
        findings = core.analyze_sources([sf], passes=self.PASSES)
        hits = [f for f in _active(findings, "generation-audit")
                if "clear_value" in f.message]
        assert hits, findings
        # and the unbroken file is clean
        sf = core.SourceFile.parse("models/fragment.py", src)
        clean = core.analyze_sources([sf], passes=self.PASSES)
        assert not _active(clean, "generation-audit")

    def test_registry_exempt_method_is_skipped(self):
        findings = _analyze("""
            class Fragment:
                def _replay_wal_file(self, path):
                    self._apply_set(1, 2)
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)


# ------------------------------------------------- P3 blocking-under-lock


class TestBlockingUnderLock:
    PASSES = (passes_locks.BlockingUnderLockPass(),)

    def test_sleep_under_lock_fires(self):
        findings = _analyze("""
            import time
            class Compactor:
                def stop(self):
                    with self._lock:
                        self._thread.join(timeout=5)
        """, "ingest/compactor.py", self.PASSES)
        assert _active(findings, "blocking-under-lock"), findings

    def test_join_outside_lock_is_clean(self):
        """The committed compactor shape: snapshot the thread under
        the lock, join OUTSIDE it."""
        findings = _analyze("""
            class Compactor:
                def stop(self):
                    with self._lock:
                        thread = self._thread
                        self._thread = None
                    if thread is not None:
                        thread.join(timeout=5)
        """, "ingest/compactor.py", self.PASSES)
        assert not _active(findings)

    def test_str_join_and_condition_wait_are_exempt(self):
        findings = _analyze("""
            class Fragment:
                def close(self):
                    with self._lock:
                        name = ", ".join(["a", "b"])
                        self._snap_done.wait(timeout=1.0)
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)

    def test_device_dispatch_under_lock_fires(self):
        findings = _analyze("""
            class Holder:
                def upload(self, m):
                    with self._lock:
                        return bm.device_put(m)
        """, "models/holder.py", self.PASSES)
        assert _active(findings, "blocking-under-lock")

    def test_future_result_under_lock_fires(self):
        findings = _analyze("""
            class C:
                def flush(self):
                    with self._lock:
                        return self.fut.result()
        """, "parallel/coalescer.py", self.PASSES)
        assert _active(findings, "blocking-under-lock")


# -------------------------------------------------- P4 recompile-hazard


class TestRecompileHazard:
    PASSES = (passes_device.RecompileHazardPass(),)

    def test_pr6_free_running_batch_fires(self):
        """The PR-6 convoy verbatim: stacking a free-running number
        of queries and dispatching the jitted program — every novel
        occupancy paid a serving-path XLA compile."""
        findings = _analyze("""
            import jax.numpy as jnp
            from pilosa_tpu.ops import expr
            def flush(live):
                stacked = jnp.stack([it.leaves for it in live])
                return expr.evaluate(("leaf", 0), (stacked,),
                                     counts=True)
        """, "parallel/coalescer.py", self.PASSES)
        assert _active(findings, "recompile-hazard"), findings

    def test_pow2_padded_batch_is_clean(self):
        findings = _analyze("""
            import jax.numpy as jnp
            from pilosa_tpu.ops import expr
            def flush(live):
                stacked = jnp.stack([it.leaves for it in live])
                pad = _pow2(len(live)) - len(live)
                if pad:
                    stacked = _pad_batch(stacked, pad)
                return expr.evaluate(("leaf", 0), (stacked,),
                                     counts=True)
        """, "parallel/coalescer.py", self.PASSES)
        assert not _active(findings)

    def test_static_literal_stack_is_clean(self):
        findings = _analyze("""
            import jax.numpy as jnp
            from pilosa_tpu.ops import expr
            def pair(a, b):
                stacked = jnp.stack([a, b])
                return expr.evaluate(("leaf", 0), (stacked,))
        """, "parallel/coalescer.py", self.PASSES)
        assert not _active(findings)

    def test_import_time_jnp_fires(self):
        findings = _analyze("""
            import jax.numpy as jnp
            _ZEROS = jnp.zeros(1024)
        """, "ops/bitmap.py", self.PASSES)
        assert _active(findings, "recompile-hazard")

    def test_jit_decorator_at_import_is_clean(self):
        findings = _analyze("""
            import jax
            @jax.jit
            def _jit_and(a, b):
                return a & b
        """, "ops/bitmap.py", self.PASSES)
        assert not _active(findings)


# ---------------------------------------------------- P5 config-baseline


class TestConfigBaseline:
    PASSES = (passes_config.ConfigBaselinePass(),)

    def test_configure_without_baseline_fires(self):
        """The PR-6 rounds 4-5 class: a call site flips the
        process-wide [ingest] config and never restores it."""
        findings = _analyze("""
            from pilosa_tpu import ingest
            def open_server():
                ingest.configure(delta_enabled=True)
        """, "server/server.py", self.PASSES)
        assert _active(findings, "config-baseline"), findings

    def test_configure_with_baseline_pair_is_clean(self):
        findings = _analyze("""
            from pilosa_tpu import ingest
            def open_server():
                ingest.capture_baseline()
                ingest.configure(delta_enabled=True)
            def close_server():
                ingest.restore_baseline()
        """, "server/server.py", self.PASSES)
        assert not _active(findings)

    def test_config_alias_attribute_write_fires(self):
        findings = _analyze("""
            from pilosa_tpu import ingest
            def tweak():
                cfg = ingest.config()
                cfg.delta_enabled = True
        """, "server/server.py", self.PASSES)
        assert _active(findings, "config-baseline")

    def test_retain_without_release_fires(self):
        findings = _analyze("""
            from pilosa_tpu.ingest import compactor
            def open_server():
                compactor.retain()
        """, "server/server.py", self.PASSES)
        assert _active(findings, "config-baseline")

    def test_owner_module_is_exempt(self):
        findings = _analyze("""
            def configure(**kw):
                pass
            def _self_test():
                configure(delta_enabled=True)
        """, "ingest/__init__.py", self.PASSES)
        assert not _active(findings)


# ------------------------------------------------ P6 metric-family drift


class TestMetricFamilyDrift:
    PASSES = (passes_metrics.MetricFamilyDriftPass(),)

    def test_undeclared_family_fires(self):
        findings = _analyze("""
            class C:
                def publish(self):
                    self.stats.gauge("bogus.thing", 1)
        """, "pilosa_tpu/newmod.py", self.PASSES)
        hits = [f for f in _active(findings, "metric-family-drift")
                if "bogus" in f.message]
        assert hits, findings

    def test_declared_family_is_clean(self):
        findings = _analyze("""
            class C:
                def publish(self):
                    self.stats.gauge("cache.hits", 1)
        """, "pilosa_tpu/newmod.py", self.PASSES)
        hits = [f for f in _active(findings, "metric-family-drift")
                if "undeclared" in f.message]
        assert not hits

    def test_counter_dict_keys_are_harvested(self):
        findings = _analyze("""
            _counters = {"mystery.executions": 0}
        """, "pilosa_tpu/newmod.py", self.PASSES)
        hits = [f for f in _active(findings, "metric-family-drift")
                if "mystery" in f.message]
        assert hits

    def test_package_families_all_have_static_emitters(self):
        """Against the real tree: every declared-static family has a
        harvested emitter and its doc still mentions it (the whole
        point of declaring families once)."""
        findings = core.analyze_paths([PKG])
        drift = _active(findings, "metric-family-drift")
        assert not drift, "\n".join(f.render() for f in drift)

    def test_registry_is_single_source_for_live_checker(self):
        from pilosa_tpu import metricfamilies as mf
        from tools import check_metrics as cm

        assert cm.ALL_FAMILIES == mf.live_prefixes()
        assert cm.DEVICE_FAMILIES == mf.live_prefixes("device")
        assert cm.INGEST_FAMILIES == mf.live_prefixes("ingest")
        assert cm.TAPE_FAMILIES == mf.live_prefixes("tape")


# --------------------------------------------------- suppression semantics


class TestSuppressionMechanism:
    PASSES = (passes_locks.LockDisciplinePass(),)

    VIOLATION = """
        class Fragment:
            def row_ids(self):
                return list(self._rows)
    """

    def test_trailing_suppression_with_reason_works(self):
        findings = _analyze("""
            class Fragment:
                def row_ids(self):
                    return list(self._rows)  # pilosa-lint: allow(lock-discipline) -- test fixture
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)
        assert any(f.suppressed and f.reason == "test fixture"
                   for f in findings)

    def test_standalone_suppression_covers_next_line(self):
        findings = _analyze("""
            class Fragment:
                def row_ids(self):
                    # pilosa-lint: allow(lock-discipline) -- test fixture
                    return list(self._rows)
        """, "models/fragment.py", self.PASSES)
        assert not _active(findings)

    def test_allow_without_reason_is_an_error(self):
        findings = _analyze("""
            class Fragment:
                def row_ids(self):
                    return list(self._rows)  # pilosa-lint: allow(lock-discipline)
        """, "models/fragment.py", self.PASSES)
        errs = _active(findings, "suppression")
        assert errs and "no reason" in errs[0].message
        # AND the underlying finding is NOT suppressed
        assert _active(findings, "lock-discipline")

    def test_allow_unknown_rule_is_an_error(self):
        findings = _analyze("""
            class Fragment:
                def row_ids(self):
                    return list(self._rows)  # pilosa-lint: allow(no-such-rule) -- because
        """, "models/fragment.py", self.PASSES)
        errs = _active(findings, "suppression")
        assert errs and "unknown rule" in errs[0].message
        assert _active(findings, "lock-discipline")

    def test_stale_suppression_is_reported_removable(self):
        findings = _analyze("""
            class Fragment:
                def row_ids(self):
                    with self._lock:
                        return list(self._rows)  # pilosa-lint: allow(lock-discipline) -- obsolete
        """, "models/fragment.py", self.PASSES)
        stale = _active(findings, "stale-suppression")
        assert stale and "remove it" in stale[0].message

    def test_malformed_directive_is_an_error(self):
        findings = _analyze("""
            x = 1  # pilosa-lint: allwo(lock-discipline) -- typo
        """, "models/fragment.py", self.PASSES)
        assert _active(findings, "suppression")

    def test_suppression_does_not_cover_other_rules(self):
        findings = _analyze("""
            import time
            class Fragment:
                def bad(self):
                    with self._lock:
                        time.sleep(1)  # pilosa-lint: allow(lock-discipline) -- wrong rule
        """, "models/fragment.py",
            (passes_locks.BlockingUnderLockPass(),))
        assert _active(findings, "blocking-under-lock")
        assert _active(findings, "stale-suppression")


# ------------------------------------------------------- typecheck config


class TestTypecheckScope:
    """The mypy --strict growth frontier: config present, scoped to
    the three declared modules, and (when mypy is installed) clean."""

    def test_strict_scope_is_declared(self):
        import configparser

        cp = configparser.ConfigParser()
        assert cp.read(os.path.join(REPO, "mypy.ini"))
        strict = [s for s in cp.sections()
                  if cp.has_option(s, "disallow_untyped_defs")
                  and cp.getboolean(s, "disallow_untyped_defs")]
        joined = " ".join(strict)
        for mod in ("pilosa_tpu.ops.tape", "pilosa_tpu.ops.expr",
                    "pilosa_tpu.runtime.resultcache"):
            assert mod in joined, (mod, strict)
        # the driver's file scope matches the declared strict scope
        from tools import typecheck

        assert tuple(sorted(typecheck.SCOPE)) == tuple(sorted((
            "pilosa_tpu/ops/tape.py", "pilosa_tpu/ops/expr.py",
            "pilosa_tpu/runtime/resultcache.py")))

    def test_typecheck_driver_gates_on_missing_mypy(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "typecheck.py")],
            cwd=REPO, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        has_mypy = True
        try:
            import mypy  # noqa: F401
        except ImportError:
            has_mypy = False
        if not has_mypy:
            assert "skipped" in proc.stdout.lower()
