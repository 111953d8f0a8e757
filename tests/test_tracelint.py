"""tracelint regression suite: each rule fires when its idiom is removed.

Two layers:

* **mutation fixtures** — for every rule TL001–TL005, a probe with the
  protective idiom surgically removed (the seam dropped, a stray read
  added, the mask deleted, the dtype left weak, a cond pushed into the
  rank loop) must produce that exact rule code, and the intact twin must
  stay clean;
* **HEAD pins** — the production entries are lint-clean under the
  committed ``tracelint.toml`` (and the two known grid-cache TL002
  findings are exactly the suppressed set), plus a subprocess test that
  the CLI gate exits 1 on a non-baselined finding.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint import baseline as lint_baseline
from repro.analysis.lint import entries as lint_entries
from repro.analysis.lint import rules as lint_rules
from repro.analysis.lint.entries import EntryProbe
from repro.analysis.lint.runner import run_lint
from repro.experiments import fused
from repro.latency.model import comp_latency_expr

REPO_ROOT = Path(__file__).resolve().parent.parent


def codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# TL001 — fma-seam
# ---------------------------------------------------------------------------


class TestTL001FmaSeam:
    def test_head_latency_chain_is_clean(self):
        entry = lint_entries.ENTRIES["latency"]()
        assert lint_rules.check_fma_seam(entry) == []

    def test_removing_the_seam_fires(self, monkeypatch):
        """Delete the jnp.maximum(comp_d, 0.0) seam: the compiled chain
        contracts the last multiply into the task_finish_time add and the
        bitwise diff against op-by-op evaluation catches it."""
        monkeypatch.setattr(
            fused,
            "guarded_comp_latency",
            lambda unit, cost, slowdown, factor: comp_latency_expr(
                unit, cost, slowdown, factor
            ),
        )
        entry = lint_entries.ENTRIES["latency"]()
        findings = lint_rules.check_fma_seam(entry)
        assert codes(findings) == ["TL001"]
        assert "seam" in findings[0].message


# ---------------------------------------------------------------------------
# TL002 — carry-copy
# ---------------------------------------------------------------------------


def _table_scan_probe(stray_read: bool) -> EntryProbe:
    """A training-scan body with a rank loop scatter-writing a table.

    ``stray_read=True`` adds the PR 4/5 bug shape: the rank loop *reads*
    the table it is about to scatter-write (``old = values[...]``-style),
    forcing a pre-write copy of the whole table per trip.
    """
    S, E, D = 2, 8, 16

    def body(carry, x):
        table, acc = carry

        def rank_body(r, tab_acc):
            tab, a = tab_acc
            val = jnp.full((S, D), 1.0, dtype=jnp.float32) * x
            if stray_read:
                a = a + tab[:, 0, 0].sum()  # pre-write read of the target
            else:
                a = a + val[0, 0]
            tab = tab.at[:, r % E].set(val)
            return tab, a

        table, acc = jax.lax.fori_loop(0, 3, rank_body, (table, acc))
        return (table, acc), acc

    init = (
        jnp.zeros((S, E, D), jnp.float32),
        jnp.zeros((), jnp.float32),
    )
    jaxpr = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs)
    )(init, jnp.arange(4, dtype=jnp.float32))
    return EntryProbe(name="synthetic_table_scan", description="", jaxpr=jaxpr)


class TestTL002CarryCopy:
    def test_write_only_rank_loop_is_clean(self):
        assert lint_rules.check_carry_copy(_table_scan_probe(False)) == []

    def test_stray_read_fires(self):
        findings = lint_rules.check_carry_copy(_table_scan_probe(True))
        assert codes(findings) == ["TL002"]
        assert "read inside its loop" in findings[0].message

    def test_production_grid_cache_read_is_detected(self):
        """Positive control on real code: the grid cache's by-design table
        read (fused._apply_cache_events) is exactly what the rule sees —
        this is the finding tracelint.toml baselines."""
        entry = lint_entries.ENTRIES["fused_logreg_grid"]()
        findings = lint_rules.check_carry_copy(entry)
        assert codes(findings) == ["TL002"]

    def test_production_write_only_caches_are_clean(self):
        """The §6 slot-universe and tiled caches keep the wmap/values0
        write-only discipline — the idiom PR 4/5 bisected into existence."""
        for name in ("fused_logreg_lb", "fused_logreg_tiled"):
            entry = lint_entries.ENTRIES[name]()
            assert lint_rules.check_carry_copy(entry) == [], name


# ---------------------------------------------------------------------------
# TL003 — pad-variant-reduce
# ---------------------------------------------------------------------------


def _unmasked_logreg_probe() -> EntryProbe:
    """The logreg sub_blocks kernel with the width mask deleted."""
    prob = lint_entries._probe_logreg()
    Xj = jnp.asarray(prob.X)
    yj = jnp.asarray(prob.y)
    n = prob.num_samples
    pad_w = 16

    def sub_blocks_unmasked(Vb, starts, widths):
        idx = jnp.clip(
            starts[:, None] - 1 + jnp.arange(pad_w)[None, :], 0, n - 1
        )
        xg = Xj[idx]
        yg = yj[idx]  # mask `* (arange < widths)` removed
        z = yg * jnp.sum(xg * Vb[:, None, :], axis=2)
        s = jax.nn.sigmoid(-z)
        return -jnp.sum(xg * (yg * s)[:, :, None], axis=1) / n

    jaxpr = jax.make_jaxpr(sub_blocks_unmasked)(
        jnp.zeros((3, prob.dim), jnp.float32),
        jnp.asarray([1, 17, 33], jnp.int32),
        jnp.asarray([11, 16, 13], jnp.int32),
    )
    return EntryProbe(
        name="synthetic_unmasked_kernel",
        description="",
        jaxpr=jaxpr,
        padded_axis_sizes=(pad_w,),
    )


class TestTL003PadVariantReduce:
    def test_removing_the_width_mask_fires(self):
        findings = lint_rules.check_pad_variant_reduce(_unmasked_logreg_probe())
        assert codes(findings) == ["TL003"]
        assert "padded axis" in findings[0].message

    @pytest.mark.parametrize("name", ["kernels_logreg", "kernels_pca"])
    def test_production_kernels_carry_mask_evidence(self, name):
        entry = lint_entries.ENTRIES[name]()
        assert lint_rules.check_pad_variant_reduce(entry) == []


# ---------------------------------------------------------------------------
# TL004 — dtype-leak
# ---------------------------------------------------------------------------


def _weak_carry_probe(explicit_dtype: bool) -> EntryProbe:
    def body(c, x):
        return c * np.float32(0.99), c.sum()

    if explicit_dtype:
        c0 = jnp.full((4,), 0.5, dtype=jnp.float32)
    else:
        c0 = jnp.full((4,), 0.5)  # python-float fill: weakly typed
    jaxpr = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs)
    )(c0, jnp.arange(3, dtype=jnp.float32))
    return EntryProbe(name="synthetic_weak_carry", description="", jaxpr=jaxpr)


class TestTL004DtypeLeak:
    def test_weak_float_carry_fires(self):
        findings = lint_rules.check_dtype_leak(_weak_carry_probe(False))
        assert codes(findings) == ["TL004"]
        assert "weakly typed" in findings[0].message

    def test_explicit_dtype_is_clean(self):
        assert lint_rules.check_dtype_leak(_weak_carry_probe(True)) == []

    def test_kernel_output_dtype_contract_fires_on_promotion(self):
        """A float64 cast leaking out of a kernel declared float32."""
        prob = lint_entries._probe_logreg()
        kernels = prob.fused_kernels()
        from repro.precision import x64

        with x64():
            jaxpr = jax.make_jaxpr(
                lambda Vb, st, wd: kernels.sub_blocks(Vb, st, wd, 16).astype(
                    jnp.float64
                )
            )(
                jnp.zeros((3, prob.dim), jnp.float32),
                jnp.asarray([1, 17, 33], jnp.int64),
                jnp.asarray([11, 16, 13], jnp.int64),
            )
        probe = EntryProbe(
            name="synthetic_promoted_kernel",
            description="",
            jaxpr=jaxpr,
            declared_output_dtypes=(np.dtype(kernels.value_dtype),),
        )
        findings = lint_rules.check_dtype_leak(probe)
        assert codes(findings) == ["TL004"]
        assert "value_dtype" in findings[0].message

    def test_fused_entries_have_strong_carries(self):
        """The PR 6 fix: lat/h_min/next_lb are filled with explicit
        dtypes, so the LB scan carries no weak types."""
        entry = lint_entries.ENTRIES["fused_logreg_lb"]()
        assert lint_rules.check_dtype_leak(entry) == []


# ---------------------------------------------------------------------------
# TL005 — cond-capture
# ---------------------------------------------------------------------------


def _cond_probe(in_rank_loop: bool) -> EntryProbe:
    big = jnp.zeros((64, 64), jnp.float32)  # 16 KiB: at the rule threshold

    def rank_cond(r, a):
        return jax.lax.cond(r > 0, lambda: a + big[0, 0], lambda: a - big[0, 0])

    def body(c, x):
        if in_rank_loop:
            c = jax.lax.fori_loop(0, 3, rank_cond, c)
        else:
            c = rank_cond(1, c)  # body-level cond: legitimate
        return c, c

    jaxpr = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs)
    )(jnp.float32(0.0), jnp.arange(4, dtype=jnp.float32))
    return EntryProbe(
        name="synthetic_cond",
        description="",
        jaxpr=jaxpr,
        cond_depth_threshold=1,  # the training scan itself, as in fused
    )


class TestTL005CondCapture:
    def test_cond_in_rank_loop_capturing_table_fires(self):
        findings = lint_rules.check_cond_capture(_cond_probe(True))
        assert codes(findings) == ["TL005"]
        assert "captures" in findings[0].message

    def test_body_level_cond_is_exempt(self):
        assert lint_rules.check_cond_capture(_cond_probe(False)) == []

    def test_production_rank_loops_have_no_conds(self):
        for name in ("fused_logreg_lb", "fused_logreg_tiled", "lb_update"):
            entry = lint_entries.ENTRIES[name]()
            assert lint_rules.check_cond_capture(entry) == [], name


# ---------------------------------------------------------------------------
# churn — the elastic-fleet scan body idioms, one mutation per rule
# ---------------------------------------------------------------------------


def _churn_latency_chain(times, sd_rows, unit, cost, factor, start, comm):
    """The churn slowdown path: per-start row lookup feeding the §3 product."""
    row = jnp.searchsorted(times, start, side="right")
    comp = fused.guarded_comp_latency(unit, cost, sd_rows[row], factor)
    from repro.cluster.simulator import task_finish_time

    return task_finish_time(start, comp, comm)


def _churn_latency_probe() -> EntryProbe:
    from repro.precision import x64

    with x64():
        batches = []
        for seed in (0, 1, 2, 3):
            rng = np.random.default_rng(seed)
            times = jnp.asarray(np.sort(rng.uniform(0.1, 3.0, 2)), jnp.float64)
            sd_rows = jnp.asarray(rng.uniform(1.0, 1.5, (3, 64)), jnp.float64)
            rest = tuple(
                jnp.asarray(rng.uniform(0.1, 3.0, size=64), dtype=jnp.float64)
                for _ in range(5)
            )
            batches.append((times, sd_rows) + rest)
    return EntryProbe(
        name="synthetic_churn_latency",
        description="",
        latency_probe=(_churn_latency_chain, batches),
    )


def _churn_clear_probe(values_in_fori_carry: bool) -> EntryProbe:
    """The death-clear loop shape: per-entry subtraction from running sums.

    The production idiom (``fused._clear_dead_dense``) keeps the values
    table OUT of the fori carry — the loop reads it from the enclosing
    scan carry at loop-invariant positions, so in-place aliasing of the
    scatter-written tables survives.  ``values_in_fori_carry=True`` is
    the mutation: threading the table through the clear loop's carry
    (written by the zero-out scatter AND read by the subtraction) forces
    a pre-write copy of the whole table per trip.
    """
    S, E, D = 2, 8, 16

    def body(carry, x):
        values, sums = carry

        if values_in_fori_carry:

            def clear_body(e, val_su):
                vals, su = val_su
                su = su - vals[:, e % E]
                vals = vals.at[:, e % E].set(jnp.zeros((S, D), jnp.float32))
                return vals, su

            values, sums = jax.lax.fori_loop(0, 3, clear_body, (values, sums))
        else:

            def clear_body(e, su):
                return su - values[:, e % E]

            sums = jax.lax.fori_loop(0, 3, clear_body, sums)
            values = values.at[:, 0].set(jnp.zeros((S, D), jnp.float32) + x)
        return (values, sums), sums[0, 0]

    init = (
        jnp.zeros((S, E, D), jnp.float32),
        jnp.zeros((S, D), jnp.float32),
    )
    jaxpr = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs)
    )(init, jnp.arange(4, dtype=jnp.float32))
    return EntryProbe(name="synthetic_churn_clear", description="", jaxpr=jaxpr)


def _churn_tau_probe(masked: bool) -> EntryProbe:
    """The liveness-masked w-th order statistic over a padded worker axis.

    ``masked=False`` drops the ``alive & (iota < width)`` select before
    the reduction — dead/pad workers' finish times silently enter tau.
    """
    pad_n = 16

    def tau(finish, width):
        if masked:
            lane = jnp.arange(pad_n)[None, :]
            finish = jnp.where(lane < width[:, None], finish, jnp.inf)
        return jnp.min(finish, axis=1)

    jaxpr = jax.make_jaxpr(tau)(
        jnp.zeros((3, pad_n), jnp.float32),
        jnp.asarray([4, 6, 5], jnp.int32),
    )
    return EntryProbe(
        name="synthetic_churn_tau",
        description="",
        jaxpr=jaxpr,
        padded_axis_sizes=(pad_n,),
    )


def _churn_boundary_probe(explicit_dtype: bool) -> EntryProbe:
    """The reactive-LB carry: ``lb_since`` starts at the -inf boundary.

    A python-float fill leaves the carry weakly typed — the first
    ``where(changed, boundary, lb_since)`` against it could re-promote.
    """
    S = 2

    def body(c, x):
        row, since = c
        return (row + 1, jnp.maximum(since, x)), since.sum()

    if explicit_dtype:
        since0 = jnp.full((S,), -jnp.inf, dtype=jnp.float32)
    else:
        since0 = jnp.full((S,), -np.inf)
    init = (jnp.zeros((S,), jnp.int32), since0)
    jaxpr = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs)
    )(init, jnp.arange(3, dtype=jnp.float32))
    return EntryProbe(
        name="synthetic_churn_boundary", description="", jaxpr=jaxpr
    )


def _churn_cond_clear_probe(branchless: bool) -> EntryProbe:
    """Per-entry clear decisions must be branchless masked arithmetic.

    A ``lax.cond`` on ``clear[e]`` inside the clear loop captures the
    values table in both branches — TL005's copy-amplification shape.
    """
    values = jnp.zeros((64, 64), jnp.float32)  # 16 KiB: at the threshold
    clear = jnp.asarray([True, False, True], bool)

    def clear_body(e, su):
        if branchless:
            return su + jnp.where(clear[e % 3], values[0, 0], 0.0)
        return jax.lax.cond(
            clear[e % 3],
            lambda: su + values[0, 0],
            lambda: su - values[0, 0],
        )

    def body(c, x):
        c = jax.lax.fori_loop(0, 3, clear_body, c)
        return c, c

    jaxpr = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs)
    )(jnp.float32(0.0), jnp.arange(4, dtype=jnp.float32))
    return EntryProbe(
        name="synthetic_churn_cond",
        description="",
        jaxpr=jaxpr,
        cond_depth_threshold=1,
    )


class TestChurnScanIdioms:
    def test_production_churn_entry_is_clean_under_every_rule(self):
        entry = lint_entries.ENTRIES["fused_logreg_churn"]()
        assert lint_rules.check_carry_copy(entry) == []
        assert lint_rules.check_dtype_leak(entry) == []
        assert lint_rules.check_cond_capture(entry) == []
        assert lint_rules.check_pad_variant_reduce(entry) == []

    def test_tl001_churn_row_lookup_keeps_the_seam(self, monkeypatch):
        assert lint_rules.check_fma_seam(_churn_latency_probe()) == []
        monkeypatch.setattr(
            fused,
            "guarded_comp_latency",
            lambda unit, cost, slowdown, factor: comp_latency_expr(
                unit, cost, slowdown, factor
            ),
        )
        findings = lint_rules.check_fma_seam(_churn_latency_probe())
        assert codes(findings) == ["TL001"]

    def test_tl002_values_threaded_through_the_clear_loop_fires(self):
        assert lint_rules.check_carry_copy(_churn_clear_probe(False)) == []
        findings = lint_rules.check_carry_copy(_churn_clear_probe(True))
        assert codes(findings) == ["TL002"]
        assert "read inside its loop" in findings[0].message

    def test_tl003_unmasked_tau_over_padded_workers_fires(self):
        assert lint_rules.check_pad_variant_reduce(_churn_tau_probe(True)) == []
        findings = lint_rules.check_pad_variant_reduce(_churn_tau_probe(False))
        assert codes(findings) == ["TL003"]

    def test_tl004_weak_lb_since_carry_fires(self):
        assert lint_rules.check_dtype_leak(_churn_boundary_probe(True)) == []
        findings = lint_rules.check_dtype_leak(_churn_boundary_probe(False))
        assert codes(findings) == ["TL004"]
        assert "weakly typed" in findings[0].message

    def test_tl005_cond_on_clear_mask_fires(self):
        assert lint_rules.check_cond_capture(_churn_cond_clear_probe(True)) == []
        findings = lint_rules.check_cond_capture(_churn_cond_clear_probe(False))
        assert codes(findings) == ["TL005"]


# ---------------------------------------------------------------------------
# baseline layer
# ---------------------------------------------------------------------------


class TestBaseline:
    def test_parse_and_match(self):
        supps = lint_baseline.parse_baseline(
            '[tracelint]\nversion = 1\n\n'
            '[[suppress]]\ncode = "TL002"\nentry = "fused_logreg_grid"\n'
            'contains = "gather"\nreason = "accepted"\n'
        )
        assert len(supps) == 1
        from repro.analysis.lint.findings import Finding

        hit = Finding("TL002", "fused_logreg_grid", "x:gather", "msg")
        miss_entry = Finding("TL002", "fused_logreg_lb", "x:gather", "msg")
        miss_code = Finding("TL004", "fused_logreg_grid", "x:gather", "msg")
        assert supps[0].matches(hit)
        assert not supps[0].matches(miss_entry)
        assert not supps[0].matches(miss_code)

    def test_reason_is_mandatory(self):
        with pytest.raises(ValueError, match="reason"):
            lint_baseline.parse_baseline('[[suppress]]\ncode = "TL001"\n')

    def test_committed_baseline_parses(self):
        supps = lint_baseline.load_baseline(REPO_ROOT / "tracelint.toml")
        assert all(s.reason for s in supps)
        assert {s.code for s in supps} == {"TL002"}


# ---------------------------------------------------------------------------
# HEAD state + the CI gate
# ---------------------------------------------------------------------------


class TestHeadAndGate:
    def test_head_is_clean_under_committed_baseline(self):
        """The acceptance pin: every entry, zero active findings, and the
        suppressed set is exactly the two known grid-cache reads."""
        report = run_lint("all", baseline_path=REPO_ROOT / "tracelint.toml")
        assert report.findings == []
        assert report.exit_code == 0
        suppressed = sorted((f.code, f.entry) for f, _ in report.suppressed)
        assert suppressed == [
            ("TL002", "fused_logreg_grid"),
            ("TL002", "fused_pca_grid"),
        ]

    def _run_cli(self, *args):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = str(REPO_ROOT / "src")
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis.lint", *args],
            capture_output=True,
            text=True,
            env=env,
            cwd=REPO_ROOT,
            timeout=300,
        )

    def test_cli_gate_fails_on_non_baselined_finding(self):
        """The CI gate demonstration: without the baseline, the grid-cache
        TL002 finding turns the build red (exit 1) and is reported in the
        JSON artifact."""
        proc = self._run_cli(
            "--entry", "fused_logreg_grid", "--no-baseline", "--format", "json"
        )
        assert proc.returncode == 1, proc.stderr
        payload = json.loads(proc.stdout)
        assert [f["code"] for f in payload["findings"]] == ["TL002"]
        assert payload["suppressed"] == []

    def test_cli_green_with_committed_baseline(self):
        proc = self._run_cli(
            "--entry", "fused_logreg_grid", "--format", "json"
        )
        assert proc.returncode == 0, proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["findings"] == []
        assert [f["code"] for f in payload["suppressed"]] == ["TL002"]
