"""graftlint: rule fixtures fire at the right spans; the repo is clean.

The fixture corpus under tests/fixtures/lint/ is parsed, never imported:
each file is a deliberately-broken miniature of the engine's scan
conventions. The round-5 gcr regression fixture pins the exact bug shape
(ADVICE.md high finding) that motivated the analysis layer — reverting
the PR-1 gcr_seg wiring reproduces it, and GL1/GL2 must fail it loudly.
"""

import json
import os
import subprocess
import sys

import pytest

from open_simulator_tpu.analysis import (
    RULE_CODES,
    RULES,
    LintError,
    assert_clean,
    format_json,
    format_text,
    run_lint,
)
from open_simulator_tpu.analysis.report import repo_root

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "lint")


def lint_fixture(name, codes=None):
    return run_lint(root=FIXTURES, paths=[name], codes=codes)


def line_of(name, needle, nth=1):
    """1-based line of the nth occurrence of `needle` in a fixture."""
    seen = 0
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        for i, ln in enumerate(f, 1):
            if needle in ln:
                seen += 1
                if seen == nth:
                    return i
    raise AssertionError(f"{needle!r} (#{nth}) not in {name}")


def by_symbol(findings, symbol):
    out = [f for f in findings if f.symbol == symbol]
    assert out, (f"no finding for {symbol!r}; got "
                 f"{[(f.code, f.symbol, f.line) for f in findings]}")
    return out


# ---- rule-by-rule fixtures ----------------------------------------------


def test_gl1_fires_on_all_three_contract_directions():
    fs = lint_fixture("gl1_xs_contract.py")
    assert {f.code for f in fs} == {"GL1"}
    missing = by_symbol(fs, "missing_leaf")[0]
    assert missing.line == line_of("gl1_xs_contract.py", 'x["missing_leaf"]')
    assert "never encoded" in missing.message
    dead = by_symbol(fs, "dead_leaf")[0]
    assert dead.line == line_of("gl1_xs_contract.py", 'xs["dead_leaf"]')
    assert "never reads" in dead.message
    ghost = by_symbol(fs, "ghost_field")[0]
    assert ghost.line == line_of("gl1_xs_contract.py", '"ghost_field"')
    assert "SnapshotArrays" in ghost.message
    assert len(fs) == 3


def test_gl2_underbound_overbound_and_bad_keyword():
    fs = lint_fixture("gl2_arity.py")
    assert {f.code for f in fs} == {"GL2"}
    lines = sorted(f.line for f in fs)
    assert lines == sorted([
        line_of("gl2_arity.py", "partial(_step, jnp.ones((4,)))"),
        line_of("gl2_arity.py", "partial(_step, 1.0, 2.0, 3.0)"),
        line_of("gl2_arity.py", "partial(_step, 1.0, weight=2.0, gain=3.0)"),
    ])
    under = [f for f in fs if "only 3 are supplied" in f.message]
    assert under and "weight" in under[0].hint
    over = [f for f in fs if "at most 4" in f.message]
    assert over
    badkw = [f for f in fs if "'gain'" in f.message]
    assert badkw


def test_gl3_flags_dead_field_and_property_only():
    fs = lint_fixture("gl3_dead_flag.py")
    assert {f.code for f in fs} == {"GL3"}
    symbols = {f.symbol for f in fs}
    assert symbols == {"EngineConfig.stale_knob", "EngineConfig.unused_prop"}
    knob = by_symbol(fs, "EngineConfig.stale_knob")[0]
    assert knob.line == line_of("gl3_dead_flag.py", "stale_knob")


def test_gl4_flags_every_host_sync_kind():
    fs = lint_fixture("gl4_trace.py")
    assert {f.code for f in fs} == {"GL4"}
    kinds = sorted(f.symbol for f in fs)
    assert kinds == ["float", "if", "if", "item", "np.asarray",
                     "range", "while"]
    # the static-argname branch and the shape-bounded loop stay silent
    ok_line = line_of("gl4_trace.py", 'mode == "fast"')
    shp_line = line_of("gl4_trace.py", "range(a.shape[0])")
    assert all(f.line not in (ok_line, shp_line) for f in fs)
    # scan-step `if` is anchored inside _step
    step_if = line_of("gl4_trace.py", 'if x["flag"]')
    assert any(f.line == step_if for f in fs)


def test_gl5_flags_unguarded_conditional_dtype_update_only():
    fs = lint_fixture("gl5_dtype.py")
    assert [f.code for f in fs] == ["GL5"]
    f = fs[0]
    assert f.symbol == "SimState.group_count"
    assert f.line == line_of("gl5_dtype.py", "bad = state.group_count + paint")
    assert "astype" in f.hint


def test_clean_fixture_is_clean():
    assert lint_fixture("clean_ok.py") == []


def test_gl4_telemetry_safe_pattern_is_clean():
    """Host-side metric recording from RECORDED outputs (np.asarray after
    the jit, float() on host values) near traced code — the pattern the
    telemetry instrumentation follows — must not trip GL4."""
    assert lint_fixture("gl4_telemetry_ok.py") == []


def test_gl4_execcache_safe_pattern_is_clean():
    """Host-side executable-cache bookkeeping — LRU dict ops, hit/miss
    counters, compile timing around jit(...).lower(...).compile() — on
    HOST keys derived from static shape/dtype metadata, the pattern
    engine/exec_cache.py follows, must not trip GL4 (or any rule)."""
    assert lint_fixture("gl4_execcache_ok.py") == []


def test_gl4_mesh_cache_safe_pattern_is_clean():
    """Mesh-path cache bookkeeping — a module-level lru_cache'd lane fn,
    the cache key extended with the mesh axis split + device ids (host
    metadata), sharding specs built host-side around the AOT
    lower().compile() — the pattern engine/exec_cache.py run_mesh_cached
    follows, must not trip GL4 (or any rule)."""
    assert lint_fixture("gl4_mesh_cache_ok.py") == []


def test_gl4_waves_safe_pattern_is_clean():
    """The host-side wave partitioner next to jit scope — numpy conflict
    analysis BEFORE the trace, the plan entering jit only as static
    Python-int segment tuples, static-bound Python loops inside — the
    pattern engine/waves.py + scheduler._run_wave_plan follow, must not
    trip GL4 (or any rule)."""
    assert lint_fixture("gl4_waves_ok.py") == []


def test_gl4_tune_safe_pattern_is_clean():
    """The traced-score-weights pattern (tune subsystem, ARCHITECTURE
    §17) — weights sliced from a traced [K] input and only multiplied,
    gate selection on STATIC enable flags plus the static traced-mode
    flag (`traced or weight`), a vmapped [W, K] lane matrix — the
    pattern scheduler._step + tune/search.py follow, must not trip GL4
    (or any rule). Branching on a traced weight is the violation this
    shape exists to avoid (gl4_trace.py's step-if covers the negative)."""
    assert lint_fixture("gl4_tune_ok.py") == []


def test_gl4_ledger_safe_pattern_is_clean():
    """Host-side run-ledger writes next to jit scope — fingerprints from
    static shape metadata, digests over np.asarray'd outputs, JSON file
    appends, counter bumps — the pattern telemetry/ledger.py and its call
    sites follow, must not trip GL4 (or any rule)."""
    assert lint_fixture("gl4_ledger_ok.py") == []


def test_suppression_swallows_finding_and_gl0_flags_naked_directive():
    fs = lint_fixture("suppressed.py")
    assert [f.code for f in fs] == ["GL0"]
    assert fs[0].line == line_of("suppressed.py", "int(jnp.max(a))")


# ---- the round-5 regression shape ---------------------------------------


def test_gcr_regression_shape_fails_gl1_and_gl2():
    fs = lint_fixture("gcr_regression.py")
    codes = {f.code for f in fs}
    assert codes == {"GL1", "GL2"}
    # GL1 both directions, with actionable spans
    gid = by_symbol(fs, "gcr_gid")[0]
    assert gid.code == "GL1"
    assert gid.line == line_of("gcr_regression.py",
                               'jnp.take(state, x["gcr_gid"]')
    key = by_symbol(fs, "gcr_key")[0]
    assert key.line == line_of("gcr_regression.py", 'keys = x["gcr_key"]')
    dead = by_symbol(fs, "gcr_dead")[0]
    assert dead.line == line_of("gcr_regression.py", 'xs["gcr_dead"]')
    live_dead = by_symbol(fs, "aff_group")[0]
    assert "declared live" in live_dead.message
    # GL2: 5 of 8 bound -> trace-time TypeError, caught statically
    arity = by_symbol(fs, "_step")[0]
    assert arity.code == "GL2"
    assert arity.line == line_of("gcr_regression.py",
                                 "functools.partial(_step, arrs")
    assert "gcr_seg" in arity.hint
    assert "TypeError" in arity.message


# ---- whole-repo enforcement ---------------------------------------------


def test_repo_tree_is_lint_clean():
    fs = run_lint()
    assert fs == [], "graftlint findings at HEAD:\n" + format_text(fs)


def test_default_lint_paths_exist():
    # a stale entry would silently shrink what `simon-tpu lint` checks
    from open_simulator_tpu.analysis.report import DEFAULT_PATHS

    for path in DEFAULT_PATHS:
        assert os.path.exists(os.path.join(repo_root(), path)), path


def test_assert_clean_raises_structured_lint_error():
    with pytest.raises(LintError) as exc:
        assert_clean(root=FIXTURES, paths=["gl5_dtype.py"])
    err = exc.value
    assert err.code == "E_LINT"
    d = err.to_dict()
    assert d["findings"][0]["code"] == "GL5"
    assert "gl5_dtype.py" in str(err)
    # and the clean control fixture does not raise
    assert_clean(root=FIXTURES, paths=["clean_ok.py"])


def test_rule_catalog_is_complete():
    assert tuple(r.code for r in RULES) == RULE_CODES
    parsed = json.loads(format_json([]))
    assert parsed["clean"] is True


def test_cli_lint_json_clean_tree():
    """Tier-1 enforcement: `simon-tpu lint --format json` exits 0 at HEAD."""
    proc = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "lint",
         "--format", "json"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["clean"] is True and payload["count"] == 0


def test_cli_lint_rejects_unknown_rule_code():
    """A mistyped --select must exit 2, not silently run zero rules."""
    proc = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "lint",
         "--select", "GL99"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert "unknown rule code" in proc.stderr


def test_cli_lint_fails_on_regression_fixture():
    proc = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "lint",
         "--format", "json", "tests/fixtures/lint/gcr_regression.py"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["count"] >= 5
    assert {f["code"] for f in payload["findings"]} == {"GL1", "GL2"}


# ---- GL6: launch-wrap discipline ----------------------------------------


def test_gl6_safe_wrapping_patterns_are_clean():
    """All four sanctioned shapes — wrapper-arg thunk (incl. through an
    aliased import), closure handoff, callee-owns-the-domain, traced
    invoker — must not trip GL6 (or any rule)."""
    assert lint_fixture("gl6_ok.py") == []


def test_gl6_regression_unwrapped_sync_fails():
    """The PR-14 incident shape: a jit result invoked and synced outside
    faults.run_launch must flag GL6 at both lines."""
    fs = lint_fixture("gl6_regression_unwrapped.py")
    assert {f.code for f in fs} == {"GL6"}
    sync = by_symbol(fs, "block_until_ready")[0]
    assert sync.line == line_of("gl6_regression_unwrapped.py",
                                "out.block_until_ready()")
    invoke = by_symbol(fs, "fn (jit/compile result)")[0]
    assert invoke.line == line_of("gl6_regression_unwrapped.py",
                                  "out = fn(xs)")
    assert "run_launch" in sync.hint


def test_gl6_regression_percall_vmap_immediate_invoke_fails():
    """The pre-ISSUE-19 mesh-branch shape: a fresh jit(vmap(lambda ...))
    built and INVOKED per call — a full recompile per bisect round,
    dispatched outside the fault domain — must flag GL6 at the invoke
    line; the sanctioned mesh-cache shape is gl4_mesh_cache_ok.py."""
    fs = lint_fixture("gl6_regression_percall_vmap.py")
    assert {f.code for f in fs} == {"GL6"}
    invoke = by_symbol(fs, "jit(...)(...) immediate invoke")[0]
    assert invoke.line == line_of("gl6_regression_percall_vmap.py",
                                  "jax.jit(jax.vmap(lambda m:")
    assert "run_launch" in invoke.hint


# ---- GL7: lock-order safety ---------------------------------------------


def test_gl7_safe_locking_patterns_are_clean():
    """Consistent order, try_hold second keys, snapshot-then-launch, and
    helper-owned self-stored locks must not trip GL7 (or any rule) — in
    particular try_hold must NOT count as a lock-order edge."""
    assert lint_fixture("gl7_ok.py") == []


def test_gl7_regression_keyedmutex_abba_fails():
    """The PR-11 session-store deadlock: blocking cross-key hold of the
    same KeyedMutex (self-stored, reached via `self._mu`) must flag GL7
    at both nested acquires."""
    fs = lint_fixture("gl7_regression_keyedmutex.py")
    assert {f.code for f in fs} == {"GL7"}
    hits = by_symbol(fs, "SessionStore._mu")
    assert len(hits) == 2
    assert {h.line for h in hits} == {
        line_of("gl7_regression_keyedmutex.py", "self._mu.hold(target)"),
        line_of("gl7_regression_keyedmutex.py", "self._mu.hold(victim)",
                nth=2),
    }
    assert all("AB-BA" in h.message for h in hits)
    assert all("try_hold" in h.hint for h in hits)


def test_gl7_cycle_selfnest_and_launch_spans():
    fs = lint_fixture("gl7_bad.py")
    assert {f.code for f in fs} == {"GL7"}
    cycle = by_symbol(fs, "LOCK_A<->LOCK_B")[0]
    assert "cycle" in cycle.message
    nest = [f for f in fs if "self-deadlock" in f.message]
    assert len(nest) == 1 and nest[0].symbol == "LOCK_A"
    spans = [f for f in fs if "held" in f.message]
    assert len(spans) == 2
    # one direct, one transitive through the helper
    assert any("via _helper_launch" in f.message for f in spans)


# ---- GL8: boundary discipline -------------------------------------------


def test_gl8_mapped_boundaries_are_clean():
    """Handlers that answer through status_for/error_payload, re-raise
    SimulationError subclasses, catch builtins locally, or classify in
    workers must not trip GL8 (or any rule)."""
    assert lint_fixture("gl8_ok.py") == []


def test_gl8_regression_literal_status_table_fails():
    """The PR-12 drift: a hand-copied code->status dict outside
    serving.py must flag GL8 at the dict itself."""
    fs = lint_fixture("gl8_regression_status_table.py")
    assert {f.code for f in fs} == {"GL8"}
    f = fs[0]
    assert f.symbol == "code->status dict"
    assert f.line == line_of("gl8_regression_status_table.py", "_STATUS = {")
    assert "STATUS_BY_CODE" in f.hint


def test_gl8_swallows_and_escaping_builtins_fail():
    fs = lint_fixture("gl8_bad.py")
    assert {f.code for f in fs} == {"GL8"}
    # the decorator-WRAPPED routed handler is still a boundary
    routed = by_symbol(fs, "simulate_endpoint")[0]
    assert "decorator-routed" in routed.message
    assert by_symbol(fs, "do_GET")
    worker = by_symbol(fs, "_worker")[0]
    assert "thread worker" in worker.message
    esc = by_symbol(fs, "ValueError")[0]
    assert esc.line == line_of("gl8_bad.py", 'raise ValueError')
    # one delegation level: do_DELETE dispatches to self._do_delete(),
    # whose broad except must still be seen (the rest.py blind spot)
    delegate = by_symbol(fs, "_do_delete")[0]
    assert "delegate of REST handler method `do_DELETE`" in delegate.message
    assert len(fs) == 5


# ---- GL9: durable-write discipline --------------------------------------


def test_gl9_journal_and_run_io_writes_are_clean():
    assert lint_fixture("gl9_ok.py") == []


def test_gl9_direct_writes_fail():
    fs = lint_fixture("gl9_bad.py")
    assert {f.code for f in fs} == {"GL9"}
    assert {f.symbol for f in fs} == {'open(..., "w")', "os.write",
                                      "os.fsync"}
    assert all("run_io" in f.hint for f in fs)


def test_gl9_scope_is_path_based():
    """GL9 only covers the durable-state subtrees (and gl9_* fixtures):
    the same direct writes in an unscoped file — e.g. the ledger ok
    fixture's JSON appends — stay clean."""
    assert lint_fixture("gl4_ledger_ok.py", codes=["GL9"]) == []


# ---- GL10: metric-name drift --------------------------------------------


def test_gl10_resolved_names_are_clean():
    assert lint_fixture("gl10_ok.py") == []


def test_gl10_callback_cost_gauge_families_are_clean():
    """The §20 idiom: literal callback-gauge cost families
    (engine/exec_cache.py's simon_exec_cost_* trio) plus a
    module-constant counter family must all resolve without drift."""
    assert lint_fixture("gl10_cost_ok.py") == []


def test_gl10_drifted_name_fails():
    fs = lint_fixture("gl10_bad.py")
    assert [f.code for f in fs] == ["GL10"]
    f = fs[0]
    assert f.symbol == "simon_fixture_run_total"
    assert f.line == line_of("gl10_bad.py", '"simon_fixture_run_total"')


def test_gl10_doc_sync_both_directions(tmp_path):
    """Full-tree runs check code<->ARCHITECTURE.md both ways: a declared
    family missing from the doc flags at its declaration; a catalog row
    naming no declared family flags as a ghost at its doc line."""
    pkg = tmp_path / "open_simulator_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "from open_simulator_tpu.telemetry import counter\n"
        "def declare():\n"
        '    return counter("simon_doc_fixture_total", "x")\n'
        "def declare_undocumented():\n"
        '    return counter("simon_undocumented_total", "x")\n',
        encoding="utf-8")
    (tmp_path / "ARCHITECTURE.md").write_text(
        "Metric catalog:\n"
        "\n"
        "| series | type |\n"
        "|---|---|\n"
        "| `simon_doc_fixture_total` | counter |\n"
        "| `simon_ghost_total` | counter |\n"
        "\n"
        "### next section\n",
        encoding="utf-8")
    fs = run_lint(root=str(tmp_path), codes=["GL10"])
    assert {f.code for f in fs} == {"GL10"}
    ghost = by_symbol(fs, "simon_ghost_total")[0]
    assert ghost.path == "ARCHITECTURE.md" and "ghost" in ghost.message
    undoc = by_symbol(fs, "simon_undocumented_total")[0]
    assert undoc.path == "open_simulator_tpu/mod.py"
    assert "missing from the ARCHITECTURE.md metric catalog" in undoc.message
    assert len(fs) == 2
    # path-scoped runs skip the doc direction (partial module sets would
    # mass-flag): only the orphan check remains, and nothing orphans here
    scoped = run_lint(root=str(tmp_path),
                      paths=["open_simulator_tpu/mod.py"], codes=["GL10"])
    assert scoped == []


# ---- CLI: --changed, --format sarif, --jobs -----------------------------


def test_cli_lint_changed_scope():
    """--changed REF lints only the changed+untracked product files; with
    no in-scope change vs HEAD it must report clean WITHOUT falling back
    to the full tree (fast path for pre-commit)."""
    proc = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "lint",
         "--changed", "--format", "json"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["clean"] is True


def test_cli_lint_sarif_shape():
    proc = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "lint",
         "--format", "sarif", "tests/fixtures/lint/gl9_bad.py"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    sarif = json.loads(proc.stdout)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert set(RULE_CODES) <= rule_ids
    results = run["results"]
    assert {r["ruleId"] for r in results} == {"GL9"}
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "tests/fixtures/lint/gl9_bad.py"
    assert loc["region"]["startLine"] > 0


def test_cli_lint_jobs_parallel_parse_matches_serial():
    proc = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "lint",
         "--jobs", "4", "--format", "json",
         "tests/fixtures/lint/gl9_bad.py",
         "tests/fixtures/lint/gl10_bad.py"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert {f["code"] for f in payload["findings"]} == {"GL9", "GL10"}
    serial = run_lint(root=repo_root(),
                      paths=["tests/fixtures/lint/gl9_bad.py",
                             "tests/fixtures/lint/gl10_bad.py"])
    assert payload["count"] == len(serial)
