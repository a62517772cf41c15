"""Tier-1 enforcement: the tree stays hslint-clean.

This is the teeth of the analyzer — every rule violation introduced
anywhere in ``hyperspace_tpu/`` or ``scripts/`` fails this
test unless it carries a per-line ``# hslint: disable=HSxxx`` suppression
with a justification. Fixture-level rule behavior is covered in
``test_analysis_rules.py``; this file only pins the zero-findings
invariant and the CLI contract.
"""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
LINT_TARGETS = ["hyperspace_tpu", "scripts"]


def test_tree_has_zero_unsuppressed_findings():
    from hyperspace_tpu.analysis import run_analysis

    findings = run_analysis([REPO / t for t in LINT_TARGETS])
    unsuppressed = [f for f in findings if not f.suppressed]
    assert unsuppressed == [], "\n" + "\n".join(f.render() for f in unsuppressed)


def test_cli_exits_zero_on_clean_tree():
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", *LINT_TARGETS],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_json_format_and_failure_exit(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "CACHE = {}\n"
        "def put(k, v):\n"
        "    CACHE[k] = v\n",
        encoding="utf-8",
    )
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--format", "json", str(bad)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["summary"]["unsuppressed"] == 1
    assert payload["findings"][0]["code"] == "HS006"


def test_cli_list_rules_names_all_nineteen():
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--list-rules"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0
    for code in (
        "HS001", "HS002", "HS003", "HS004", "HS005", "HS006", "HS007",
        "HS008", "HS009", "HS010", "HS011", "HS012", "HS013", "HS014",
        "HS015", "HS016", "HS017", "HS018", "HS019",
    ):
        assert code in proc.stdout


# --- metrics exporter validation (runs in the lint tier alongside hslint) ---


def test_metrics_cli_check_validates_prometheus_rendering():
    """``scripts/metrics.py --check`` renders a synthetic registry
    exercising every metric type (plus the live one) and validates the
    Prometheus text the way a scraper would — a malformed metric name
    or duplicate family fails HERE, not the fleet's scrape."""
    proc = subprocess.run(
        [sys.executable, "scripts/metrics.py", "--check"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "metrics check: OK" in proc.stdout


def test_metrics_cli_renders_both_formats():
    for fmt, needle in (("prom", "# TYPE "), ("jsonl", '"type"')):
        proc = subprocess.run(
            [sys.executable, "scripts/metrics.py", "--format", fmt,
             "--demo"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert needle in proc.stdout


def test_cli_missing_path_is_usage_error():
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "no/such/dir"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 2


# --- whole-program phase: CLI contract and wall-time budget -----------------


def test_full_tree_wall_time_budget():
    """Both phases over the whole tree stay under the pre-commit budget
    (~10 s on an idle dev container) — the property that keeps --changed
    runs viable, since they pay the FULL model build. Best-of-two: one
    measurement on a loaded CI box measures the neighbors, not the
    analyzer. The budget is CALIBRATED per machine: a fixed constant
    measured general load, not the analyzer — a loaded 2-core runner
    failed on analyzer-unrelated contention. The calibration workload
    (ast.parse over the same sources) is a fixed, analyzer-free fraction
    of the same CPU work, so it scales with machine speed AND current
    load exactly like the analyzer does; the multiplier pins the
    analysis/parse ratio (~20x measured) with ~50% headroom, and the
    10 s floor keeps the fast-machine contract as strict as before."""
    import ast
    import time

    from hyperspace_tpu.analysis import run_analysis

    sources = []
    for t in LINT_TARGETS:
        p = REPO / t
        files = [p] if p.suffix == ".py" else sorted(p.rglob("*.py"))
        sources += [f.read_text(encoding="utf-8") for f in files]
    t0 = time.perf_counter()
    for s in sources:
        ast.parse(s)
    parse_s = time.perf_counter() - t0
    budget = max(10.0, 30.0 * parse_s)

    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        run_analysis([REPO / t for t in LINT_TARGETS])
        best = min(best, time.perf_counter() - t0)
        if best < budget:
            break
    assert best < budget, (
        f"full-tree analysis took {best:.1f}s "
        f"(calibrated budget {budget:.1f}s from parse baseline "
        f"{parse_s:.2f}s)"
    )


def test_project_phase_finds_cross_module_cycle(tmp_path):
    """End-to-end through the CLI: a two-module A->B / B->A lock cycle
    fires HS009 with --project (the default) and is invisible with
    --no-project."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import threading\n"
        "from . import b\n"
        "_A_LOCK = threading.Lock()\n"
        "def locked_a():\n"
        "    with _A_LOCK:\n"
        "        pass\n"
        "def do_a():\n"
        "    with _A_LOCK:\n"
        "        b.locked_b()\n",
        encoding="utf-8",
    )
    (pkg / "b.py").write_text(
        "import threading\n"
        "from . import a\n"
        "_B_LOCK = threading.Lock()\n"
        "def locked_b():\n"
        "    with _B_LOCK:\n"
        "        pass\n"
        "def do_b():\n"
        "    with _B_LOCK:\n"
        "        a.locked_a()\n",
        encoding="utf-8",
    )
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts/lint.py"), "--format", "json",
         str(pkg)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["summary"]["by_code"] == {"HS009": 2}
    proc2 = subprocess.run(
        [sys.executable, str(REPO / "scripts/lint.py"), "--no-project",
         str(pkg)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc2.returncode == 0


def test_cli_default_paths_and_timings():
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--timings"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    # per-rule timings (stderr): every project rule accounted for, and
    # the phase-3 flow fixpoint under its own key (not inflating the
    # first rule that touches it)
    for code in (
        "HS009", "HS010", "HS011", "HS012", "HS013", "HS015", "HS016",
        "HS017", "HS018", "HS019", "project-model", "device-flow",
    ):
        assert code in proc.stderr


def test_cli_call_graph_dump(tmp_path):
    out = tmp_path / "cg.json"
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--call-graph-dump", str(out),
         "hyperspace_tpu/serve"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(out.read_text(encoding="utf-8"))
    assert set(payload) == {"functions", "locks", "modules"}
    assert any(q.startswith("serve.server:QueryServer.") for q in payload["functions"])
    # phase 3: functions with device-value facts carry a valueflow entry
    assert any(
        "valueflow" in info for info in payload["functions"].values()
    )


def test_cli_check_suppressions_clean_tree_and_stale_detection(tmp_path):
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--check-suppressions"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert ", 0 stale" in proc.stdout
    stale = tmp_path / "stale.py"
    stale.write_text(
        "def f(x):\n"
        "    return x  # hslint: disable=HS001\n",
        encoding="utf-8",
    )
    proc2 = subprocess.run(
        [sys.executable, str(REPO / "scripts/lint.py"),
         "--check-suppressions", str(stale)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc2.returncode == 1
    assert "HS001 no longer fires" in proc2.stdout


def test_cli_changed_mode_filters_to_changed_files():
    # HEAD as the ref: a clean worktree (or one where only non-.py files
    # changed) reports nothing; the full model still builds — the mode's
    # contract is filtering, not skipping analysis
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--changed", "HEAD"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    bad_ref = subprocess.run(
        [sys.executable, "scripts/lint.py", "--changed",
         "no-such-ref-anywhere"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert bad_ref.returncode == 2


def test_cli_audit_and_dump_reject_no_project(tmp_path):
    # auditing with project rules off would report live HS009+
    # suppressions as stale; both combos are usage errors
    for flag in (["--check-suppressions"], ["--call-graph-dump",
                                            str(tmp_path / "cg.json")]):
        proc = subprocess.run(
            [sys.executable, "scripts/lint.py", "--no-project", *flag],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 2, (flag, proc.stdout, proc.stderr)


# --- phase 3 satellites: SARIF, finding cache, suppression budget -----------


def test_sarif_output_round_trips_and_validates():
    """--format sarif emits a SARIF 2.1.0 document: validated against a
    condensed schema of the spec's required shape (the full OASIS schema
    is network-hosted; the subset pins everything a consumer dereferences
    — version, driver rule catalog, result anchoring), then round-tripped
    against the JSON reporter for finding-for-finding agreement."""
    import jsonschema

    from hyperspace_tpu.analysis import render_sarif, run_analysis
    from hyperspace_tpu.analysis.rules import REGISTRY

    findings = run_analysis([REPO / t for t in LINT_TARGETS])
    doc = json.loads(render_sarif(findings, REGISTRY, base=REPO))

    subset_schema = {
        "type": "object",
        "required": ["version", "runs"],
        "properties": {
            "version": {"const": "2.1.0"},
            "$schema": {"type": "string", "pattern": "sarif-schema-2.1.0"},
            "runs": {
                "type": "array",
                "minItems": 1,
                "items": {
                    "type": "object",
                    "required": ["tool", "results"],
                    "properties": {
                        "tool": {
                            "type": "object",
                            "required": ["driver"],
                            "properties": {
                                "driver": {
                                    "type": "object",
                                    "required": ["name", "rules"],
                                    "properties": {
                                        "name": {"const": "hslint"},
                                        "rules": {
                                            "type": "array",
                                            "items": {
                                                "type": "object",
                                                "required": [
                                                    "id",
                                                    "name",
                                                    "shortDescription",
                                                ],
                                            },
                                        },
                                    },
                                }
                            },
                        },
                        "results": {
                            "type": "array",
                            "items": {
                                "type": "object",
                                "required": [
                                    "ruleId",
                                    "message",
                                    "locations",
                                ],
                                "properties": {
                                    "message": {
                                        "type": "object",
                                        "required": ["text"],
                                    },
                                    "locations": {
                                        "type": "array",
                                        "minItems": 1,
                                        "items": {
                                            "type": "object",
                                            "required": [
                                                "physicalLocation"
                                            ],
                                            "properties": {
                                                "physicalLocation": {
                                                    "type": "object",
                                                    "required": [
                                                        "artifactLocation",
                                                        "region",
                                                    ],
                                                    "properties": {
                                                        "region": {
                                                            "type": "object",
                                                            "required": [
                                                                "startLine",
                                                                "startColumn",
                                                            ],
                                                            "properties": {
                                                                "startLine": {
                                                                    "type": "integer",
                                                                    "minimum": 1,
                                                                },
                                                                "startColumn": {
                                                                    "type": "integer",
                                                                    "minimum": 1,
                                                                },
                                                            },
                                                        }
                                                    },
                                                }
                                            },
                                        },
                                    },
                                    "suppressions": {
                                        "type": "array",
                                        "items": {
                                            "type": "object",
                                            "required": ["kind"],
                                            "properties": {
                                                "kind": {
                                                    "enum": [
                                                        "inSource",
                                                        "external",
                                                    ]
                                                }
                                            },
                                        },
                                    },
                                },
                            },
                        },
                    },
                },
            },
        },
    }
    jsonschema.validate(doc, subset_schema)

    # round trip: one SARIF result per finding, suppression state and
    # rule catalog intact, columns converted 0->1 based exactly once
    results = doc["runs"][0]["results"]
    assert len(results) == len(findings)
    assert [r["ruleId"] for r in results] == [f.code for f in findings]
    for r, f in zip(results, findings):
        region = r["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] == f.line
        assert region["startColumn"] == f.col + 1
        assert bool(r.get("suppressions")) == f.suppressed
    catalog = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert {r.code for r in REGISTRY} <= catalog


def test_cli_sarif_format_is_parseable(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "CACHE = {}\n"
        "def put(k, v):\n"
        "    CACHE[k] = v\n",
        encoding="utf-8",
    )
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--format", "sarif", str(bad)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1  # exit contract unchanged by format
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert [r["ruleId"] for r in doc["runs"][0]["results"]] == ["HS006"]


def test_cache_replays_hits_and_invalidates_on_edit(tmp_path):
    """The cache contract both ways: a byte-identical rerun REPLAYS the
    stored findings (proven by doctoring the entry and watching the
    doctored verdict come back), and any source edit changes the key so
    the doctored entry is orphaned and the real analysis runs again."""
    target = tmp_path / "mod.py"
    target.write_text(
        "CACHE = {}\n"
        "def put(k, v):\n"
        "    CACHE[k] = v\n",
        encoding="utf-8",
    )
    cache_dir = tmp_path / "cache"

    def lint():
        return subprocess.run(
            [sys.executable, "scripts/lint.py", "--format", "json",
             "--cache-dir", str(cache_dir), str(target)],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )

    first = lint()
    assert first.returncode == 1
    assert json.loads(first.stdout)["summary"]["by_code"] == {"HS006": 1}
    entries = list(cache_dir.glob("*.json"))
    assert len(entries) == 1

    # doctor the entry: if the second run replays it, the cache was used
    entries[0].write_text(json.dumps({"findings": []}), encoding="utf-8")
    second = lint()
    assert second.returncode == 0
    assert json.loads(second.stdout)["summary"]["unsuppressed"] == 0

    # edit the source: new key, doctored entry orphaned, fresh analysis
    target.write_text(
        target.read_text(encoding="utf-8") + "\n# touched\n",
        encoding="utf-8",
    )
    third = lint()
    assert third.returncode == 1
    assert json.loads(third.stdout)["summary"]["by_code"] == {"HS006": 1}


def test_cli_no_cache_skips_read_and_write(tmp_path):
    target = tmp_path / "mod.py"
    target.write_text("x = 1\n", encoding="utf-8")
    cache_dir = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--no-cache",
         "--cache-dir", str(cache_dir), str(target)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0
    assert not cache_dir.exists()


def test_suppression_budget_is_pinned():
    """The tier-1 ratchet: the tree's suppression count stays at or
    under the audited pin. A NEW suppression must retire an old one or
    raise this number in the same diff — which is the review prompt the
    budget exists to force. (26 suppressed findings ride on 21 markers:
    a line-level marker covers every finding its rule raises there.)"""
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--check-suppressions",
         "--budget", "21"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert ", 0 stale" in proc.stdout


def test_suppression_budget_exceeded_fails(tmp_path):
    over = tmp_path / "over.py"
    over.write_text(
        "def f(dev):\n"
        "    return dev.item()  # hslint: disable=HS001 - fixture\n",
        encoding="utf-8",
    )
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts/lint.py"),
         "--check-suppressions", "--budget", "0", str(over)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1
    assert "budget exceeded" in proc.stdout


def test_budget_without_audit_is_usage_error():
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--budget", "5"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 2


# --- phase 3 acceptance: the HS016 fixture flips finding -> clean -----------


_HS016_BAKED = {
    "fac.py": (
        "import threading\n"
        "\n"
        "import jax\n"
        "\n"
        "_CACHE = {}\n"
        "_LOCK = threading.Lock()\n"
        "\n"
        "def counts_fn(lo, n_rows):\n"
        "    key = (lo, n_rows)\n"
        "    with _LOCK:\n"
        "        if len(_CACHE) > 64:\n"
        "            _CACHE.clear()\n"
        "        if key not in _CACHE:\n"
        "            def body(x):\n"
        "                return x + lo\n"
        "            _CACHE[key] = jax.jit(body)\n"
        "        return _CACHE[key]\n"
    ),
    "use.py": (
        "from .fac import counts_fn\n"
        "\n"
        "def run(x):\n"
        "    fn = counts_fn(3, 128)\n"
        "    return fn(x)\n"
    ),
}

_HS016_TRACED = {
    "fac.py": (
        "import threading\n"
        "\n"
        "import jax\n"
        "\n"
        "_CACHE = {}\n"
        "_LOCK = threading.Lock()\n"
        "\n"
        "def counts_fn(n_rows):\n"
        "    key = (n_rows,)\n"
        "    with _LOCK:\n"
        "        if len(_CACHE) > 64:\n"
        "            _CACHE.clear()\n"
        "        if key not in _CACHE:\n"
        "            def body(x, lo):\n"
        "                return x + lo\n"
        "            _CACHE[key] = jax.jit(body)\n"
        "        return _CACHE[key]\n"
    ),
    "use.py": (
        "from .fac import counts_fn\n"
        "\n"
        "def run(x):\n"
        "    fn = counts_fn(128)\n"
        "    return fn(x, 3)\n"
    ),
}


def test_hs016_acceptance_flip_through_cli(tmp_path):
    """End-to-end through scripts/lint.py: the literal-baked jit factory
    fires HS016 at the binding call site; rewriting it to the
    lits-vector discipline (literal masked from the key, shipped as a
    traced operand) flips the same tree to clean. This is the workflow a
    developer hits: finding -> apply the message's fix -> rerun -> green."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    for name, src in _HS016_BAKED.items():
        (pkg / name).write_text(src, encoding="utf-8")
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts/lint.py"), "--format", "json",
         "--no-cache", str(pkg)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["summary"]["by_code"] == {"HS016": 1}
    (finding,) = payload["findings"]
    assert finding["path"].endswith("use.py")
    assert "'lo'" in finding["message"]

    for name, src in _HS016_TRACED.items():
        (pkg / name).write_text(src, encoding="utf-8")
    proc2 = subprocess.run(
        [sys.executable, str(REPO / "scripts/lint.py"), "--format", "json",
         "--no-cache", str(pkg)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert json.loads(proc2.stdout)["summary"]["unsuppressed"] == 0


# --- phase 3: the PR's real fixes stay fixed --------------------------------


def test_real_fixes_are_pinned_in_the_flow_model():
    """The true positives HS015-HS019 surfaced were FIXED, not
    suppressed; this pins each fix in the value-flow model so a refactor
    that drops a trace call, an ensure_x64 anchor, or a decline counter
    resurfaces as a tier-1 failure with a named site, not a silent
    regression."""
    from hyperspace_tpu.analysis import run_analysis

    models = []
    run_analysis([REPO / "hyperspace_tpu"], model_sink=models)
    model = models[0]
    flow = model.device_flow()

    # HS019 fixes: every transfer leg reaches trace.add_bytes
    traced = flow.traced_reach()
    for qual in (
        "hyperspace_tpu.exec.distributed:distributed_filter",
        "hyperspace_tpu.exec.distributed:distributed_filter_aggregate",
        "hyperspace_tpu.exec.distributed:distributed_bucketed_join",
        "hyperspace_tpu.exec.hbm_cache:HbmIndexCache._build",
        "hyperspace_tpu.exec.mesh_cache:MeshHbmCache._build",
        "hyperspace_tpu.residency.streaming:_upload_window",
        "hyperspace_tpu.residency.streaming:_mesh_upload_window",
    ):
        assert qual in traced, f"{qual} lost its trace.add_bytes"

    # HS017 fixes: the x64 anchor at module import
    assert flow.module_x64("hyperspace_tpu.exec.scan_agg")
    assert flow.module_x64("hyperspace_tpu.exec.join_residency")

    # HS018 fixes: the silent tails now count their reasons
    for qual, n_min in (
        ("hyperspace_tpu.index.stream_builder:StreamingIndexWriter."
         "_try_stage_chunk", 1),
        ("hyperspace_tpu.exec.delta:prepare_hybrid_predicate", 1),
    ):
        fl = flow.flows.get(qual)
        assert fl is not None and fl.declined_incr, (
            f"{qual} no longer counts declines"
        )
