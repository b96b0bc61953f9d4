"""reprolint (repro.analysis): golden fixtures, suppressions, CLI gating.

The fixtures under tests/fixtures/reprolint/ are the checker's own test
suite in both directions: seeded violations must be reported with the
right rule id and line, clean/suppressed files must pass, and the
shipped tree must be clean end to end (the same assertions the
``reprolint`` CI job makes via the CLI).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (
    BAD_SUPPRESSION,
    all_rules,
    load_metrics,
    load_stages,
    run,
)
from repro.analysis.core import check_file

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "reprolint"


def sites(findings):
    return sorted({(f.rule, f.line) for f in findings})


def check(name):
    return check_file(FIXTURES / name, all_rules())


# ---------------------------------------------------------------------------
# Golden fixtures: each seeded violation reported with the right id/line
# ---------------------------------------------------------------------------


def test_rl001_host_jnp_and_wall_clock():
    assert sites(check("rl001_host_jnp.py")) == [
        ("RL001", 13),  # jnp.concatenate
        ("RL001", 14),  # jnp.pad
        ("RL001", 20),  # time.time()
    ]


def test_rl002_stage_vocabulary():
    assert sites(check("rl002_stage_vocab.py")) == [
        ("RL002", 5),  # span("warp_speed")
        ("RL002", 7),  # add("decoed", ...)
        ("RL002", 8),  # observe(..., stage="telemetry")
        ("RL002", 12),  # annotate("evaluate")
        ("RL002", 14),  # open_span("decode_wait")
    ]


def test_rl003_metrics_discipline():
    assert sites(check("rl003_metrics.py")) == [
        ("RL003", 5),  # undeclared metric name
        ("RL003", 6),  # missing label key
        ("RL003", 7),  # f-string label value (cardinality bomb)
        ("RL003", 10),  # .observe() on a counter (+ label-set drift)
        ("RL003", 11),  # registration label drift
    ]


def test_rl004_lock_discipline():
    assert sites(check("rl004_locks.py")) == [
        ("RL004", 16),  # attr assigned without lock
        ("RL004", 17),  # dict item assigned without lock
        ("RL004", 18),  # .pop() without lock
        ("RL004", 24),  # .clear() after the with-block closed
    ]


def test_rl005_host_float64():
    assert sites(check("rl005_dtype.py")) == [
        ("RL005", 10),  # dtype=np.float32
        ("RL005", 11),  # .astype("float16")
    ]


# ---------------------------------------------------------------------------
# Suppression semantics
# ---------------------------------------------------------------------------


def test_suppression_with_reason_is_honored():
    assert check("suppressed_with_reason.py") == []


def test_suppression_without_reason_is_an_error_and_suppresses_nothing():
    got = sites(check("suppressed_no_reason.py"))
    assert (BAD_SUPPRESSION, 9) in got  # the bare ignore is itself reported
    assert ("RL001", 9) in got  # ... and the violation still surfaces


def test_clean_file_has_no_findings():
    assert check("clean.py") == []


def test_suppression_is_rule_scoped(tmp_path):
    # A justification for RL001 must not silence an unrelated rule.
    f = tmp_path / "mod.py"
    f.write_text(
        "# reprolint: host-path\n"
        "import time\n"
        "import jax.numpy as jnp\n"
        "# reprolint: monotonic-time\n"
        "def g(parts):\n"
        "    t = time.time()  # reprolint: ignore[RL005] -- wrong rule id\n"
        "    return jnp.concatenate(parts), t\n"
    )
    got = sites(check_file(f, all_rules()))
    assert ("RL001", 6) in got  # time.time() still reported
    assert ("RL001", 7) in got


# ---------------------------------------------------------------------------
# Vocabulary extraction matches the importable constants
# ---------------------------------------------------------------------------


def test_load_stages_matches_trace_module():
    from repro.serve.trace import STAGES

    assert load_stages() == tuple(STAGES)


def test_load_metrics_matches_obs_module():
    from repro.serve.obs import METRICS

    assert load_metrics() == METRICS
    for name, spec in load_metrics().items():
        assert spec["kind"] in {"counter", "gauge", "histogram"}, name
        assert isinstance(spec["labels"], tuple), name


def test_metrics_table_is_registered_one_to_one():
    # Every declared metric exists on a fresh engine's registry with the
    # declared kind — the engine supplies behavior, never vocabulary.
    from repro.serve.engine import CVEngine
    from repro.serve.obs import METRICS

    engine = CVEngine()
    for name, spec in METRICS.items():
        assert name in engine.metrics, name
        assert engine.metrics.get(name).kind == spec["kind"], name


# ---------------------------------------------------------------------------
# Tree-wide: the shipped tree is clean (same gate as the reprolint CI job)
# ---------------------------------------------------------------------------


def test_shipped_tree_is_clean():
    findings = run([str(REPO / "src"), str(REPO / "benchmarks")])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_every_in_tree_suppression_has_a_reason():
    from repro.analysis.core import iter_py_files, parse_file

    for path in iter_py_files([str(REPO / "src"), str(REPO / "benchmarks")]):
        ctx = parse_file(path)
        assert ctx.bare_suppression_lines == [], path


# ---------------------------------------------------------------------------
# CLI: exit codes + JSON output (what the CI job drives)
# ---------------------------------------------------------------------------


def _cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True,
        text=True,
        env=env,
        cwd=REPO,
    )


def test_cli_exits_zero_on_clean_and_nonzero_on_seeded():
    assert _cli(str(FIXTURES / "clean.py")).returncode == 0
    for seeded in sorted(FIXTURES.glob("rl00*.py")):
        proc = _cli(str(seeded))
        assert proc.returncode == 1, seeded.name
        assert seeded.name.split("_")[0].upper() in proc.stdout


def test_cli_json_output():
    proc = _cli("--json", str(FIXTURES / "rl005_dtype.py"))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["count"] == 2
    assert {f["rule"] for f in payload["findings"]} == {"RL005"}
    assert all(f["path"].endswith("rl005_dtype.py") for f in payload["findings"])


def test_cli_rule_filter():
    proc = _cli("--rules", "RL005", str(FIXTURES / "rl001_host_jnp.py"))
    assert proc.returncode == 0  # RL001 findings filtered out
    bad = _cli("--rules", "RL999", str(FIXTURES / "clean.py"))
    assert bad.returncode == 2  # argparse error for unknown rule


@pytest.mark.parametrize("rule_id", ["RL001", "RL002", "RL003", "RL004", "RL005"])
def test_rule_table_lists_every_rule(rule_id):
    assert rule_id in {r.id for r in all_rules()}
