"""Tests for the repro.analysis static-analysis engine and the JitGuard
recompilation sanitizer.

The rule tests run the real engine over fixture trees under
``tests/fixtures/analysis/`` — ``bad_tree`` reconstructs the pre-PR-8
watchdog race plus one representative of every lint class, ``good_tree``
is the same shape of code written correctly (waived designated sync,
cancel-disciplined worker). The fixtures are parsed, never imported.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import JitGuard, analyze
from repro.analysis import engine as ae
from repro.analysis.__main__ import main as analysis_main
from repro.core.fleet import Fleet
from repro.core.pipeline import PipelineConfig
from repro.data.synthetic import SceneSpec, make_scene, revisit_frames

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"
BAD = FIXTURES / "bad_tree"
GOOD = FIXTURES / "good_tree"
SRC = ae.REPO_ROOT / "src" / "repro"


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# thread-ownership race checker
# ---------------------------------------------------------------------------

def test_thread_rule_flags_pre_pr8_watchdog():
    """The reconstructed pre-PR-8 worker must trip both violation
    classes: cancel-free write-backs and a foreground-owned accumulator
    written from the worker thread."""
    findings, _ = analyze([BAD / "repro" / "core" / "contact_pre_pr8.py"])
    cancel = [f for f in findings if f.rule == "thread-ownership/cancel"]
    fg = [f for f in findings if f.rule == "thread-ownership/foreground"]
    assert len(cancel) >= 2, _rules(findings)
    assert any("counts_gd" in f.message for f in cancel)
    assert any("contact_stages" in f.message for f in cancel)
    assert len(fg) == 1 and "recount_s" in fg[0].message


def test_thread_rule_clean_on_current_contact():
    """The shipped (post-PR-8) ground segment honors the ownership map."""
    findings, _ = analyze([SRC / "core" / "contact.py"])
    assert [f for f in findings if f.rule.startswith("thread-ownership")] == []


def test_thread_rule_clean_on_good_fixture():
    findings, _ = analyze([GOOD / "repro" / "core" / "engine.py"])
    assert [f for f in findings if f.rule.startswith("thread-ownership")] == []


# ---------------------------------------------------------------------------
# host-sync-in-hot-path lint
# ---------------------------------------------------------------------------

def test_host_sync_flags_tainted_asarray_in_hot_module():
    findings, _ = analyze([BAD / "repro" / "core" / "engine.py"])
    sync = [f for f in findings if f.rule.startswith("host-sync")]
    assert len(sync) == 1
    assert sync[0].rule == "host-sync/asarray"
    assert sync[0].line == 13


def test_host_sync_waiver_suppresses_with_reason():
    findings, waived = analyze([GOOD / "repro" / "core" / "engine.py"])
    assert [f for f in findings if f.rule.startswith("host-sync")] == []
    assert any(f.rule == "host-sync/asarray" for f in waived)


def test_waiver_without_reason_is_itself_a_finding(tmp_path):
    mod = tmp_path / "repro" / "core" / "engine.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(
        "import jax\nimport numpy as np\n"
        "f = jax.jit(lambda x: x)\n"
        "# analysis: waive(host-sync):\n"
        "y = np.asarray(f(1.0))\n")
    findings, _ = analyze([mod], repo_root=tmp_path)
    assert any(f.rule == "waiver/missing-reason" for f in findings)


# ---------------------------------------------------------------------------
# determinism lints
# ---------------------------------------------------------------------------

def test_determinism_rules_each_fire_once():
    findings, _ = analyze([BAD / "repro" / "core" / "rng.py"])
    assert _rules(findings) == [
        "determinism/frozen-setattr",
        "determinism/global-rng",
        "determinism/random-module",
        "determinism/unseeded-rng",
        "determinism/wall-clock",
    ]


def test_frozen_setattr_allowed_in_post_init(tmp_path):
    mod = tmp_path / "repro" / "core" / "spec.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(
        "class Spec:\n"
        "    def __post_init__(self):\n"
        "        object.__setattr__(self, 'n', 4)\n")
    findings, _ = analyze([mod], repo_root=tmp_path)
    assert findings == []


# ---------------------------------------------------------------------------
# CLI: exit codes, baseline ratchet
# ---------------------------------------------------------------------------

def test_cli_bad_tree_exits_nonzero(tmp_path):
    rc = analysis_main([str(BAD), "--baseline", str(tmp_path / "bl.json")])
    assert rc == 1


def test_cli_shipped_tree_is_clean():
    """`python -m repro.analysis` on the shipped tree: exit 0 with the
    checked-in (empty) baseline — the acceptance gate for this PR."""
    assert analysis_main([]) == 0


def test_baseline_ratchet(tmp_path):
    bl = tmp_path / "baseline.json"
    # --update-baseline swallows the current findings and exits 0 ...
    assert analysis_main([str(BAD), "--baseline", str(bl),
                          "--update-baseline"]) == 0
    assert analysis_main([str(BAD), "--baseline", str(bl)]) == 0
    data = json.loads(bl.read_text())
    assert len(data["findings"]) > 0
    # ... but a NEW finding is never masked by old entries ...
    extra = tmp_path / "repro" / "core" / "fresh.py"
    extra.parent.mkdir(parents=True)
    extra.write_text("import numpy as np\nnp.random.seed(0)\n")
    assert analysis_main([str(BAD), str(extra.parent),
                          "--baseline", str(bl)]) == 1
    # ... and fixing findings leaves stale keys that --update drops
    assert analysis_main([str(GOOD), "--baseline", str(bl),
                          "--update-baseline"]) == 0
    assert json.loads(bl.read_text())["findings"] == {}


# ---------------------------------------------------------------------------
# JitGuard: jit-recompilation sanitizer
# ---------------------------------------------------------------------------

def test_jitguard_counts_fresh_compile_and_cached_silence():
    fn = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.arange(7, dtype=jnp.float32)
    with JitGuard("cold") as cold:
        fn(x).block_until_ready()
    assert cold.compilations >= 1
    with JitGuard("warm") as warm:
        fn(x).block_until_ready()
    assert warm.compilations == 0
    warm.assert_steady_state("cached call")


def test_jitguard_assert_raises_on_recompile():
    fn = jax.jit(lambda x: jnp.cos(x) + 1.0)
    fn(jnp.arange(5, dtype=jnp.float32)).block_until_ready()
    with JitGuard("churn") as g:
        # a fresh shape forces a new XLA program
        fn(jnp.arange(6, dtype=jnp.float32)).block_until_ready()
    with pytest.raises(AssertionError, match="churn"):
        g.assert_steady_state("shape churn")


def test_jitguard_fleet_rounds_reach_steady_state(counters):
    """Steady-state fleet ingest compiles ZERO new programs: identical
    frame shapes round over round must hit every jit cache (the runtime
    analogue of the PR 9 churn gate)."""
    space, ground = counters
    rng = np.random.default_rng(17)
    img, b, c = make_scene(rng, SceneSpec("jg", 256, (6, 12), (10, 20),
                                          cloud_fraction=0.2))
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    fleet = Fleet(space, ground, pcfg, n_sats=2)

    def round_(fl):
        fl.ingest([revisit_frames(rng, img, b, c, 1) for _ in range(2)])

    # warm-up rounds trace and compile the programs
    round_(fleet)
    round_(fleet)
    with JitGuard("fleet steady state") as g:
        round_(fleet)
        round_(fleet)
    g.assert_steady_state("steady-state ingest rounds")
    fleet.finalize()
