"""graftlint rule-engine tests: fixture corpus, suppressions, baseline
semantics, and the zero-cost annotation contract.

The fixture pairs under tests/fixtures/analysis/ are the rule spec in
executable form: each *_bad.py raises EXACTLY its rule (no cross-rule
noise) and each *_good.py is silent under EVERY rule.
"""

import json
import os
import pickle
import subprocess
import sys

import pytest

from deepspeed_tpu.analysis import (AnalysisConfig, analyze_file,
                                    analyze_source, apply_baseline,
                                    collect_findings, load_baseline,
                                    write_baseline)
from deepspeed_tpu.analysis.annotations import hot_path

FIXTURES = os.path.join(os.path.dirname(__file__), os.pardir,
                        "fixtures", "analysis")
RULES = ("HOSTSYNC", "RECOMPILE", "DONATION", "DETERMINISM", "THREADRACE")


def _fixture(name):
    return os.path.join(FIXTURES, name)


def _rules_hit(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------ fixture pairs

@pytest.mark.parametrize("rule", RULES)
def test_bad_fixture_raises_exactly_its_rule(rule):
    findings = analyze_file(_fixture(f"{rule.lower()}_bad.py"))
    assert findings, f"{rule} bad fixture produced no findings"
    assert _rules_hit(findings) == {rule}, (
        f"{rule} bad fixture leaked other rules: {findings}")


@pytest.mark.parametrize("rule", RULES)
def test_good_fixture_is_silent(rule):
    findings = analyze_file(_fixture(f"{rule.lower()}_good.py"))
    assert findings == [], f"{rule} good fixture is not clean: {findings}"


def test_bad_fixture_finding_counts():
    # Pin the exact count per bad fixture so a rule that silently stops
    # matching half its patterns fails loudly here, not in production.
    expected = {"HOSTSYNC": 7, "RECOMPILE": 3, "DONATION": 2,
                "DETERMINISM": 4, "THREADRACE": 1}
    for rule, want in expected.items():
        got = len(analyze_file(_fixture(f"{rule.lower()}_bad.py")))
        assert got == want, f"{rule}: expected {want} findings, got {got}"


# ------------------------------------------------------------ suppressions

def test_same_line_suppression():
    src = (
        "from deepspeed_tpu.analysis.annotations import hot_path\n"
        "@hot_path\n"
        "def decode_step(logits):\n"
        "    return logits.tolist()  # graftlint: disable=HOSTSYNC\n")
    assert analyze_source("fake.py", src) == []


def test_preceding_comment_suppression():
    src = (
        "from deepspeed_tpu.analysis.annotations import hot_path\n"
        "@hot_path\n"
        "def decode_step(logits):\n"
        "    # graftlint: disable=HOSTSYNC\n"
        "    return logits.tolist()\n")
    assert analyze_source("fake.py", src) == []


def test_suppression_is_per_rule():
    # A HOSTSYNC directive must NOT hide a DETERMINISM finding.
    src = (
        "import time\n"
        "from deepspeed_tpu.analysis.annotations import hot_path\n"
        "@hot_path\n"
        "def decode_step(logits):\n"
        "    return time.time()  # graftlint: disable=HOSTSYNC\n")
    findings = analyze_source("fake.py", src)
    assert _rules_hit(findings) == {"DETERMINISM"}


def test_disable_all_suppression():
    src = (
        "import time\n"
        "from deepspeed_tpu.analysis.annotations import hot_path\n"
        "@hot_path\n"
        "def decode_step(logits):\n"
        "    return time.time(), logits.tolist()  # graftlint: disable=all\n")
    assert analyze_source("fake.py", src) == []


def test_unsuppressed_line_still_fires():
    src = (
        "from deepspeed_tpu.analysis.annotations import hot_path\n"
        "@hot_path\n"
        "def decode_step(logits, cache):\n"
        "    a = logits.tolist()  # graftlint: disable=HOSTSYNC\n"
        "    return a, cache.tolist()\n")
    findings = analyze_source("fake.py", src)
    assert len(findings) == 1 and findings[0].line == 5


# ------------------------------------------------------------ baseline

def test_baseline_masks_known_findings(tmp_path):
    findings = analyze_file(_fixture("donation_bad.py"))
    baseline_path = tmp_path / "baseline.json"
    write_baseline(str(baseline_path), findings)
    baseline = load_baseline(str(baseline_path))
    new, stale = apply_baseline(findings, baseline)
    assert new == [] and stale == []


def test_stale_baseline_entry_fails(tmp_path):
    # Grandfather the bad fixture's findings, then "fix the code" by
    # analyzing the good twin: every baseline entry must surface STALE.
    bad = analyze_file(_fixture("donation_bad.py"))
    baseline_path = tmp_path / "baseline.json"
    write_baseline(str(baseline_path), bad)
    baseline = load_baseline(str(baseline_path))
    fixed = analyze_file(_fixture("donation_good.py"))
    new, stale = apply_baseline(fixed, baseline)
    assert new == []
    assert len(stale) == len(bad) and stale, (
        "fixed findings left in the baseline must be reported stale")


def test_baseline_is_additive_only_for_known_keys():
    # A NEW finding (not in baseline) must not be masked by unrelated entries.
    bad = analyze_file(_fixture("hostsync_bad.py"))
    other = analyze_file(_fixture("donation_bad.py"))
    new, stale = apply_baseline(bad, [f.to_dict() for f in other])
    assert len(new) == len(bad)
    assert len(stale) == len(other)


# ------------------------------------------------------------ config overrides

def test_module_allowlist_marks_hot_without_decorator():
    src = ("def decode_step(logits):\n"
           "    return logits.tolist()\n")
    cfg = AnalysisConfig(hot_path_functions={"fake.py": frozenset({"decode_step"})})
    findings = analyze_source("fake.py", src, cfg)
    assert _rules_hit(findings) == {"HOSTSYNC"}


def test_determinism_module_list_covers_whole_module():
    src = ("import time\n"
           "def pace():\n"
           "    return time.time()\n")
    cfg = AnalysisConfig(determinism_modules=("fake.py",))
    findings = analyze_source("fake.py", src, cfg)
    assert _rules_hit(findings) == {"DETERMINISM"}
    assert analyze_source("fake.py", src) == []  # not listed -> host code


def test_thread_checked_class_without_manifest():
    src = ("class ServingFleet:\n"
           "    def poke(self):\n"
           "        self._flag = 1\n")
    findings = analyze_source("fake.py", src)
    assert _rules_hit(findings) == {"THREADRACE"}


# ------------------------------------------------------------ ADAPTER rule
#
# Path-sensitive (fires only under deepspeed_tpu/inference/), so it is
# tested via analyze_source with synthetic paths instead of the fixture
# corpus — a fixture under tests/ would be out of the rule's scope.

_SERVING_PATH = "/x/deepspeed_tpu/inference/scheduler.py"


@pytest.mark.parametrize("src", [
    "from deepspeed_tpu.models import generation\n",
    "import deepspeed_tpu.models.generation\n",
    "from deepspeed_tpu.models.generation import decode_step\n",
])
def test_adapter_flags_generation_import_in_inference(src):
    findings = analyze_source(_SERVING_PATH, src)
    assert _rules_hit(findings) == {"ADAPTER"}, (src, findings)


def test_adapter_sanctions_gpt2_adapter_only():
    src = "from deepspeed_tpu.models import generation\n"
    gpt2 = "/x/deepspeed_tpu/inference/adapters/gpt2.py"
    assert analyze_source(gpt2, src) == []
    other = "/x/deepspeed_tpu/inference/adapters/longcontext.py"
    assert _rules_hit(analyze_source(other, src)) == {"ADAPTER"}


def test_adapter_silent_outside_inference():
    src = "from deepspeed_tpu.models import generation\n"
    assert analyze_source("/x/deepspeed_tpu/models/gpt2.py", src) == []
    assert analyze_source("/x/tests/unit/test_inference.py", src) == []


def test_adapter_allows_protocol_imports():
    src = ("from deepspeed_tpu.inference.adapters import GPT2Adapter\n"
           "from deepspeed_tpu.models import gpt2\n")
    assert analyze_source(_SERVING_PATH, src) == []


def test_adapter_rule_suppressible():
    src = ("from deepspeed_tpu.models import generation"
           "  # graftlint: disable=ADAPTER\n")
    assert analyze_source(_SERVING_PATH, src) == []


def test_adapter_rule_registered():
    from deepspeed_tpu.analysis.core import RULE_NAMES
    from deepspeed_tpu.analysis.rules import RULES as REGISTRY
    assert "ADAPTER" in RULE_NAMES
    assert "ADAPTER" in REGISTRY


# ------------------------------------------------------------ annotations

def test_hot_path_is_identity():
    def f(x):
        return x
    assert hot_path(f) is f
    assert f.__graftlint_hot_path__ is True
    assert not hasattr(f, "__wrapped__")


def test_hot_path_pickles_and_jits():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from deepspeed_tpu.models import generation

    # Module-level decorated functions pickle by reference — the
    # identity decorator keeps __module__/__qualname__ intact.
    blob = pickle.dumps(generation.decode_step)
    assert pickle.loads(blob) is generation.decode_step

    @hot_path
    def double(x):
        return x * 2

    out = jax.jit(double)(jnp.arange(4))
    assert out.tolist() == [0, 2, 4, 6]


def test_thread_owned_manifests_are_plain_frozensets():
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.fleet import ServingFleet
    assert isinstance(InferenceEngine._THREAD_OWNED, frozenset)
    assert isinstance(ServingFleet._THREAD_OWNED, frozenset)
    assert "_pool" in InferenceEngine._THREAD_OWNED
    assert ServingFleet._THREAD_OWNED == frozenset()


# ------------------------------------------------------------ CLI

def test_cli_json_on_fixture_dir(tmp_path):
    # One subprocess round-trip: exercises argparse, baseline plumbing,
    # exit codes, and the JSON artifact shape in one go.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis",
         _fixture("donation_bad.py"), "--baseline", "none",
         "--format", "json"],
        capture_output=True, text=True, env=env, timeout=240)
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["counts_by_rule"] == {"DONATION": 2}
    assert payload["stale_baseline"] == []
    assert payload["findings"][0]["rule"] == "DONATION"
