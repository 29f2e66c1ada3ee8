"""The per-test wall-clock limit of ``tests/conftest.py`` (``--test-limit``):
a test that waits past it fails by name, and the run goes on."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_a_test_that_sleeps_past_its_limit_fails_by_name_and_the_run_goes_on(
        tmp_path):
    case = tmp_path / "test_waits.py"
    case.write_text(textwrap.dedent("""
        import time


        def test_waits_for_what_never_comes():
            try:
                time.sleep(5)
            except Exception:       # a handler this broad must not eat it
                pass


        def test_the_one_behind_it():
            pass
        """))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "tests.conftest", str(case),
         "--test-limit", "1", "-q", "-p", "no:cacheprovider", "-p",
         "no:xdist", "-p", "no:randomly", "--rootdir", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    report = run.stdout + run.stderr
    assert run.returncode == 1, report
    assert "1 failed, 1 passed" in report, report
    assert "test limit: " in report and \
        "test_waits.py::test_waits_for_what_never_comes ran over its 1 s" \
        in report, report
