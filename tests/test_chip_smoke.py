"""CPU rehearsal of ``chip_smoke.py``: the same phases on the same three
networks, cut to 32px and run with the interpret-mode Pallas backend —
wrong paths, arguments and control flow show here, not on the chip.  And
the script itself refuses to run without a TPU, printing no result."""
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_one_chip_phases_rehearsed_on_cpu(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    report = chip_smoke.one_chip(backend="pallas", resolution=32,
                                 buckets=(1, 2))
    assert sorted(report) == sorted(chip_smoke.MODELS)
    for key, r in report.items():
        assert r["max_rel_err"] <= chip_smoke.REF_TOL, key
        assert r["n"] == 3           # one alone, then a bucket of two


def test_reference_mismatch_fails_the_phase(monkeypatch, tmp_path):
    """A served logit vector outside REF_TOL fails the phase (the script
    then exits non-zero before its last line)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(chip_smoke.SmokeFailure, match="exceeds"):
        chip_smoke.one_chip(chip_smoke.MODELS[:1], backend="pallas",
                            resolution=32, buckets=(1, 2), tol=0.0)
