"""What ``run.py`` does where it must not measure."""
import os
import shutil
import subprocess
import sys

import pytest

import run
import spec

RUN = os.path.join(spec.HERE, "run.py")


def test_refuses_the_cpu_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "deepseek7b.longdoc_bucket",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "deepseek7b.longdoc_bucket", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        run.main(["--workload", "no-such-cell"])


def test_numerics_the_program_cannot_run_raise():
    numerics = dict(spec.config("deepseek-7b")["numerics"], init_tokens=16)
    with pytest.raises(ValueError, match="init_tokens"):
        run.quant_config(numerics)


def test_seed_key_uses_all_64_bits():
    import numpy as np
    a, b = run.seed_key(5), run.seed_key(5 + 2 ** 32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(run.seed_key(2 ** 31 + 1)),
                          np.asarray(run.seed_key(2 ** 31 + 1)))
