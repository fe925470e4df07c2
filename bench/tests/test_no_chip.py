"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, 'bench/run.py', '--workload',
         'paper-cnn.k20.packed', '--seed', '3', '--seconds', '1',
         '--trace', '0'], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_exits_nonzero_without_a_tpu(tiny_root):
    proc = _run(tiny_root)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert 'TPU' in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / 'BENCHMARK.json', tmp_path / 'BENCHMARK.json')
    shutil.copytree(ROOT / 'bench', tmp_path / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
