"""Tests of the benchmark itself (`pytest bench/tests`): they run on the
CPU at a small size, in copies of the benchmark made under tmp_path."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / 'src')]
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

TINY = {'clients': 4, 'per_client': 16,
        'segment_rounds': 4}


def make_root(tmp: Path, traffic_overrides=None) -> Path:
    """A checkout-shaped copy: BENCHMARK.json, bench/ and a link to src/,
    with every traffic file shrunk to a CPU size."""
    root = tmp / 'checkout'
    root.mkdir()
    shutil.copy(ROOT / 'BENCHMARK.json', root / 'BENCHMARK.json')
    shutil.copytree(ROOT / 'bench', root / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    (root / 'src').symlink_to(ROOT / 'src')
    for path in (root / 'bench' / 'traffic').glob('*.json'):
        data = json.loads(path.read_text())
        data.update(TINY if traffic_overrides is None else traffic_overrides)
        path.write_text(json.dumps(data))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
