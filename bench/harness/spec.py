"""Finds a cell's files by the names in BENCHMARK.json.

A cell is one `workloads` entry: a configuration (`bench/configs/`, named
by the `configs` entry's `file`), a traffic mix (`bench/traffic/<traffic>
.json`), the limits its check holds the program to (`bench/limits/<cell>
.json`) and the per-layer metrics that list it (`bench/metrics/<metric>
.py`).  Adding any of these is adding files and entries; no code names
them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class Spec:
    def __init__(self, workload: str, root: Path = ROOT):
        self.root = Path(root)
        bench = json.loads((self.root / 'BENCHMARK.json').read_text())
        cells = {w['name']: w for w in bench['workloads']}
        if workload not in cells:
            raise KeyError(f'no workload {workload!r} in BENCHMARK.json '
                           f'(have {sorted(cells)})')
        self.bench = bench
        self.cell = cells[workload]
        self.name = workload
        configs = {c['name']: c for c in bench['configs']}
        self.config_entry = configs[self.cell['config']]
        self.config = self._json(self.config_entry['file'])
        self.traffic = self._json(f'bench/traffic/{self.cell["traffic"]}.json')
        self.limits = self._json(f'bench/limits/{workload}.json')
        self.chips = int(self.cell['chips'])

    def _json(self, rel: str) -> dict:
        return json.loads((self.root / rel).read_text())

    def end_to_end(self) -> list:
        return [m for m in self.bench['end_to_end'] if self._lists(m)]

    def per_layer(self) -> list:
        return [m for m in self.bench['per_layer'] if self._lists(m)]

    def _lists(self, metric: dict) -> bool:
        return 'workloads' not in metric or self.name in metric['workloads']

    def module(self, rel: str):
        """Import a file under the checkout by its relative path."""
        path = self.root / rel
        name = 'bench_dyn_' + rel.replace('/', '_').replace('.', '_').replace(
            '-', '_')
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def adapter(self):
        return self.module(f'bench/adapters/{self.config["adapter"]}.py')

    def metric_reader(self, name: str):
        return self.module(f'bench/metrics/{name}.py')

    def metric_data(self, name: str) -> dict:
        path = self.root / f'bench/metrics/{name}.json'
        return json.loads(path.read_text()) if path.exists() else {}
