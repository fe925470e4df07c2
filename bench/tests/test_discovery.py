"""A cell, a traffic mix and a per-layer metric are added as files and
entries only: the harness finds them by name."""
import json

from bench.harness import readers
from bench.harness.spec import Spec


def test_new_cell_and_metric_are_found_without_code_changes(tiny_root):
    bench = json.loads((tiny_root / 'BENCHMARK.json').read_text())
    traffic = json.loads(
        (tiny_root / 'bench/traffic/k20.packed.json').read_text())
    traffic['clients'] = 8
    (tiny_root / 'bench/traffic/k8.packed.json').write_text(
        json.dumps(traffic))
    bench['workloads'].append({
        'name': 'paper-cnn.k8.packed', 'config': 'paper-cnn',
        'traffic': 'k8.packed', 'chips': 1, 'why': 'test cell'})
    (tiny_root / 'bench/limits/paper-cnn.k8.packed.json').write_text(
        json.dumps({'limits': {'loss_gap': 0.1}}))
    bench['per_layer'].append({
        'name': 'other_ms', 'unit': 'ms', 'better': 'lower',
        'source': 'device_trace', 'layer': 'round step',
        'moves': 'rounds_per_s', 'workloads': ['paper-cnn.k8.packed']})
    (tiny_root / 'bench/metrics/other_ms.json').write_text(json.dumps(
        {'layer': 'round step', 'modules': ['repro/training/']}))
    (tiny_root / 'bench/metrics/other_ms.py').write_text(
        'from bench.harness.readers import module_ms\n\n\n'
        'def read(ctx):\n    return module_ms(ctx, "other_ms")\n')
    (tiny_root / 'BENCHMARK.json').write_text(json.dumps(bench))

    spec = Spec('paper-cnn.k8.packed', tiny_root)
    assert spec.traffic['clients'] == 8
    assert spec.limits == {'limits': {'loss_gap': 0.1}}
    assert spec.config['adapter'] == 'fl_cnn'
    names = [m['name'] for m in spec.per_layer()]
    assert 'other_ms' in names and 'kernel_ms' not in names

    class Ctx:
        rounds = 4
        spec = None

        def module_seconds(self, modules):
            assert modules == ['repro/training/']
            return 0.02

        def per_round_ms(self, seconds):
            return 1e3 * seconds / self.rounds
    ctx = Ctx()
    ctx.spec = spec
    assert spec.metric_reader('other_ms').read(ctx) == 5.0
    assert readers.module_ms(ctx, 'other_ms') == 5.0


def test_every_declared_cell_and_metric_has_its_files(tiny_root):
    bench = json.loads((tiny_root / 'BENCHMARK.json').read_text())
    for cell in bench['workloads']:
        spec = Spec(cell['name'], tiny_root)
        assert spec.limits['limits']
        assert (tiny_root / 'bench/adapters'
                / f'{spec.config["adapter"]}.py').exists()
    for metric in bench['per_layer']:
        assert (tiny_root / f'bench/metrics/{metric["name"]}.py').exists()
