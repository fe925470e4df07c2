"""Helpers the per-layer metric readers share."""
from __future__ import annotations

import json

from bench.harness.spec import ROOT


def peaks(device_kind: str, root=ROOT) -> dict:
    """Published peaks of one chip, by JAX's ``device_kind``.  A kind that
    is not in ``bench/peaks.json`` is an error, not a default."""
    table = json.loads((root / 'bench' / 'peaks.json').read_text())
    if device_kind not in table['chips']:
        raise KeyError(f'no published peaks for device kind '
                       f'{device_kind!r} in bench/peaks.json')
    return table['chips'][device_kind]


def module_ms(ctx, metric: str):
    """Device self time per round, in ms, of the ops whose HLO source file
    lies in the modules that ``bench/metrics/<metric>.json`` lists."""
    modules = ctx.spec.metric_data(metric)['modules']
    return ctx.per_round_ms(ctx.module_seconds(modules))


def flops_module(ctx):
    return ctx.spec.module(f'bench/flops/{ctx.spec.config["flops"]}.py')
