"""Readings that decide `correct` for a federated training segment: what
the program's first segment produced against the plain reference that
followed the same rounds from the same inputs, transmitting with the same
(q, p)."""
from __future__ import annotations

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone and is left out of the leaf comparisons
NOUGHT = 1e-3


def _rel(a, b) -> float:
    a, b = float(a), float(b)
    if not (np.isfinite(a) and np.isfinite(b)):
        return float('inf')
    return abs(a - b) / max(abs(b), 1e-30)


def _leaf_norms(flat, sizes) -> np.ndarray:
    edges = np.cumsum([0] + list(sizes))
    return np.array([np.linalg.norm(np.asarray(flat[a:b], np.float64))
                     for a, b in zip(edges[:-1], edges[1:])])


def worst_leaf_gap(got, want, sizes, keep) -> float:
    """The worst leaf's gap of norms, | |got| - |want| |, over the larger
    of that leaf's reference norm and the median leaf's."""
    g, w = _leaf_norms(got, sizes)[keep], _leaf_norms(want, sizes)[keep]
    if not np.all(np.isfinite(g)):
        return float('inf')
    scale = np.maximum(w, np.median(w))
    return float(np.max(np.abs(g - w) / np.maximum(scale, 1e-30)))


def _obj_rel(a, b, scale) -> float:
    a, b = float(a), float(b)
    if not (np.isfinite(a) and np.isfinite(b)):
        return float('inf')
    return abs(a - b) / max(scale, 1e-30)


def fl_readings(run: dict, ref: dict, params0, leaves: dict) -> dict:
    """Numbers compared between a run's segment and the reference:

    loss_gap       worst relative gap of a round's mean client loss
                   (gradient pass; from round 1 on, the updates before);
                   loss_gap.r<i> and objective_gap.r<i> are round i's
    objective_gap  worst gap between the eq. (28) objective the run
                   reports and the reference's float64 objective of the
                   same (q, p) on its own statistics (the allocation's
                   inputs: every client's gradient moments)
    alloc_gap      worst excess of that objective over the reference's
                   float64 optimum, in the rounds that solve (the
                   allocation's answer); alloc_gap.median the median
                   round's, which a single round's near-tied local optimum
                   does not move
    outcome_gap    packet outcomes (sign_ok, mod_ok) that differ, over all
                   rounds (the channel)
    agreement_gap  worst |gap| of the packed decode's sign agreement
                   (packed wire only; the decode)
    update_gap     worst leaf's gap of the norms of the parameters' change
                   over the segment (the update)
    aggregate_gap  the same for the compensation vector, |the last
                   round's aggregate| (the aggregate)

    The objective's gaps are taken over the sum of the magnitudes of its
    terms, which have either sign.  Leaves whose round-0 reference
    gradient is under NOUGHT of the median leaf's are left out of the last
    two.
    """
    rounds = len(ref['loss'])
    sizes = list(leaves.values())
    g0 = _leaf_norms(ref['grad0'], sizes)
    keep = g0 >= NOUGHT * np.median(g0)
    excess = {i: (ref['objective'][i] - ref['optimum'][i])
              / ref['objective_scale'][i] for i in range(rounds)
              if np.isfinite(ref['optimum'][i])}
    obj_gaps = [_obj_rel(run['objective'][i], ref['objective'][i],
                         ref['objective_scale'][i]) for i in range(rounds)]
    out = {
        'loss_gap': max(_rel(run['loss'][i], ref['loss'][i])
                        for i in range(rounds)),
        'objective_gap': max(obj_gaps),
        'alloc_gap': max(excess.values(), default=0.0),
        'alloc_gap.median': float(np.median(list(excess.values())))
        if excess else 0.0,
        'outcome_gap': float(sum(
            np.sum(run['sign_ok'][i] != ref['sign_ok'][i])
            + np.sum(run['mod_ok'][i] != ref['mod_ok'][i])
            for i in range(rounds))),
        'update_gap': worst_leaf_gap(run['params'] - params0,
                                     ref['params'] - params0, sizes, keep),
        'aggregate_gap': worst_leaf_gap(run['gbar'], ref['gbar'], sizes,
                                        keep),
    }
    if np.isfinite(ref['agreement'][0]):
        out['agreement_gap'] = max(abs(run['agreement'][i]
                                       - ref['agreement'][i])
                                   for i in range(rounds))
    for i in range(rounds):
        out[f'loss_gap.r{i}'] = _rel(run['loss'][i], ref['loss'][i])
        out[f'objective_gap.r{i}'] = obj_gaps[i]
        if i in excess:
            out[f'alloc_gap.r{i}'] = excess[i]
    out['leaves_left_out'] = float(np.sum(~keep))
    return {k: (float(v) if np.isfinite(v) else float('inf'))
            for k, v in out.items()}
