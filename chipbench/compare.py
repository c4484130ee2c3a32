"""The numbers that decide ``correct``: the program against the plain
reference, each reduced to one number that a limit bounds.

Training (the three rounds set-up drives through the window's own
call): per round the loss, and by the worst leaf the norm of the first
update and of the parameters' change after three rounds. A leaf is one
perceptron unitary. The "gradient as the optimizer gets it" of a
QuantumFed round is the unitary the server applied, ``G = U_1 U_0†``,
whose distance from the identity ``|U_1 - U_0|_F`` is to first order
``eps |sum_n w_n sum_k K_{n,k}|_F``; it is read from the state after one
round. Norm gaps are taken as ``abs(|p| - |r|)``, not ``|p - r|``, over
``max(|r_leaf|, |r_median leaf|)``. A leaf whose first update in the
reference is under a thousandth of the median leaf's moves by round-off
alone and is left out.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

SKIP_BELOW = 1e-3


def _leaves(params) -> List[np.ndarray]:
    """Every perceptron unitary of a state, as host arrays."""
    out = []
    for layer in params:
        layer = np.asarray(layer)
        out.extend(layer[j] for j in range(layer.shape[0]))
    return out


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.abs(x) ** 2)))


def _worst_norm_gap(prog_delta: Sequence[np.ndarray],
                    ref_delta: Sequence[np.ndarray],
                    keep: Sequence[bool]) -> float:
    rn = np.array([_norm(d) for d in ref_delta])
    pn = np.array([_norm(d) for d in prog_delta])
    med = float(np.median(rn[np.asarray(keep)]))
    gaps = [abs(p - r) / max(r, med) for p, r, k in zip(pn, rn, keep) if k]
    return float(max(gaps))


def training(prog_states, ref_states, prog_loss, ref_loss) -> Dict[str, float]:
    """``*_states``: the state before round 1 and after rounds 1..3
    (the first is the same input on both sides); ``*_loss``: the loss
    after rounds 1..3."""
    p = [_leaves(s) for s in prog_states]
    r = [_leaves(s) for s in ref_states]
    ref_first = [b - a for a, b in zip(r[0], r[1])]
    rn = np.array([_norm(d) for d in ref_first])
    keep = list(rn >= SKIP_BELOW * float(np.median(rn)))
    grad = _worst_norm_gap([b - a for a, b in zip(p[0], p[1])], ref_first,
                           keep)
    change = _worst_norm_gap([b - a for a, b in zip(p[0], p[3])],
                             [b - a for a, b in zip(r[0], r[3])], keep)
    loss = max(abs(float(a) - float(b)) / abs(float(b))
               for a, b in zip(prog_loss, ref_loss))
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}


def state_gap(prog_params, ref_params) -> float:
    """Worst leaf ``|P - R|_F / |R|_F`` of two states."""
    return float(max(_norm(a - b) / _norm(b)
                     for a, b in zip(_leaves(prog_params),
                                     _leaves(ref_params))))
