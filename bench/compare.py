"""The numbers that decide ``correct``: a run's readings against the
reference's, for the first steps of a training cell and for served tokens.

Readings are plain dicts of host numbers:

* ``losses``: the loss of each of the first steps;
* ``grad``: {leaf: norm} of the first gradient as the optimizer got it;
* ``update``: {leaf: norm} of the parameters' change over the first steps.

Norms are compared leaf by leaf, each gap against the reference's norm of
that leaf or of the median leaf, whichever is larger, and the worst leaf
is the number.  Leaves whose reference gradient is under a thousandth of
the median leaf's move by round-off alone and are left out of the change.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable

import numpy as np

#: a leaf whose reference gradient norm is below this share of the median
#: leaf's is left out of the parameter-change number
ROUNDOFF_SHARE = 1e-3


def rel_gap(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def loss_gap(prog: Iterable[float], ref: Iterable[float]) -> float:
    """Largest relative gap of one step's loss."""
    return max(rel_gap(a, b) for a, b in zip(prog, ref, strict=True))


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: Iterable[str] = None) -> float:
    """Worst leaf's |prog norm - ref norm| / max(ref norm, median ref norm)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = statistics.median(float(v) for v in ref.values())
    keys = list(ref) if leaves is None else list(leaves)
    return max(abs(float(prog[k]) - float(ref[k]))
               / max(float(ref[k]), med, 1e-30) for k in keys)


def moved_leaves(ref_grad: Dict[str, float]):
    """Leaves whose reference gradient is not nought to round-off."""
    med = statistics.median(float(v) for v in ref_grad.values())
    return [k for k, v in ref_grad.items() if float(v) >= ROUNDOFF_SHARE * med]


def training(prog: Dict, ref: Dict) -> Dict[str, float]:
    """loss_gap, grad_norm_gap and update_norm_gap of one training run."""
    return {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "grad_norm_gap": norm_gap(prog["grad"], ref["grad"]),
        "update_norm_gap": norm_gap(prog["update"], ref["update"],
                                    moved_leaves(ref["grad"])),
    }


def served_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which a served token's logit lies below the reference's
    best at its position.  ref_logits (..., vocab), tokens (...)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    chosen = np.take_along_axis(ref_logits, np.asarray(tokens)[..., None],
                                -1)[..., 0]
    return float(np.max(ref_logits.max(-1) - chosen))
