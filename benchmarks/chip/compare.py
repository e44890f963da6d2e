"""The numbers that decide ``correct``: what the timed path produced, set
against the plain reference.

Training (every step of the session: those set-up drives through the
window's own step, and the window's): the relative gap of each step's
loss; for each leaf, the gap between the program's and the reference's
norm of the first gradient as the optimizer gets it, and of the
parameters' change over all the steps.
A leaf's gap is taken against the larger of the reference's norm of that
leaf and of the median leaf, and the worst leaf is the reading.  Leaves
whose reference gradient is under a thousandth of the median leaf's move
by round-off alone and are left out of the change.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

STILL = 1e-3       # a leaf whose gradient is under this share of the median's


def leaf_norms(tree) -> Dict[str, float]:
    """'a/b/c' -> float32 norm of each leaf."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out[name] = float(jnp.linalg.norm(leaf.astype(jnp.float32).ravel()))
    return out


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   leaves: Sequence[str]) -> float:
    floor = float(np.median([want[k] for k in want]))
    return max(abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
               for k in leaves)


def train_readings(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses``, ``grad_norms`` and
    ``delta_norms`` (see ``references.*.train``)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = float("inf")
    g = ref["grad_norms"]
    med = float(np.median(list(g.values())))
    moving = [k for k in g if g[k] >= STILL * med]
    return {
        "loss_gap": float(loss_gap),
        "grad_gap": float(worst_leaf_gap(prog["grad_norms"], g, list(g))),
        "delta_gap": float(worst_leaf_gap(prog["delta_norms"],
                                          ref["delta_norms"], moving)),
    }


def token_gaps(served: Sequence[Sequence[int]],
               ref_logits: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Per request: the reference's best logit minus its logit of each
    served token."""
    out = []
    for toks, lg in zip(served, ref_logits):
        toks = np.asarray(toks, np.int64)
        out.append(lg.max(-1) - lg[np.arange(len(toks)), toks])
    return out


def decode_readings(served, ref_logits) -> Dict[str, float]:
    gaps = token_gaps(served, ref_logits)
    return {"token_gap": float(max(g.max() for g in gaps))}
