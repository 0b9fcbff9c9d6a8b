"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, number by number, each beside its limit.

Pure host arithmetic on readings the runners collect, so the tests can feed
it broken readings and see it fail. The limits come from
``limits/<workload>.json`` (harness.Cell), with the readings they were set
from.
"""

from __future__ import annotations

import math
from statistics import median
from typing import Dict, List, Optional


class Check:
    """One number compared: ``value <= limit`` (or ``== limit`` when
    ``exact``)."""

    def __init__(self, name: str, value: float, limit: float,
                 exact: bool = False):
        self.name, self.value, self.limit = name, float(value), float(limit)
        self.ok = (self.value == self.limit) if exact else \
            (math.isfinite(self.value) and self.value <= self.limit)

    def as_dict(self) -> dict:
        return {"value": self.value, "limit": self.limit, "ok": self.ok}

    def line(self) -> str:
        return (f"check {self.name}: {self.value:.6g} "
                f"(limit {self.limit:.6g}) {'ok' if self.ok else 'FAILED'}")


# ------------------------------------------------------------------ training
def moved_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's. The others move by round-off alone
    and are left out of the change comparison (rule on the reference's
    gradient, never by name)."""
    floor = 1e-3 * median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items() if v >= floor]


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: Optional[List[str]] = None) -> Dict[str, float]:
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero). A leaf the
    program lacks, or whose norm is not a number, is infinitely wrong."""
    leaves = list(ref) if leaves is None else leaves
    med = median(ref[k] for k in leaves)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med)
            if k in prog and math.isfinite(prog[k]) else math.inf
            for k in leaves}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: Optional[List[str]] = None) -> float:
    """The largest leaf gap."""
    return max(leaf_gaps(prog, ref, leaves).values())


def median_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                    leaves: Optional[List[str]] = None) -> float:
    """The median leaf's gap: steady from seed to seed where the worst
    leaf is the rounding noise of one small leaf (PERF.md), and moved by
    whatever shifts most leaves at once, as a lower precision or a batch
    partly left out does."""
    gaps = leaf_gaps(prog, ref, leaves).values()
    return math.inf if math.inf in gaps else median(gaps)


def worst_leaves(prog: Dict[str, float], ref: Dict[str, float],
                 leaves: Optional[List[str]] = None, n: int = 5):
    """The ``n`` leaves with the largest gap, for the look a limit needs:
    ``(gap, leaf, program's norm, reference's norm)``."""
    gaps = leaf_gaps(prog, ref, leaves)
    rows = sorted(((g, k, prog.get(k, math.nan), ref[k])
                   for k, g in gaps.items()), reverse=True)[:n]
    return rows + [("median_norm", median(ref[k] for k in gaps))]


def training_checks(prog: dict, ref: dict, limits: Dict[str, float]
                    ) -> List[Check]:
    """``prog`` / ``ref``: ``losses`` (one per followed step),
    ``grad1_norms`` and ``change_norms`` per leaf."""
    checks = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        checks.append(Check(f"loss_step{i + 1}_rel_gap",
                            abs(a - b) / abs(b), limits["loss_rel_gap"]))
    moved = moved_leaves(ref["grad1_norms"])
    for stat, gap in (("worst", worst_leaf_gap), ("median", median_leaf_gap)):
        checks.append(Check(
            f"grad1_norm_{stat}_leaf_gap",
            gap(prog["grad1_norms"], ref["grad1_norms"]),
            limits[f"grad1_norm_{stat}_leaf_gap"]))
        checks.append(Check(
            f"change_norm_{stat}_leaf_gap",
            gap(prog["change_norms"], ref["change_norms"], moved),
            limits[f"change_norm_{stat}_leaf_gap"]))
    return checks


# ------------------------------------------------------------------- serving
def widest_token_gap(ref_logits, tokens) -> float:
    """Widest gap by which a token's logit lies below the reference's
    best, over the positions of one request. ``ref_logits`` [n, V]."""
    import numpy as np
    ref_logits = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens)
    best = ref_logits.max(axis=1)
    got = ref_logits[np.arange(len(tokens)), tokens]
    return float(np.max(best - got))


def print_checks(checks: List[Check], stream) -> None:
    for c in checks:
        print(c.line(), file=stream)
    stream.flush()
