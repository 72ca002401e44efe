"""The numbers ``correct`` compares, each against its limit.

The reference follows each run that set-up checked (see
``bench/harness/train.py``) from the same seeded weights and rows.  Each
number is the worst over the runs:

* ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over every step of every run;
* ``grad_gap``: each run's first gradient as the optimizer got it
  (clipped), worked out from the program's Adam state after one step
  (m_1 = (1 - beta1) g); per leaf, the gap between the program's norm and
  the reference's, over the larger of the reference's norm of that leaf and
  its median leaf norm; the worst leaf;
* ``update_gap``: the same for the norm of each leaf's change over the
  run of three steps, leaving out leaves whose reference gradient is under
  a thousandth of the median leaf's (they move by round-off alone).  A
  run of one step has no change to compare: Adam's first step moves every
  weight by about the learning rate, whatever its gradient.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

NEGLIGIBLE_GRAD = 1e-3  # of the median leaf's gradient norm


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def norm_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Sequence[bool] = None) -> float:
    if len(prog) != len(ref):
        raise ValueError(f"{len(prog)} program leaves vs {len(ref)}")
    med = statistics.median(ref)
    worst = 0.0
    for i, (p, r) in enumerate(zip(prog, ref)):
        if keep is not None and not keep[i]:
            continue
        gap = abs(p - r) / max(r, med, 1e-30)
        if gap != gap:  # NaN: the program's norm is not a number
            return float("inf")
        worst = max(worst, gap)
    return worst


def train_numbers(runs: Sequence[dict]
                  ) -> Tuple[Dict[str, float], List[Dict[str, float]]]:
    """``runs``: per checked run its ``seq`` and the program's and the
    reference's ``*_losses``, ``*_grad`` and ``*_delta`` (None for a run
    of one step).  Returns the worst numbers and each run's own."""
    per_run = []
    for r in runs:
        loss_gap = max(abs(p - q) / abs(q) if p == p else float("inf")
                       for p, q in zip(r["prog_losses"], r["ref_losses"]))
        got = {"seq": r["seq"], "loss_gap": loss_gap,
               "grad_gap": norm_gap(r["prog_grad"], r["ref_grad"])}
        if r["prog_delta"] is not None:
            med = statistics.median(r["ref_grad"])
            keep = [g >= NEGLIGIBLE_GRAD * med for g in r["ref_grad"]]
            got["update_gap"] = norm_gap(r["prog_delta"], r["ref_delta"],
                                         keep)
        per_run.append(got)
    worst = {name: max(g[name] for g in per_run if name in g)
             for name in ("loss_gap", "grad_gap", "update_gap")}
    return worst, per_run


def checks(numbers: Dict[str, float], limits: Dict[str, dict]
           ) -> List[Check]:
    """The numbers the cell's limits file compares.  A number it lists
    with ``"limit": null`` had no reading that a fault or the control gave
    above the program's (see that file) and is not compared."""
    out = []
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        if limits[name]["limit"] is None:
            continue
        out.append(Check(name, float(value), float(limits[name]["limit"])))
    return out
