"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against the limit the configuration file states
(``limits``), set from readings of sound runs, of the control and of
the faults as ``PERF.md`` records:

* ``loss_gap``: the largest gap, over the first steps, between the
  program's loss and the reference's, in nats;
* ``grad_gap``: over the leaves, the largest gap between the norm of the
  program's first gradient (as its optimizer got it) and the
  reference's, over the larger of the reference's norm of that leaf and
  of the median leaf;
* ``delta_gap``: the same for the change of the weights over the first
  steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (a leaf under that moves by round-off
  alone).
"""
from __future__ import annotations

import statistics

TINY_GRAD = 1e-3


def _worst(prog, ref, keep) -> float:
    med = statistics.median(ref[i] for i in keep)
    return max(abs(prog[i] - ref[i]) / max(ref[i], med) for i in keep)


def train_gaps(prog: dict, ref: dict) -> dict:
    """The three numbers from the program's and the reference's
    readings (``losses``, ``grad_norms``, ``delta_norms``)."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                               ref["losses"]))
    g = ref["grad_norms"]
    every = range(len(g))
    med_g = statistics.median(g)
    moved = [i for i in every if g[i] >= TINY_GRAD * med_g]
    return {"loss_gap": loss_gap,
            "grad_gap": _worst(prog["grad_norms"], g, every),
            "delta_gap": _worst(prog["delta_norms"], ref["delta_norms"],
                                moved)}


def train_checks(prog: dict, ref: dict, cfg: dict) -> dict:
    """``{name: {"value": gap, "limit": limit}}`` for each number the
    configuration gives a limit; a number with none (no control or fault
    reads far enough above sound runs to set one) is not compared."""
    gaps = train_gaps(prog, ref)
    return {k: {"value": gaps[k], "limit": limit}
            for k, limit in cfg["limits"].items()}


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
