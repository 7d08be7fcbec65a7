"""The comparison that decides `correct` for a training cell.

The program's first steps, driven through the window's own call, against
the plain reference's same steps from the same weights and tokens:

  loss_gap    largest |loss - reference loss| / |reference loss| over the
              steps;
  grad_gap    the first (clipped) gradient as the optimizer got it, leaf by
              leaf: |norm - reference norm| over the larger of the
              reference's norm of that leaf and of the median leaf;
  update_gap  the same for the parameters' change over the steps.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both leaf comparisons: their gradient is nought to
rounding (a key's bias under softmax), and Adam moves them by round-off
alone.
"""
from __future__ import annotations

import statistics

ZERO_GRAD = 1e-3


def _leaf_gaps(got: dict, want: dict, keep) -> dict:
    med = statistics.median(want.values())
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in keep}


def _gap(got: dict, want: dict, keep) -> float:
    return max(_leaf_gaps(got, want, keep).values())


def gaps(prog: dict, ref: dict) -> dict:
    """Readings {"losses", "grad", "update"} of the program and of the
    reference -> the three numbers compared."""
    med = statistics.median(ref["grad"].values())
    keep = [k for k, g in ref["grad"].items() if g >= ZERO_GRAD * med]
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(prog['grad']) ^ set(ref['grad']))}")
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"], strict=True))
    return {"loss_gap": loss,
            "grad_gap": _gap(prog["grad"], ref["grad"], keep),
            "update_gap": _gap(prog["update"], ref["update"], keep)}


def worst_leaves(prog: dict, ref: dict) -> dict:
    """The leaf that reads grad_gap and the one that reads update_gap."""
    keep = [k for k in ref["grad"] if k not in left_out(ref)]
    out = {}
    for what in ("grad", "update"):
        g = _leaf_gaps(prog[what], ref[what], keep)
        out[what] = max(g, key=g.get)
    return out


def left_out(ref: dict) -> list:
    """The leaves the rule above leaves out."""
    med = statistics.median(ref["grad"].values())
    return sorted(k for k, g in ref["grad"].items() if g < ZERO_GRAD * med)


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every number beside its limit; a number is
    within its limit when it is at most the limit."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    ok = all(v == v and v <= limits[k] for k, v in numbers.items())
    return ok, checks
