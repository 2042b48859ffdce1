"""The numbers a training cell compares with the reference, each with
a limit of its own (the configuration file's ``correct`` group). Plain
Python: the harness, which may not import jax, does the comparing."""

from __future__ import annotations

from typing import Any, Dict, List



def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]
                   ) -> float:
    """The widest gap between the program's and the reference's norm of
    a leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    names: List[str] = sorted(reference)
    if sorted(program) != names:
        raise ValueError("program and reference name different leaves: "
                         f"{sorted(set(program) ^ set(names))[:6]}")
    med = sorted(reference.values())[len(names) // 2]
    return max(abs(program[n] - reference[n]) / max(reference[n], med)
               for n in names)


def training_rows(program: Dict[str, Any], ref: Dict[str, Any],
                  limits: Dict[str, float]) -> List[Dict[str, Any]]:
    loss_gap = max(abs(a - b)
                   for a, b in zip(program["losses"], ref["losses"]))
    return [
        {"name": "loss_gap_max", "value": loss_gap,
         "limit": limits["loss_gap_max"]},
        {"name": "first_grad_norm_gap", "limit": limits["first_grad_norm_gap"],
         "value": worst_leaf_gap(program["first_grad_norms"],
                                 ref["first_grad_norms"])},
        {"name": "param_change_norm_gap",
         "limit": limits["param_change_norm_gap"],
         "value": worst_leaf_gap(program["param_change_norms"],
                                 ref["param_change_norms"])},
    ]
