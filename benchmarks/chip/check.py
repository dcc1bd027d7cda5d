"""The comparison that decides ``correct``.

What the run's timed path produced is set against the float32 reference
(``reference.py``), which makes its own weights from the seed and follows
the program's first three GRPO steps on the same batches:

- ``train_loss0_gap``: ``|loss - reference loss|`` of step 0, where both
  sides start from the same weights (the losses of steps 1-2 are not
  compared: they swing with the reference's own AdamW steps,
  whose sign-like first updates part from the program's wherever a
  gradient element sits at rounding level);
- ``train_grad_gap``: over the leaves, the largest gap between the norm
  of the first gradient as the optimizer got it (read back from AdamW's
  first moment after one step, ``mu / (1 - b1)``) and the reference's,
  over the larger of the reference leaf's norm and the median leaf's;
- ``train_update_gap``: the same for each leaf's change over the three
  steps;
- ``serve_v0_lp_gap``: the largest ``|engine logprob - reference
  logprob|`` of the served tokens of turns served on the seed's weights;
- ``barrier_weight_gap``: over every barrier of set-up and window and
  every engine, the largest gap of any element between the weights the
  engine took and the trainer's weights of that step, in the engine's
  own type (exact: the trainer's weights themselves are held to the
  reference by ``train_update_gap``);
- ``barrier_version_errors``: barriers after which an engine did not
  hold the step's weight version (exact).

Leaves whose reference gradient is under a thousandth of the median
leaf's take no part in the two leaf gaps: AdamW moves them by round-off
alone. Each number's limit is in the configuration file under
``limits``; a mix whose rows make rounding read differently names its
own under ``limits`` (``train_grad_gap`` grows with the rows' length).
``PERF.md`` gives the readings each was set from.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.chip import reference as R

SMALL_LEAF = 1e-3


@dataclass
class ProgramSide:
    """What the system under test produced, as the check needs it."""
    losses: List[float]
    grad_norms: Dict[str, float]
    change_norms: Dict[str, float]
    v0_turns: Sequence = ()
    versions: Dict[int, List[int]] = field(default_factory=dict)
    weight_gap: Optional[float] = None


def leaf_gap(side: Dict[str, float], ref: Dict[str, float],
             grads: Dict[str, float]) -> Optional[float]:
    """Worst leaf's ``|side - ref| / max(ref, median ref)`` over the
    leaves the reference gradient moves."""
    med_g = float(np.median(list(grads.values())))
    keep = [k for k in ref if grads[k] >= SMALL_LEAF * med_g]
    if not keep or med_g == 0.0:
        return None
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(side.get(k, 0.0) - ref[k]) / max(ref[k], med)
               for k in keep)


def lp_gap(served: Sequence, ref_lps: Sequence) -> Optional[float]:
    gaps = [np.max(np.abs(np.asarray(lp, np.float32) - r))
            for (_, _, lp), r in zip(served, ref_lps) if len(lp)]
    return float(max(gaps)) if gaps else None


def padded_length(turns: Sequence, multiple: int = 128) -> int:
    n = max(len(s) for s, _, _ in turns)
    return -(-n // multiple) * multiple


def reference_side(cfg: Dict, seed: int, batches: Sequence[Dict],
                   v0_turns: Sequence, lowp: bool) -> Dict:
    """The reference's own readings (``lowp``: the control's)."""
    losses, grads, change = R.follow_training(cfg, seed, batches, lowp=lowp)
    out = {"losses": losses, "grad_norms": grads, "change_norms": change}
    if v0_turns:
        out["v0"] = R.served_logprobs(cfg, R.init_params(cfg, seed),
                                      v0_turns, padded_length(v0_turns),
                                      lowp=lowp)
    return out


def numbers(side: ProgramSide, ref: Dict,
            served: Optional[Dict] = None) -> Dict[str, Optional[float]]:
    """The compared numbers of one side against the reference. ``served``
    replaces the side's own served logprobs (the control's)."""
    out = {}
    out["train_loss0_gap"] = (abs(side.losses[0] - ref["losses"][0])
                              if side.losses and ref["losses"] else None)
    out["train_grad_gap"] = leaf_gap(side.grad_norms, ref["grad_norms"],
                                     ref["grad_norms"])
    out["train_update_gap"] = leaf_gap(side.change_norms,
                                       ref["change_norms"],
                                       ref["grad_norms"])
    turns = side.v0_turns
    if turns and "v0" in ref:
        mine = ([(s, a, lp) for (s, a, _), lp in zip(turns, served["v0"])]
                if served is not None else turns)
        out["serve_v0_lp_gap"] = lp_gap(mine, ref["v0"])
    else:
        out["serve_v0_lp_gap"] = None
    out["barrier_weight_gap"] = side.weight_gap
    out["barrier_version_errors"] = float(sum(
        any(v != step for v in vs) for step, vs in side.versions.items()))
    return out


def compare(cfg: Dict, seed: int, batches: Sequence[Dict],
            program: ProgramSide) -> Dict[str, Optional[float]]:
    """Every number of the program against the reference."""
    ref = reference_side(cfg, seed, batches, program.v0_turns, lowp=False)
    return numbers(program, ref)


def checks(cfg: Dict, vals: Dict[str, Optional[float]],
           mix_limits: Optional[Dict[str, float]] = None) -> Dict[str, Dict]:
    """``{name: {"value", "limit"}}`` for each number the configuration
    gives a limit, or the mix where it names one; a number that could not
    be read has value None and fails. A number without a limit is read but
    not compared."""
    limits = dict(cfg["limits"], **(mix_limits or {}))
    return {k: {"value": vals[k], "limit": limit}
            for k, limit in limits.items()}


def control(cfg: Dict, seed: int, batches: Sequence[Dict],
            program: ProgramSide) -> Dict[str, Optional[float]]:
    """The control's numbers: the reference in float8 put in the
    program's place, against the float32 reference, on the same batches
    and turns. The control serves from the weights it trains, so its
    barrier numbers are the program's."""
    ref = reference_side(cfg, seed, batches, program.v0_turns, lowp=False)
    low = reference_side(cfg, seed, batches, program.v0_turns, lowp=True)
    side = ProgramSide(losses=low["losses"], grad_norms=low["grad_norms"],
                       change_norms=low["change_norms"],
                       v0_turns=program.v0_turns,
                       versions=program.versions,
                       weight_gap=program.weight_gap)
    return numbers(side, ref, served=low)


def correct(compared: Dict[str, Dict]) -> bool:
    return all(c["value"] is not None and np.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in compared.values())
