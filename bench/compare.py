"""The numbers that decide ``correct``: gaps between served and reference.

Each gap is the largest over the answers compared, so one wrong answer
shows. Estimates are compared relative to the reference; where the
reference's harmonic estimate lies within :data:`BRANCH_ZONE` of the
linear-counting switch at ``2.5 r``, rounding may pick either branch, and
the gap to the nearer branch counts.

The control (``control=True``) is the reference computed one precision
below the configuration's: registers in four bits (values above 15 held
at 15) and every estimate's arithmetic in bfloat16.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from bench import reference as R

__all__ = ["BRANCH_ZONE", "estimate_gap", "union_answers",
           "intersection_answers", "CONTROL_CAP"]

#: relative distance from the raw/linear switch inside which either
#: branch is a faithful evaluation of the estimator
BRANCH_ZONE = 1e-5
#: the control's register ceiling (four-bit registers)
CONTROL_CAP = 15
_BF16 = ml_dtypes.bfloat16


def _bf(x) -> np.ndarray:
    return np.asarray(np.asarray(x, np.float32).astype(_BF16), np.float64)


def row_stats_control(rows: np.ndarray) -> tuple:
    """(s, z) of rows with registers capped at 15 and s summed in bfloat16."""
    rows = np.minimum(np.asarray(rows), CONTROL_CAP)
    terms = np.exp2(-rows.astype(np.float32)).astype(_BF16)
    s = terms[..., 0]
    for j in range(1, terms.shape[-1]):          # bfloat16 accumulator
        s = (s + terms[..., j]).astype(_BF16)
    z = (rows == 0).sum(axis=-1)
    return np.asarray(s, np.float64), z.astype(np.float64)


def estimates(rows: np.ndarray, r: int, control: bool) -> tuple:
    """(estimate, raw, lin) per row: reference, or the control's."""
    if not control:
        return R.estimate_rows(rows, r)
    s, z = row_stats_control(rows)
    est, raw, lin = R.estimate_from_stats(s, z, r, dtype=np.float32)
    return _bf(est), _bf(raw), _bf(lin)


def estimate_gap(served, ref: tuple, r: int) -> np.ndarray:
    """Relative gap of each served estimate to the reference's."""
    served = np.asarray(served, np.float64)
    est, raw, lin = (np.asarray(x, np.float64) for x in ref)
    gap = np.abs(served - est) / np.maximum(est, 1.0)
    near = np.abs(raw - 2.5 * r) <= BRANCH_ZONE * 2.5 * r
    alt = np.minimum(np.abs(served - raw) / np.maximum(raw, 1.0),
                     np.abs(served - lin) / np.maximum(lin, 1.0))
    return np.where(near, np.minimum(gap, alt), gap)


def union_answers(rows: np.ndarray, r: int, control: bool = False) -> tuple:
    """Union estimates of sets ``rows[B, L, r]`` (all L rows are members)."""
    return estimates(np.max(rows, axis=1), r, control)


def intersection_answers(a: np.ndarray, b: np.ndarray, p: int, method: str,
                         control: bool = False) -> tuple:
    """(|A ∩ B| estimates, |A ∪ B| estimates) of row pairs ``[P, r]``.

    ``method`` is ``"mle"`` (Ertl's maximum likelihood) or ``"ie"``
    (inclusion-exclusion).
    """
    r, q = 1 << p, 64 - p
    if control:
        a, b = np.minimum(a, CONTROL_CAP), np.minimum(b, CONTROL_CAP)
    ea = estimates(a, r, control)[0]
    eb = estimates(b, r, control)[0]
    eu = estimates(np.maximum(a, b), r, control)[0]
    if method == "ie":
        out = ea + eb - eu
        return (_bf(out) if control else out), eu
    stats = R.pair_stats(a, b, q)
    if control:
        out = R.intersection_mle(stats, ea, eb, eu, q, r,
                                 state_round=_bf)
        return _bf(out), eu
    return R.intersection_mle(stats, ea, eb, eu, q, r), eu


def intersection_gap(served, ref: np.ndarray, union: np.ndarray
                     ) -> np.ndarray:
    """|served - reference| relative to the pair's union estimate."""
    served = np.asarray(served, np.float64)
    return np.abs(served - ref) / np.maximum(union, 1.0)
