"""The comparison that decides ``correct``: every request retired from the
window's start (the window and the drain after it) against a plain numpy
reference of the same semantics, which imports nothing of the program.

Each check returns numbers compared, each with its limit.  The gather and
the chase both state exact results, so every limit is 0.

The controls put the reference in the program's place one step below the
stated guarantee: the gather's rows rounded to bfloat16 (what an MXU
contraction at default precision returns for an f32 table), and the
chase one hop short of its depth.  They are for ``control.py`` and the
tests; the benchmark's own runs never compute them.
"""

from __future__ import annotations

import numpy as np

BLOCK = 4096  # requests compared at a time


def bfloat16_round(x: np.ndarray) -> np.ndarray:
    """f32 -> the nearest bfloat16 (ties to even) -> f32."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def gather_checks(table: np.ndarray, keys: np.ndarray, answers: list, missing: int,
                  control: bool = False) -> tuple[dict, int]:
    """``keys`` is (n, K) row ids, ``answers`` the n (K, D) f32 blocks the
    system returned.  Returns ``({name: (value, limit)}, failed requests)``."""
    differing = wrong = 0
    for lo in range(0, len(answers), BLOCK):
        want = table[keys[lo : lo + BLOCK]]
        got = bfloat16_round(want) if control else np.stack(answers[lo : lo + BLOCK])
        if got.shape != want.shape:
            differ = np.ones(want.shape[:2], bool)
        else:
            differ = np.any(
                got.astype(np.float32).view(np.uint32) != want.view(np.uint32), axis=-1
            )
        differing += int(differ.sum())
        wrong += int(differ.any(axis=1).sum())
    checks = {"rows_differing": (differing, 0), "requests_missing": (missing, 0)}
    return checks, wrong + missing


def chase_reference(chain: np.ndarray, starts: np.ndarray, depth: int) -> np.ndarray:
    """Every chase at once: ``depth`` applications of the successor map."""
    a = np.asarray(starts, np.int64)
    for _ in range(depth):
        a = chain[a]
    return a


def chase_checks(chain: np.ndarray, starts: np.ndarray, answers: np.ndarray, depth: int,
                 missing: int, control: bool = False) -> tuple[dict, int]:
    want = chase_reference(chain, starts, depth)
    got = chase_reference(chain, starts, depth - 1) if control else np.asarray(answers)
    wrong = int(np.sum(got != want))
    checks = {"chases_differing": (wrong, 0), "chases_missing": (missing, 0)}
    return checks, wrong + missing


def check_cell(cell, records: list, missing: int, control: bool = False) -> tuple[dict, int]:
    """Compare ``records`` (the cell's ``Retired`` entries) against the
    reference; ``missing`` counts requests due that never retired."""
    idx = np.array([r.index for r in records], np.int64)
    asked = cell.traffic.pool[idx % len(cell.traffic.pool)]
    answers = [r.answer for r in records]
    if cell.kind == "gather":
        return gather_checks(cell.table, asked, answers, missing, control)
    return chase_checks(cell.chain, asked, np.array(answers, np.int64), cell.depth, missing,
                        control)


def passed(checks: dict) -> bool:
    return all(value <= limit for value, limit in checks.values())
