"""The work a call needs, counted from its shapes by the algorithm's
measure, not the implementation's, so that every later implementation
is held to the same yardstick.

* A CIM MVM ``(M, K) int8 @ (K, N) int8 -> (M, N) int32`` needs
  ``2 M K N`` integer operations and moves at least its operands once
  and its int32 result once: ``M K + K N + 4 M N`` bytes.  Unpadded
  shapes; the 8 bit-planes of the bit-serial kernel are not counted, so
  that kernel can reach at most an eighth of the operations bound.
* A stage pass over ``n`` decode rows for ``machines`` machines reads
  its 13 int64 input columns (``op``, ``starts`` and 11 operand
  columns) once and writes one int64 latency per row and machine plus
  11 resolved int64 columns once: ``8 n (13 + machines + 11)`` bytes.
  Padding to the bucket and the dense per-register intermediates are
  not counted.
"""

from __future__ import annotations

from typing import Dict

__all__ = ["mvm_ops", "mvm_bytes", "mvm_least_s", "stage_pass_bytes",
           "stage_pass_least_s"]

STAGE_INPUT_COLS = 13
STAGE_RESOLVED_COLS = 11
WORD = 8


def mvm_ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def mvm_bytes(m: int, k: int, n: int) -> int:
    return m * k + k * n + 4 * m * n


def mvm_least_s(m: int, k: int, n: int, peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the operations
    bound and the memory bound."""
    return max(mvm_ops(m, k, n) / peaks["int8_ops_per_s"],
               mvm_bytes(m, k, n) / peaks["hbm_bytes_per_s"])


def stage_pass_bytes(n: int, machines: int) -> int:
    return WORD * n * (STAGE_INPUT_COLS + machines + STAGE_RESOLVED_COLS)


def stage_pass_least_s(n: int, machines: int,
                       peaks: Dict[str, float]) -> float:
    return stage_pass_bytes(n, machines) / peaks["hbm_bytes_per_s"]
