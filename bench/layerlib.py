"""Readers shared by the per-layer metric files in ``metrics/``.

Each reader takes a traced :class:`harness.Run` and returns the
metric's value, or ``None`` when the trace holds nothing to read (the
metric is then left out of the result line).  Shares are in percent.
"""

from __future__ import annotations

import sys
from typing import Any, Optional

import work

__all__ = ["idle_percent", "stage_pass_roofline", "cim_mvm_roofline",
           "func_mfu"]

STAGE_PASS = "jit_stage_pass"
CIM_MVM = "jit_cim_mvm"


def idle_percent(run: Any) -> Optional[float]:
    """Share of the window in which no program ran on the device."""
    return 100.0 * run.reduction.idle_share()


def _note(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)


def stage_pass_roofline(run: Any) -> Optional[float]:
    """Least time of the window's stage-pass calls (memory bound of
    :func:`work.stage_pass_bytes`) over their device time."""
    calls = run.entry.stage_calls() * len(run.requests)
    events = run.reduction.module_events(STAGE_PASS)
    if not events:
        return None
    if len(events) != len(calls):
        _note(f"{len(events)} stage-pass executions traced for "
              f"{len(calls)} calls; stage_pass_roofline left out")
        return None
    least = sum(work.stage_pass_least_s(n, m, run.peaks) for n, m in calls)
    return 100.0 * least / (sum(e[2] for e in events) / 1e9)


def cim_mvm_roofline(run: Any) -> Optional[float]:
    """Least time of the window's CIM MVMs (:func:`work.mvm_least_s`)
    over the summed device time of the Pallas kernel's executions."""
    calls = run.entry.kernel_calls() * len(run.requests)
    events = run.reduction.kernel_events(CIM_MVM)
    if not events:
        return None
    if len(events) != len(calls):
        _note(f"{len(events)} kernel executions traced for {len(calls)} "
              f"calls; cim_mvm_roofline left out")
        return None
    least = sum(work.mvm_least_s(m, k, n, run.peaks) for m, k, n in calls)
    return 100.0 * least / (sum(e[2] for e in events) / 1e9)


def func_mfu(run: Any) -> Optional[float]:
    """The whole validation step's share of the chip's int8 peak: two
    operations per model MAC validated in the window, over the window."""
    ops = 2.0 * sum(r.work for r in run.requests)
    if not ops:
        return None
    return 100.0 * ops / run.reduction.window_s / run.peaks[
        "int8_ops_per_s"]
