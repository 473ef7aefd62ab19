"""Plain numpy reference of the 16-core CMP evaluator.

A copy of the repository's numpy golden (the scalar interval model, the
Table-3 coordinator and controllers, CPpf and the registry's policy
families), with its device backends removed and its imports made local,
kept here so that a change to the program cannot move the reference.
It imports nothing of the program.

``memsys.DTYPE`` sets the precision of the interval model's arithmetic:
float64 as the configuration states, float32 for the precision control.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from . import apps, memsys
from .managers import MANAGER_NAMES, run_manager
from .runner import CMPConfig, CMPPlant, equal_share
from .types import Allocation, CBPParams, Mode


def golden(mix: Sequence[str], total_ms: float,
           managers: Optional[Sequence[str]] = None) -> Dict[str, np.ndarray]:
    """Per-app IPC of one mix (a list of app names) under each manager on
    the paper's CMP with ``CBPParams()`` defaults, and ``"__baseline__"``
    (the paper's unpartitioned, prefetch-off baseline)."""
    plant = CMPPlant(list(mix), CMPConfig())
    out = {name: np.asarray(run_manager(name, plant, total_ms,
                                        CBPParams()).ipc, np.float64)
           for name in (managers or MANAGER_NAMES)}
    n = plant.n_clients
    units, bw = equal_share(n, plant.total_cache_units, plant.total_bandwidth)
    out["__baseline__"] = np.asarray(plant.evaluate(Allocation(
        cache_units=units, bandwidth=bw, prefetch_on=np.zeros(n, bool),
        cache_mode=Mode.UNPARTITIONED,
        bandwidth_mode=Mode.UNPARTITIONED)).ipc, np.float64)
    return out


__all__ = ["MANAGER_NAMES", "apps", "golden", "memsys"]
