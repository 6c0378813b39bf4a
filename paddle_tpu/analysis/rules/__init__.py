"""graftlint rule registry — one Rule instance per CLAUDE.md invariant.

Adding a rule: subclass :class:`paddle_tpu.analysis.core.Rule` in a
module here, instantiate it in ``ALL_RULES``, give it a bad/good
fixture pair in ``tests/test_analysis.py``, and document the incident
it encodes in ``docs/ANALYSIS.md`` (same-commit, like the round-7
sweep rule for new API surfaces)."""
from __future__ import annotations

from .autograd import AutogradBypass, ThreadGradState
from .chaos_clock import ServingRawSleep
from .dist_spec import DistSpecPassthrough
from .env_knobs import EnvKnobRegistry
from .fleet_spawn import FleetProcessSpawn
from .jit_capture import JitConstantCapture
from .kvtier_access import KvtierBlessedAccess
from .pallas import PallasHazards
from .serving_lock import EngineLockDiscipline, PageMigrationLock
from .weight_swap import WeightSwapLock

ALL_RULES = [
    AutogradBypass(),
    ThreadGradState(),
    PallasHazards(),
    JitConstantCapture(),
    DistSpecPassthrough(),
    EngineLockDiscipline(),
    PageMigrationLock(),
    EnvKnobRegistry(),
    ServingRawSleep(),
    FleetProcessSpawn(),
    KvtierBlessedAccess(),
    WeightSwapLock(),
]

RULES_BY_ID = {r.id: r for r in ALL_RULES}

__all__ = ["ALL_RULES", "RULES_BY_ID", "AutogradBypass",
           "ThreadGradState", "PallasHazards", "JitConstantCapture",
           "DistSpecPassthrough",
           "EngineLockDiscipline", "PageMigrationLock",
           "EnvKnobRegistry", "ServingRawSleep", "FleetProcessSpawn",
           "KvtierBlessedAccess", "WeightSwapLock"]
