"""Adversarial scenario library (ROADMAP 5b, ISSUE 12).

First-class hostile workloads driving a real server over real ZeroMQ:
``CATALOG`` maps names to :class:`~.engine.Scenario` classes;
:func:`run_scenario` produces one structured survival + SLO report.
Consumed by ``python -m worldql_server_tpu.scenarios`` (CI scenario
smoke) and tests/test_scenarios.py.
"""

from .catalog import (
    BandwidthCap, BattleRoyale, ClusterFlashCrowd, FlashCrowd, GameTick,
    MegaCity, ProjectileStorm, ReconnectStorm, ReconnectStormReplay,
    RollingRestart, SniperScope,
)
from .engine import Check, Scenario, ScenarioContext, format_report, run_scenario

CATALOG = {
    scenario.name: scenario
    for scenario in (
        FlashCrowd, BattleRoyale, ReconnectStorm, GameTick,
        ReconnectStormReplay, ClusterFlashCrowd,
        SniperScope, ProjectileStorm, BandwidthCap,
        MegaCity, RollingRestart,
    )
}

__all__ = [
    "CATALOG",
    "BandwidthCap",
    "BattleRoyale",
    "Check",
    "ClusterFlashCrowd",
    "FlashCrowd",
    "GameTick",
    "MegaCity",
    "ProjectileStorm",
    "ReconnectStorm",
    "ReconnectStormReplay",
    "RollingRestart",
    "Scenario",
    "ScenarioContext",
    "SniperScope",
    "format_report",
    "run_scenario",
]
