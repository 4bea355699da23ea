"""repro.core.sim — cycle-level out-of-order pipeline simulation.

The third prediction backend (after the analytic port bound and the LCD
bound): a parametric front-end + finite-window + port-arbitration
simulator for x86 loop kernels, a vectorized struct-of-arrays batch
driver, and the event-driven DAG scheduler used for compiled HLO.
See docs/simulation.md for the model and docs/architecture.md for how
the three backends compose.
"""
from __future__ import annotations

from .batch import AUTO_JIT_MIN_BATCH, JIT_SHARD, simulate_many, warm_chain
from .dag import DagNode, DagSchedule, schedule_dag
from .pipeline import (BOTTLENECKS, DEFAULT_PARAMS, FE_MODE_NAMES,
                       FrontendSchedule, SimProgram, SimResult, SimUop,
                       compile_program, frontend_schedule, simulate,
                       simulate_kernel)

__all__ = [
    "AUTO_JIT_MIN_BATCH", "BOTTLENECKS", "DEFAULT_PARAMS",
    "DagNode", "DagSchedule", "FE_MODE_NAMES", "FrontendSchedule",
    "JIT_SHARD", "SimProgram", "SimResult", "SimUop", "compile_program",
    "frontend_schedule", "schedule_dag", "simulate",
    "simulate_kernel", "simulate_many", "warm_chain",
]
