"""Scenario parallelism: vmap + GSPMD sharding over a device mesh.

The reference's capacity planner re-runs the entire simulation from
scratch for every candidate node count, with a human in the loop
(pkg/apply/apply.go:202-258). Here the node-count axis and arbitrary
what-if scenarios are a *batch dimension*: `vmap` over per-scenario
active-node masks, sharded across devices with `jax.sharding`
NamedSharding so XLA GSPMD handles all communication (SURVEY.md
section 2c: the rebuild's communication backend is GSPMD over ICI/DCN,
not hand-written collectives).

Mesh axes:
  "scenario" — data-parallel over what-if scenarios (the throughput axis)
  "node"     — held at 1: a node split placed pods wrongly on a TPU mesh
               and is refused until a chip run verifies it (ROADMAP B3)
"""

from open_simulator_tpu.parallel.sweep import (
    CapacityPlan,
    SweepThresholds,
    batched_schedule,
    capacity_bisect,
    capacity_sweep,
    make_mesh,
)
