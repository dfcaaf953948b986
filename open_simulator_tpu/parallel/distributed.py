"""Multi-host initialization (DCN scale-out of the scenario axis).

The reference is single-process (SURVEY.md section 2c); its 3000-node scale
claim is bounded by one Go process. Here multi-host is the same program on
a bigger mesh: scenario lanes are embarrassingly parallel, so hosts join a
`jax.distributed` job, the mesh's "scenario" axis spans all hosts' devices
over DCN, and each host feeds its local shard of the lane batch. No code
in engine/ or ops/ changes — GSPMD owns the transport, ICI within a slice,
DCN across slices.

Cannot be exercised in this single-host image; `dryrun_multichip` covers
the sharding paths on virtual devices, and this helper is the documented
entry point for real pods/slices.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from open_simulator_tpu.parallel.sweep import make_mesh


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join (or bootstrap) a jax.distributed job. Arguments default to the
    standard env vars (JAX_COORDINATOR_ADDRESS etc.) / TPU metadata, which
    is all that is needed on Cloud TPU pods."""
    kwargs = {}
    if coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        kwargs["coordinator_address"] = coordinator_address or os.environ["JAX_COORDINATOR_ADDRESS"]
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def global_scenario_mesh():
    """A mesh over every device in the job (all hosts), every device on
    the scenario axis. Feed lane batches via
    jax.make_array_from_process_local_data so each host materializes
    only its shard."""
    return make_mesh(require_all=True)
