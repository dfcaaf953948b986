"""The scheduling engine: a `lax.scan` over the pod sequence.

This replaces the entire reference hot loop — scheduler goroutine, queue,
informer handshake, bind plugin (SURVEY.md section 3.3) — with

    state = bind(state, select(score & mask(state, pod)))

scanned over pods. Placement is order-dependent (each bind changes
occupancy), so the pod axis stays sequential; throughput comes from
vmapping whole scenarios (parallel/), not from pod parallelism.
"""

from open_simulator_tpu.engine.scheduler import (
    EngineConfig,
    ScheduleOutput,
    SimState,
    device_arrays,
    init_state,
    schedule_pods,
)
from open_simulator_tpu.engine.exec_cache import (
    EXEC_CACHE,
    bucket_shape,
    bucketed_device_arrays,
    enable_persistent_cache,
    pad_snapshot_arrays,
    run_batched_cached,
    unpad_output,
)
from open_simulator_tpu.engine.queue import sort_pods_greedy, sort_pods_affinity, sort_pods_toleration
