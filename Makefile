# Developer entry points. `make smoke` is the documented pre-PR check:
# graftlint + the tier-1 verify command from ROADMAP.md plus one chaos
# scenario end to end (tools/smoke.sh).

.PHONY: test lint smoke lifecycle-smoke \
	multichip-smoke campaign-smoke replay-smoke session-smoke serve-smoke \
	tune-smoke fault-smoke journal-smoke trace-smoke live-smoke

test:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'

# static trace-safety / engine-contract analysis (rules GL1-GL5);
# exits nonzero on any finding — see ARCHITECTURE.md "graftlint".
# Full tree, all rules (GL0-GL10), parallel parse; `simon-tpu lint
# --changed` is the fast pre-commit subset, this target stays strict.
lint:
	python -m open_simulator_tpu.cli lint --jobs 4

smoke:
	bash tools/smoke.sh

# graceful-drain smoke against a real server process: SIGTERM with one
# request in flight must flip /readyz (not /healthz), reject new work
# with 503, finish the held request, and write the final ledger record
lifecycle-smoke:
	env JAX_PLATFORMS=cpu python tools/lifecycle_smoke.py

# the 8-device gate (ROADMAP item 1): batched_schedule over a
# (scenario x node) mesh of 8 virtual CPU devices must produce
# BIT-IDENTICAL node assignments (ledger digest equality) to the
# single-device run — incl. the wave-scheduled pools workload.
multichip-smoke:
	env XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  JAX_PLATFORMS=cpu python tools/multichip_smoke.py

# fleet fault-isolation gate: a 3-cluster fixture fleet (one malformed)
# must complete with exactly 1 quarantined cluster, audits passing on
# the good ones; a child process SIGKILLed after cluster 1 must resume
# via the campaign journal to a BIT-IDENTICAL fleet report digest, with
# the quarantined cluster reported once (not re-run, not lost)
campaign-smoke:
	env JAX_PLATFORMS=cpu python tools/campaign_smoke.py

# time-axis gate (replay/): a synthetic arrival trace with one mid-trace
# kill_node must converge under the autoscaler; a child SIGKILLed after
# step 3 must resume via the replay journal to a BIT-IDENTICAL trajectory
# digest; and the frontier CLI must return a non-trivial Pareto set
replay-smoke:
	env JAX_PLATFORMS=cpu python tools/replay_smoke.py

# digital-twin gate (replay/session.py): a journaled session on a real
# server survives SIGKILL — the restarted server serves it with a
# BIT-IDENTICAL trajectory digest (also vs an uninterrupted reference
# run) — and a chaos fork completes / a poisoned fork quarantines while
# the mainline keeps settling events
session-smoke:
	env JAX_PLATFORMS=cpu python tools/session_smoke.py

# inference-serving gate (server/serving.py): POST a cluster once, probe
# it by digest — delta probes must digest bit-identically to cold full
# re-encodes; mixed coalesced/singleton load with ONE poisoned lane must
# answer the siblings 200 with singleton digests (the poisoned member
# gets its own 504); SIGTERM drain finishes the in-flight probe, exits 0
serve-smoke:
	env JAX_PLATFORMS=cpu python tools/serve_smoke.py

# policy-search gate (tune/): a real server must answer a grid round's
# Pareto set over (unplaced, cost, disruption), reproduce a seeded cem
# digest, turn a lapsed deadline into a structured 504 and a bogus
# weight into a 400, and run a 6-cluster 2-bucket fleet campaign in 2
# launches (the fleet-lane witness: launches < clusters)
tune-smoke:
	env JAX_PLATFORMS=cpu python tools/tune_smoke.py

# device-fault-domain gate (resilience/faults.py): a real server under
# an injected SIMON_FAULT_PLAN must answer the poisoned launch with a
# structured 5xx (taxonomy code, never a bare traceback) while siblings
# answer 200; the OOM pair walks the cache_drop -> resident_drop ladder
# and still returns the healthy digest; simon_fault_* counters match
# the plan exactly; SIGTERM under the plan still exits 0
fault-smoke:
	env JAX_PLATFORMS=cpu python tools/fault_smoke.py

# durable-state fault-domain gate (resilience/journal.py): SIGKILL a
# real server mid-session, then damage the journals both ways — a torn
# FINAL line must resume digest-identically while a flipped byte
# mid-file answers a structured 409 E_CORRUPT (kind/record/offset, the
# sibling unharmed); an injected ENOSPC plan walks the shared
# checkpointing_disabled rung with simon_journal_* counters matching;
# SIGTERM under the plan still exits 0
journal-smoke:
	env JAX_PLATFORMS=cpu python tools/journal_smoke.py

# causal-tracing gate (telemetry/context.py): a real server must echo a
# client X-Simon-Trace-Id and reconstruct the request's causal timeline
# (queue wait, coalesced launch, durable journal appends) from the black
# box; /debug/executables lists harvested XLA costs; a deterministic
# OOM plan yields a structured 503 whose timeline records the ladder
# rungs and attempts plus a trace:dump ledger event; SIGTERM under
# traced load still exits 0
trace-smoke:
	env JAX_PLATFORMS=cpu python tools/trace_smoke.py

# live-operations gate (telemetry/live.py): a real server must stream
# the black box over GET /api/events (an SSE follower sees the same
# causal sequence /api/trace/<id> reconstructs), drop a stalled
# subscriber's events without blocking any worker, expose the per-owner
# device-memory ledger on /debug/stats + /metrics, render one
# `simon-tpu top --once` frame, and end live streams cleanly on SIGTERM
live-smoke:
	env JAX_PLATFORMS=cpu python tools/live_smoke.py
