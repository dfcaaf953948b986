#!/usr/bin/env bash
# Pre-PR smoke check: graftlint, the tier-1 verify command (ROADMAP.md),
# plus one chaos scenario end to end. Run as `make smoke` or
# `bash tools/smoke.sh`.
set -u
cd "$(dirname "$0")/.."

echo "== graftlint (static trace-safety / engine-contract / runtime analysis) =="
# full tree, all rules — the --changed subset is for pre-commit only
lint_t0=$(date +%s)
python -m open_simulator_tpu.cli lint --jobs 4
rc=$?
lint_wall=$(( $(date +%s) - lint_t0 ))
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: graftlint exited $rc" >&2
  exit "$rc"
fi
# wall-clock budget: the lint stage must stay interactive. The full-repo
# run is ~10-15s warm; 90s flags a pathological regression (e.g. a rule
# going quadratic over the module set) without tripping on cold CI disks.
LINT_BUDGET_S=${LINT_BUDGET_S:-90}
echo "graftlint wall: ${lint_wall}s (budget ${LINT_BUDGET_S}s)"
if [ "$lint_wall" -gt "$LINT_BUDGET_S" ]; then
  echo "smoke FAILED: graftlint took ${lint_wall}s > budget ${LINT_BUDGET_S}s" >&2
  exit 1
fi

echo
echo "== tier-1 test suite (ROADMAP.md verify command) =="
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: tier-1 suite exited $rc" >&2
  exit "$rc"
fi

echo
echo "== chaos scenario end to end (kill one node + one zone) =="
env JAX_PLATFORMS=cpu python -m open_simulator_tpu.cli chaos \
  --cluster-config examples/cluster/demo \
  --kill-node worker-a-0 --kill-zone zone-b
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: chaos scenario exited $rc" >&2
  exit "$rc"
fi

echo
echo "== telemetry end to end (server + /metrics scrape + explain) =="
env JAX_PLATFORMS=cpu python tools/metrics_smoke.py
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: telemetry stage exited $rc" >&2
  exit "$rc"
fi

echo
echo "== run ledger (flight recorder): apply x2, diff =="
SMOKE_LEDGER="$(mktemp -d)"
env JAX_PLATFORMS=cpu SIMON_LEDGER_DIR="$SMOKE_LEDGER" python - <<'PYEOF'
# the demo apply twice in one process: records 2 "apply" RunRecords with
# identical result digests/config fingerprints, and run 2's sweep must be
# ALL exec-cache hits (zero misses) — compile-once-run-many, witnessed by
# the ledger's metric deltas
import json, sys
from open_simulator_tpu.cli.main import main
from open_simulator_tpu.telemetry import ledger

for i in range(2):
    rc = main(["apply", "-f", "examples/config.yaml", "--max-new-nodes", "8",
               "--output-file", "/dev/null"])
    assert rc == 0, f"apply run {i} exited {rc}"
recs = ledger.default_ledger().records(surface="apply")
assert len(recs) == 2, f"expected 2 apply records, got {len(recs)}"
a, b = recs
assert a["result"]["digest"] == b["result"]["digest"], (a["result"], b["result"])
assert a["fingerprint"] == b["fingerprint"], (a["fingerprint"], b["fingerprint"])
hits = sum(v for k, v in b["metrics"].items()
           if "simon_compile_cache_total" in k and "event=hit" in k)
misses = sum(v for k, v in b["metrics"].items()
             if "simon_compile_cache_total" in k and "event=miss" in k)
assert hits > 0 and misses == 0, (
    f"second apply run should be pure cache hits, got hits={hits} misses={misses}")
print(f"ledger OK: 2 apply records, equal digests "
      f"({a['result']['digest']}), second run {hits} cache hit(s), 0 misses")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: ledger stage exited $rc" >&2
  exit "$rc"
fi
env JAX_PLATFORMS=cpu python -m open_simulator_tpu.cli runs \
  --ledger-dir "$SMOKE_LEDGER" diff prev last
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: runs diff exited $rc" >&2
  exit "$rc"
fi
rm -rf "$SMOKE_LEDGER"

echo
echo "== multichip digest gate (8 fake devices vs single-device) =="
make multichip-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: multichip-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== fleet campaign (quarantine isolation + SIGKILL resume digest) =="
make campaign-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: campaign-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== trace replay (chaos mid-trace, autoscaler converges, SIGKILL resume, frontier) =="
make replay-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: replay-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== digital-twin sessions (SIGKILL the server, resume digest-identical, fork isolation) =="
make session-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: session-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== policy tuning (grid Pareto, seeded cem digest, cancellation 504/400, fleet lanes) =="
make tune-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: tune-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== inference serving (resident snapshot, delta == cold re-encode, poisoned lane, drain) =="
make serve-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: serve-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== device fault domain (injected plan: structured 5xx, ladder rungs, healthy digest) =="
make fault-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: fault-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== durable-state fault domain (torn tail resumes, mid-file corruption 409s, ENOSPC rung) =="
make journal-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: journal-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== serving lifecycle (SIGTERM drain: readyz flip, 503s, in-flight finishes) =="
make lifecycle-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: lifecycle-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== causal tracing (trace-id timelines, journal appends, XLA costs, fault rungs) =="
make trace-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: trace-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== live operations (event stream, devmem ledger, simon-tpu top) =="
make live-smoke
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: live-smoke exited $rc" >&2
  exit "$rc"
fi

echo
echo "== simon-tpu explain on the example cluster =="
env JAX_PLATFORMS=cpu python -m open_simulator_tpu.cli explain \
  -f examples/config.yaml --top-k 2
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "smoke FAILED: explain exited $rc" >&2
  exit "$rc"
fi

echo
echo "smoke OK"
