"""Flight recorder: the persistent run ledger (ARCHITECTURE.md §10).

PR 3 built point-in-time observability (metrics, spans, explain); this
module adds the time axis. Every simulation — CLI apply, chaos, a REST
route, a capacity sweep — appends one structured ``RunRecord`` JSON
line to an on-disk ledger, so regressions, nondeterminism and config
drift stay visible after the process exits.

A record carries:

* identity: ``run_id`` + wall-clock ``ts`` + ``surface`` (which entry
  point ran: ``apply`` / ``chaos`` / ``server:<route>`` / ``sweep`` /
  ``simulate``),
* a config fingerprint: EngineConfig content hash + the exec-cache
  bucket shape + a workload digest over the encoded SnapshotArrays —
  two runs with equal fingerprints asked the engine the same question,
* per-phase wall times harvested from the span tree (encode / transfer
  / schedule / decode + the synthetic compile span),
* metric deltas over the run (every ``simon_*`` counter that moved:
  compile-cache hits/misses, sweep trials, retries, chaos events),
* a result digest (placed/unplaced counts + hash of the per-pod node
  assignments and fail_counts) — equal fingerprints with unequal
  digests flag nondeterminism,
* environment (jax version, backend, device kind).

Recording is OFF unless a ledger directory is configured
(``--ledger-dir`` / ``SIMON_LEDGER_DIR``); disabled captures cost one
dict lookup. One record per logical run: the outermost active capture
claims the run and nested captures (the sweep inside an apply, the
simulate inside a REST route) are no-ops, with the entry point naming
the surface via ``surface_override``. The ledger file is size-capped:
past ``SIMON_LEDGER_MAX_BYTES`` the current ``runs.jsonl`` rotates to
``runs.jsonl.1`` (one prior generation kept), so long-lived servers
bound their disk.

Trace-safety contract (graftlint GL4): the ledger is HOST machinery.
Digests hash decoded ``np.asarray`` outputs after the device blocked;
nothing here runs inside jit/scan scope (see
tests/fixtures/lint/gl4_ledger_ok.py for the pattern).
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional

from open_simulator_tpu.telemetry import registry as _registry
from open_simulator_tpu.telemetry import spans as _spans

_log = logging.getLogger(__name__)

LEDGER_DIR_ENV = "SIMON_LEDGER_DIR"
MAX_BYTES_ENV = "SIMON_LEDGER_MAX_BYTES"
DEFAULT_MAX_BYTES = 4 * 1024 * 1024
LEDGER_FILE = "runs.jsonl"
SCHEMA_VERSION = 1

# canonical phase ordering for reports/diffs (unknown names follow, sorted)
PHASE_ORDER = ("admit", "expand", "encode", "transfer", "schedule",
               "compile", "decode", "sweep", "chaos.baseline", "chaos.event",
               "replay.step", "frontier", "tune.round", "fleet.launch")

# SnapshotArrays fields whose CONTENT feeds the workload digest (the
# discriminative cheap core: capacities, requests, pins, activation,
# compat classes). Every field's name+shape is hashed regardless, so
# structural drift in any array still changes the digest.
_WORKLOAD_CONTENT_FIELDS = ("alloc", "req", "forced_node", "active",
                            "class_id", "gpu_cnt", "spread_valid")

_state: Dict[str, Optional[str]] = {"dir": None, "broken": None}
_tls = threading.local()
_io_lock = threading.Lock()


class LedgerError(ValueError):
    """Bad ledger lookup (unknown/ambiguous run id, empty ledger)."""


# ---- configuration -------------------------------------------------------


def configure(path: Optional[str]) -> None:
    """Set the process-wide ledger directory (the --ledger-dir flag).
    Empty/None falls back to the SIMON_LEDGER_DIR environment knob.
    Reconfiguring clears the unwritable-dir latch (an explicit new
    configuration is a request to try again)."""
    _state["dir"] = path or None
    _state["broken"] = None


def ledger_dir() -> Optional[str]:
    return _state["dir"] or os.environ.get(LEDGER_DIR_ENV) or None


def mark_unwritable(root: str, err: Exception) -> None:
    """Degrade-to-disabled: an unwritable/readonly ledger dir (full disk,
    bad mount) must cost ONE warning, not a crash — and not a warning per
    run for the rest of a fleet campaign. Latched per-directory; cleared
    by configure()."""
    if _state["broken"] != root:
        _state["broken"] = root
        _log.warning(
            "ledger dir %s is unwritable (%s); run recording disabled "
            "for this process (reconfigure --ledger-dir to retry)",
            root, err)


def enabled() -> bool:
    d = ledger_dir()
    return d is not None and d != _state["broken"]


def default_ledger() -> Optional["Ledger"]:
    d = ledger_dir()
    if d is None or d == _state["broken"]:
        return None
    return Ledger(d)


# ---- fingerprints and digests -------------------------------------------


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def engine_config_hash(cfg) -> str:
    """Content hash of an EngineConfig, stable across processes: the
    extensions tuple (function objects whose repr embeds addresses) is
    replaced by the extension names before hashing."""
    d = cfg._asdict()
    d["extensions"] = tuple(
        getattr(e, "name", repr(e)) for e in d.get("extensions", ()))
    return _sha(repr(sorted(d.items())).encode())


def workload_digest(arrs) -> str:
    """Digest of the encoded workload: every SnapshotArrays field's name
    and shape, plus the raw bytes of the discriminative content fields.
    Host numpy in, host hash out — never call with device arrays on the
    hot path (snapshot.arrays is the host-side encode output)."""
    import dataclasses

    import numpy as np

    h = hashlib.sha256()
    for f in dataclasses.fields(arrs):
        x = getattr(arrs, f.name)
        h.update(f"{f.name}:{tuple(np.shape(x))};".encode())
    for name in _WORKLOAD_CONTENT_FIELDS:
        h.update(np.ascontiguousarray(np.asarray(getattr(arrs, name))).tobytes())
    return h.hexdigest()[:16]


def config_fingerprint(cfg, snapshot=None, arrs=None) -> Dict[str, Any]:
    """{"engine", "bucket", "workload"}: same fingerprint == the engine
    was asked the same question with the same compiled shapes."""
    fp: Dict[str, Any] = {"engine": engine_config_hash(cfg)}
    if arrs is not None:
        fp["bucket"] = [int(arrs.alloc.shape[0]), int(arrs.req.shape[0])]
    elif snapshot is not None:
        from open_simulator_tpu.engine.exec_cache import bucket_shape

        n, p = bucket_shape(snapshot.n_nodes, snapshot.n_pods)
        fp["bucket"] = [int(n), int(p)]
    if snapshot is not None:
        fp["workload"] = workload_digest(snapshot.arrays)
    return fp


def result_digest(result) -> Dict[str, Any]:
    """Digest of a SimulateResult: per-pod placement map + fail_counts."""
    import numpy as np

    h = hashlib.sha256()
    for sp in sorted(result.scheduled_pods, key=lambda s: s.pod.key):
        h.update(f"{sp.pod.key}->{sp.node_name};".encode())
    for up in sorted(result.unscheduled_pods, key=lambda u: u.pod.key):
        h.update(f"{up.pod.key}->!;".encode())
    if result.fail_counts is not None:
        h.update(np.ascontiguousarray(
            np.asarray(result.fail_counts)).tobytes())
    return {"placed": len(result.scheduled_pods),
            "unplaced": len(result.unscheduled_pods),
            "digest": h.hexdigest()[:16]}


def plan_digest(plan) -> Dict[str, Any]:
    """Digest of a CapacityPlan: probed counts, verdicts, and every
    lane's node assignments."""
    import numpy as np

    h = hashlib.sha256()
    h.update(repr((list(plan.counts), plan.best_count,
                   list(plan.satisfied))).encode())
    if plan.nodes_per_scenario is not None:
        nodes = np.asarray(plan.nodes_per_scenario)
        h.update(np.ascontiguousarray(nodes).tobytes())
    else:
        nodes = None
    if nodes is not None and len(plan.counts):
        idx = (plan.counts.index(plan.best_count)
               if plan.best_count is not None else len(plan.counts) - 1)
        placed = int(np.sum(nodes[idx] >= 0))
        unplaced = int(np.sum(nodes[idx] < 0))
    else:
        placed = unplaced = 0
    return {"placed": placed, "unplaced": unplaced,
            "digest": h.hexdigest()[:16]}


def report_digest(report) -> Dict[str, Any]:
    """Digest of a chaos DisruptionReport (the full structured report —
    two identical fault plans must produce identical digests)."""
    h = _sha(json.dumps(report.to_dict(), sort_keys=True).encode())
    unplaced = (report.steps[-1].unschedulable_after if report.steps
                else report.baseline_unschedulable)
    return {"placed": report.total_pods - unplaced, "unplaced": unplaced,
            "digest": h}


def array_result_digest(node_assign) -> Dict[str, Any]:
    """Digest of raw node assignments (bench lanes: [S, P] or [P])."""
    import numpy as np

    nodes = np.asarray(node_assign)
    return {"placed": int(np.sum(nodes >= 0)),
            "unplaced": int(np.sum(nodes < 0)),
            "digest": _sha(np.ascontiguousarray(nodes).tobytes())}


def _environment() -> Dict[str, str]:
    try:
        import jax

        dev = jax.devices()[0]
        return {"jax": str(jax.__version__),
                "backend": str(jax.default_backend()),
                "device_kind": str(getattr(dev, "device_kind", dev.platform))}
    except Exception:  # noqa: BLE001 — env info must never fail a run
        return {}


# ---- capture -------------------------------------------------------------


class _NullCapture:
    """The disabled/nested stand-in: call sites stay unconditional."""

    recording = False

    def set_config(self, cfg, snapshot=None, arrs=None) -> None:
        pass

    def set_result(self, result) -> None:
        pass

    def set_plan(self, plan) -> None:
        pass

    def set_report(self, report) -> None:
        pass

    def set_result_info(self, placed: int, unplaced: int, digest: str) -> None:
        pass

    def tag(self, key: str, value) -> None:
        pass


NULL_CAPTURE = _NullCapture()


class RunCapture:
    """One run's in-flight record: marks the span window and counter
    snapshot on entry; ``finish()`` harvests both into a RunRecord dict."""

    recording = True

    def __init__(self, surface: str, tags: Optional[Dict[str, Any]] = None):
        self.surface = surface
        self.tags: Dict[str, Any] = dict(tags or {})
        self.fingerprint: Optional[Dict[str, Any]] = None
        self.result: Optional[Dict[str, Any]] = None
        self._mark = _spans.RECORDER.mark()
        self._counters0 = _registry.REGISTRY.counter_samples("simon_")
        self._ts = time.time()
        self._t0 = time.perf_counter()

    def set_config(self, cfg, snapshot=None, arrs=None) -> None:
        self.fingerprint = config_fingerprint(cfg, snapshot=snapshot,
                                              arrs=arrs)

    def set_result(self, result) -> None:
        self.result = result_digest(result)
        if getattr(result, "elapsed_s", 0.0):
            self.result["elapsed_s"] = round(result.elapsed_s, 6)

    def set_plan(self, plan) -> None:
        self.result = plan_digest(plan)
        self.tags.setdefault("best_count", plan.best_count)
        self.tags.setdefault("lanes", len(plan.counts))

    def set_report(self, report) -> None:
        self.result = report_digest(report)
        self.tags.setdefault("events", len(report.steps))

    def set_result_info(self, placed: int, unplaced: int, digest: str) -> None:
        self.result = {"placed": int(placed), "unplaced": int(unplaced),
                       "digest": digest}

    def tag(self, key: str, value) -> None:
        self.tags[key] = value

    def _phases(self) -> Dict[str, float]:
        phases: Dict[str, float] = {}
        for rec in _spans.RECORDER.records_since(self._mark):
            phases[rec.name] = phases.get(rec.name, 0.0) + rec.dur
        return {k: round(v, 6) for k, v in phases.items()}

    def _metric_deltas(self) -> Dict[str, float]:
        now = _registry.REGISTRY.counter_samples("simon_")
        out: Dict[str, float] = {}
        for key, v in now.items():
            d = v - self._counters0.get(key, 0.0)
            if d:
                out[key] = int(d) if float(d).is_integer() else d
        return out

    def finish(self) -> Dict[str, Any]:
        from open_simulator_tpu.telemetry import context as _trace_ctx

        trace = _trace_ctx.current_trace()
        if trace and "trace" not in self.tags:
            # the §20 identity spine: the RunRecord names the request
            # that produced it, so `runs show` ↔ `trace show` cross-link
            self.tags["trace"] = trace
        rec = {
            "schema": SCHEMA_VERSION,
            "run_id": uuid.uuid4().hex[:12],
            "ts": round(self._ts, 6),
            "surface": self.surface,
            "wall_s": round(time.perf_counter() - self._t0, 6),
            "fingerprint": self.fingerprint,
            "phases": self._phases(),
            "metrics": self._metric_deltas(),
            "result": self.result,
            "env": _environment(),
            "tags": self.tags,
        }
        costs = _provided_costs()
        if costs:
            # per-executable XLA cost profiles (flops / bytes / peak-HBM
            # estimate) harvested at compile time — the "why is my run
            # slow/big" section of `simon-tpu runs show`
            rec["costs"] = costs
        return rec


# per-executable cost snapshot provider (engine/exec_cache.py registers
# ExecutableCache.cost_snapshot). A hook instead of an import: the ledger
# must not depend on the engine layer, and tests can stub it.
_cost_provider: Optional[Any] = None


def set_cost_provider(fn) -> None:
    global _cost_provider
    _cost_provider = fn


def _provided_costs() -> Dict[str, Any]:
    if _cost_provider is None:
        return {}
    try:
        return dict(_cost_provider() or {})
    except Exception:  # noqa: BLE001 — cost accounting is best-effort
        return {}


def append_event(surface: str, tags: Optional[Dict[str, Any]] = None,
                 wall_s: float = 0.0) -> Optional[str]:
    """Append a minimal lifecycle record (no fingerprint/result): the
    graceful-drain path writes one as the server's last word — how many
    requests it served, whether the drain finished clean — so a restart
    loop leaves an audit trail even when no simulation was in flight.
    Returns the run_id, or None when the ledger is disabled."""
    led = default_ledger()
    if led is None:
        return None
    tags = dict(tags or {})
    from open_simulator_tpu.telemetry import context as _trace_ctx

    trace = _trace_ctx.current_trace()
    if trace and "trace" not in tags:
        tags["trace"] = trace
    rec = {
        "schema": SCHEMA_VERSION,
        "run_id": uuid.uuid4().hex[:12],
        "ts": round(time.time(), 6),
        "surface": surface,
        "wall_s": round(float(wall_s), 6),
        "fingerprint": None,
        "phases": {},
        "metrics": {},
        "result": None,
        "env": _environment(),
        "tags": tags,
    }
    from open_simulator_tpu.resilience.faults import DeviceFault

    try:
        led.append(rec)
    except (OSError, DeviceFault) as e:
        # classified storage fault (E_STORAGE_FULL after run_io's retry
        # schedule) or a raw OSError: one warning, then disabled.
        # Deliberately NOT record_rung — that writes a ledger event, and
        # the ledger is the thing that just failed (recursion).
        mark_unwritable(led.root, e)
        return None
    except Exception as e:  # noqa: BLE001 — lifecycle records are best-effort
        _log.warning("ledger append failed (%s): %s", led.path, e)
        return None
    return rec["run_id"]


@contextlib.contextmanager
def surface_override(name: str) -> Iterator[None]:
    """Name the entry point for any capture opened inside this scope (a
    REST route wraps its handler so the simulate/sweep/chaos capture
    underneath records surface ``server:<route>``)."""
    prev = getattr(_tls, "surface", None)
    _tls.surface = name
    try:
        yield
    finally:
        _tls.surface = prev


@contextlib.contextmanager
def run_capture(surface: str,
                tags: Optional[Dict[str, Any]] = None) -> Iterator:
    """Record one run into the default ledger. Yields a RunCapture the
    call site feeds (set_config / set_result / tag); the record is
    written on CLEAN exit only — a raised simulation is not a run.
    No-op (yields NULL_CAPTURE) when the ledger is disabled or another
    capture is already active on this thread (one record per run: the
    outermost entry point claims it)."""
    led = default_ledger()
    if led is None or getattr(_tls, "active", False):
        yield NULL_CAPTURE
        return
    _tls.active = True
    cap = RunCapture(getattr(_tls, "surface", None) or surface, tags)
    try:
        yield cap
    finally:
        _tls.active = False
    from open_simulator_tpu.resilience.faults import DeviceFault

    try:
        led.append(cap.finish())
    except (OSError, DeviceFault) as e:
        # unwritable dir / full disk (raw, or classified E_STORAGE_*
        # out of run_io): one warning, then recording goes dark for
        # this process instead of warning on every later run
        mark_unwritable(led.root, e)
    except Exception as e:  # noqa: BLE001 — a non-JSON tag, ...:
        # the flight recorder must never take the plane down
        _log.warning("ledger append failed (%s): %s", led.path, e)


# ---- storage -------------------------------------------------------------


class Ledger:
    """Append-only JSON-lines store with one-generation size rotation.

    Writes (the append itself AND the rotation rename) run inside the
    ``ledger_append`` storage fault domain (resilience/faults.py, ARCH
    §19): EIO retries on disk timescales, ENOSPC escapes as a
    deterministic ``E_STORAGE_FULL`` DeviceFault for the callers'
    ``mark_unwritable`` latch. Reads count what they skip
    (``skipped_corrupt``) so a rotting ledger cannot quietly shrink the
    regression window."""

    def __init__(self, root: str, max_bytes: Optional[int] = None):
        self.root = root
        if max_bytes is None:
            try:
                max_bytes = int(os.environ.get(MAX_BYTES_ENV,
                                               DEFAULT_MAX_BYTES))
            except ValueError:
                max_bytes = DEFAULT_MAX_BYTES
        self.max_bytes = max(4096, int(max_bytes))
        self.path = os.path.join(root, LEDGER_FILE)
        # corrupt lines skipped by the most recent records() call — the
        # CLI/REST/bench read paths surface this instead of hiding it
        self.skipped_corrupt = 0

    def append(self, record: Dict[str, Any]) -> None:
        from open_simulator_tpu.resilience import faults

        line = json.dumps(record, sort_keys=True) + "\n"

        def write() -> None:
            with _io_lock:
                os.makedirs(self.root, exist_ok=True)
                size = (os.path.getsize(self.path)
                        if os.path.exists(self.path) else 0)
                if size and size + len(line) > self.max_bytes:
                    # rotate: current generation becomes .1 (prior .1
                    # dropped)
                    os.replace(self.path, self.path + ".1")
                with open(self.path, "a", encoding="utf-8") as f:
                    f.write(line)

        faults.run_io("ledger_append", write)
        # witness the append in the flight recorder (and through it the
        # live event feed) — AFTER the durable write, so a failed append
        # raises without a phantom event; record() never writes the
        # ledger back, so there is no recursion
        from open_simulator_tpu.telemetry.context import BLACKBOX

        BLACKBOX.record("ledger", surface=record.get("surface"),
                        run_id=record.get("run_id"))

    def records(self, surface: Optional[str] = None,
                limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """All parseable records, oldest first (.1 generation included).
        Corrupt lines (a crash mid-append, bit rot) are skipped but
        COUNTED into ``self.skipped_corrupt`` — the read survives, the
        damage is visible."""
        out: List[Dict[str, Any]] = []
        skipped = 0
        for path in (self.path + ".1", self.path):
            if not os.path.exists(path):
                continue
            with open(path, "r", encoding="utf-8") as f:
                for ln in f:
                    if not ln.strip():
                        continue  # a blank line is not a record
                    try:
                        rec = json.loads(ln)
                    except json.JSONDecodeError:
                        skipped += 1
                        continue
                    if isinstance(rec, dict) and rec.get("run_id"):
                        out.append(rec)
                    else:
                        skipped += 1  # parseable JSON, not a RunRecord
        self.skipped_corrupt = skipped
        if skipped:
            _log.warning(
                "run ledger %s: skipped %d corrupt record(s) — the "
                "regression window is smaller than the file suggests",
                self.path, skipped)
        if surface:
            out = [r for r in out if r.get("surface") == surface]
        out.sort(key=lambda r: r.get("ts", 0.0))
        if limit is not None and limit > 0:
            out = out[-limit:]
        return out

    def find(self, token: str,
             surface: Optional[str] = None) -> Dict[str, Any]:
        """Resolve ``last`` / ``prev`` / a unique run-id prefix."""
        recs = self.records(surface=surface)
        if not recs:
            raise LedgerError(f"ledger at {self.root} holds no runs")
        if token in ("last", "latest"):
            return recs[-1]
        if token in ("prev", "previous"):
            if len(recs) < 2:
                raise LedgerError("ledger holds only one run; no 'prev'")
            return recs[-2]
        matches = [r for r in recs if str(r["run_id"]).startswith(token)]
        ids = {r["run_id"] for r in matches}
        if not matches:
            raise LedgerError(f"no run id matches {token!r}")
        if len(ids) > 1:
            raise LedgerError(
                f"run id prefix {token!r} is ambiguous: {sorted(ids)}")
        return matches[-1]


# ---- diffing and rendering ----------------------------------------------


def _phase_rows(a: Dict[str, Any], b: Dict[str, Any]) -> List[Dict[str, Any]]:
    pa, pb = a.get("phases") or {}, b.get("phases") or {}
    names = set(pa) | set(pb)
    ordered = [n for n in PHASE_ORDER if n in names]
    ordered += sorted(names - set(ordered))
    rows = []
    for name in ordered:
        va, vb = pa.get(name), pb.get(name)
        row: Dict[str, Any] = {"phase": name, "a_s": va, "b_s": vb}
        if va is not None and vb is not None:
            row["delta_s"] = round(vb - va, 6)
            row["pct"] = round(100.0 * (vb - va) / va, 1) if va > 0 else None
        rows.append(row)
    return rows


def diff_records(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Structured diff of two RunRecords: fingerprint drift, result-digest
    equality (nondeterminism flag), and per-phase timing deltas."""
    fa, fb = a.get("fingerprint") or {}, b.get("fingerprint") or {}
    drift = [k for k in ("engine", "bucket", "workload")
             if fa.get(k) != fb.get(k)]
    ra, rb = a.get("result") or {}, b.get("result") or {}
    have_digests = bool(ra.get("digest")) and bool(rb.get("digest"))
    identical = have_digests and ra["digest"] == rb["digest"]
    nondeterministic = (have_digests and not identical
                        and bool(fa) and fa == fb)
    return {
        "a": {k: a.get(k) for k in ("run_id", "ts", "surface")},
        "b": {k: b.get(k) for k in ("run_id", "ts", "surface")},
        "fingerprint": {"match": not drift and bool(fa),
                        "drift": drift, "a": fa, "b": fb},
        "result": {"identical": identical,
                   "nondeterministic": nondeterministic, "a": ra, "b": rb},
        "phases": _phase_rows(a, b),
    }


def _fmt_ts(ts) -> str:
    try:
        return time.strftime("%Y-%m-%dT%H:%M:%S", time.localtime(float(ts)))
    except (TypeError, ValueError):
        return "?"


def format_diff(d: Dict[str, Any]) -> str:
    a, b = d["a"], d["b"]
    lines = [
        f"runs diff: {a['run_id']} ({a['surface']}, {_fmt_ts(a['ts'])}) -> "
        f"{b['run_id']} ({b['surface']}, {_fmt_ts(b['ts'])})",
    ]
    fp = d["fingerprint"]
    if fp["match"]:
        fa = fp["a"]
        lines.append(
            f"config fingerprint: MATCH (engine={fa.get('engine')} "
            f"bucket={fa.get('bucket')} workload={fa.get('workload')})")
    elif not fp["a"] and not fp["b"]:
        lines.append("config fingerprint: absent on both records")
    else:
        parts = []
        for key in ("engine", "bucket", "workload"):
            va, vb = fp["a"].get(key), fp["b"].get(key)
            if va != vb:
                what = {
                    "engine": "engine config changed",
                    "bucket": "bucket shapes changed (recompile boundary)",
                    "workload": "workload changed",
                }[key]
                parts.append(f"{what}: {va} -> {vb}")
        lines.append("config fingerprint: DRIFT — " + "; ".join(parts))
    res = d["result"]
    ra, rb = res["a"], res["b"]
    if res["identical"]:
        lines.append(
            f"result: IDENTICAL digest {ra.get('digest')} "
            f"(placed {ra.get('placed')} / unplaced {ra.get('unplaced')}, "
            "both runs)")
    elif ra.get("digest") and rb.get("digest"):
        lines.append(
            f"result: DIFFERS — placed {ra.get('placed')} -> "
            f"{rb.get('placed')}, unplaced {ra.get('unplaced')} -> "
            f"{rb.get('unplaced')} "
            f"(digest {ra.get('digest')} -> {rb.get('digest')})")
        if res["nondeterministic"]:
            lines.append("  [!] NONDETERMINISM: identical config "
                         "fingerprints produced different result digests")
        elif d["fingerprint"]["drift"]:
            lines.append("  (explained by the config-fingerprint drift above)")
    else:
        lines.append("result: digest absent on at least one record")
    lines.append("phases (seconds, a -> b):")
    for row in d["phases"]:
        va = "-" if row["a_s"] is None else f"{row['a_s']:.6f}"
        vb = "-" if row["b_s"] is None else f"{row['b_s']:.6f}"
        pct = (f"{row['pct']:+.1f}%"
               if row.get("pct") is not None else "")
        lines.append(f"  {row['phase']:<16} {va:>12} -> {vb:>12}  {pct}")
    return "\n".join(lines)


def run_summary(rec: Dict[str, Any]) -> Dict[str, Any]:
    res = rec.get("result") or {}
    return {
        "run_id": rec.get("run_id"),
        "ts": rec.get("ts"),
        "time": _fmt_ts(rec.get("ts")),
        "surface": rec.get("surface"),
        "placed": res.get("placed"),
        "unplaced": res.get("unplaced"),
        "digest": res.get("digest"),
        "wall_s": rec.get("wall_s"),
    }


def format_run_list(records: List[Dict[str, Any]]) -> str:
    if not records:
        return "(ledger holds no runs)"
    lines = [f"{'RUN ID':<14} {'TIME':<20} {'SURFACE':<24} "
             f"{'PLACED':>7} {'UNPLACED':>9} {'WALL_S':>9}  DIGEST"]
    for rec in records:
        s = run_summary(rec)
        lines.append(
            f"{str(s['run_id']):<14} {s['time']:<20} "
            f"{str(s['surface']):<24} "
            f"{('-' if s['placed'] is None else s['placed']):>7} "
            f"{('-' if s['unplaced'] is None else s['unplaced']):>9} "
            f"{('-' if s['wall_s'] is None else format(s['wall_s'], '.3f')):>9}"
            f"  {s['digest'] or '-'}")
    return "\n".join(lines)
