"""A plain kube-scheduler (v1.23 default profile) over Kubernetes object
dicts, written for the benchmark's correctness check.

It imports nothing of the program and reads nothing the program made: it
parses the generator's node and pod dicts itself. It does not schedule
on its own. It follows the program's placements pod by pod (teacher
forcing): the state before pod i is built from the placements the
program gave pods 0..i-1, and at a pod under test it runs every filter
and score plugin of the profile over all nodes of the lane. That judges
each placement on its own, so one early difference cannot cascade.

Plugins and weights (v1beta2 defaults, plus open-simulator's Simon
score plugin with weight 1): NodeUnschedulable, NodeAffinity (filter,
score 1), TaintToleration (filter, score 1), NodePorts,
NodeResourcesFit (filter; LeastAllocated score 1),
NodeResourcesBalancedAllocation (score 1), InterPodAffinity (filter,
score 1, hardPodAffinityWeight 1), PodTopologySpread (filter, score 2),
Simon (score 1). Scores are kept as real numbers (float64 by default):
the Go code truncates each plugin's score to an int64, which the program
documents it does not do, and which would blur gaps of under one point.

`dtype` selects the precision of every floating value, the state's
counts and sums included; bfloat16 is the control of PERF.md.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

HOST = "kubernetes.io/hostname"
LOW = ml_dtypes.bfloat16          # the control's precision
# NonZeroRequested defaults of the scheduler (util/non_zero.go)
NONZERO_CPU = 100
NONZERO_MEM = 200 * 1024 * 1024
W_FIT = W_BALANCED = W_NODE_AFF = W_TAINT = W_INTERPOD = W_SIMON = 1.0
W_SPREAD = 2.0
HARD_POD_AFFINITY_WEIGHT = 1.0

_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "Pi": 2 ** 50, "Ei": 2 ** 60, "k": 10 ** 3, "M": 10 ** 6,
           "G": 10 ** 9, "T": 10 ** 12, "P": 10 ** 15, "E": 10 ** 18}


def quantity(q, milli: bool = False) -> int:
    """A resource.Quantity string as an int: milli-units for cpu, else
    units (bytes), rounded up as the API server does."""
    s = str(q).strip()
    if s.endswith("m"):
        val = float(s[:-1])
        return math.ceil(val) if milli else math.ceil(val / 1000)
    scale = 1
    for suf in sorted(_SUFFIX, key=len, reverse=True):
        if s.endswith(suf):
            s, scale = s[: -len(suf)], _SUFFIX[suf]
            break
    val = float(s) * scale
    return math.ceil(val * 1000) if milli else math.ceil(val)


# ---- selectors -----------------------------------------------------------

def label_selector_matches(sel: Optional[Dict], labels: Dict[str, str]) -> bool:
    """metav1.LabelSelector: nil matches nothing, {} matches everything."""
    if sel is None:
        return False
    for k, v in (sel.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for e in sel.get("matchExpressions") or []:
        k, op, vals = e["key"], e["operator"], e.get("values") or []
        if op == "In" and labels.get(k) not in vals:
            return False
        if op == "NotIn" and k in labels and labels[k] in vals:
            return False
        if op == "Exists" and k not in labels:
            return False
        if op == "DoesNotExist" and k in labels:
            return False
    return True


def node_requirement_matches(req: Dict, labels: Dict[str, str], name: str,
                             field: bool = False) -> bool:
    """One NodeSelectorRequirement (matchExpressions or matchFields)."""
    k, op, vals = req["key"], req["operator"], req.get("values") or []
    if field:
        have = {"metadata.name": name}.get(k)
        if op == "In":
            return have in vals
        if op == "NotIn":
            return have not in vals
        return False
    present = k in labels
    v = labels.get(k)
    if op == "In":
        return present and v in vals
    if op == "NotIn":
        return not present or v not in vals
    if op == "Exists":
        return present
    if op == "DoesNotExist":
        return not present
    if op in ("Gt", "Lt"):
        try:
            a, b = int(v), int(vals[0])
        except (TypeError, ValueError, IndexError):
            return False
        return a > b if op == "Gt" else a < b
    return False


def node_term_matches(term: Dict, labels: Dict[str, str], name: str) -> bool:
    """A NodeSelectorTerm: all its requirements (an empty term matches
    nothing)."""
    exprs = term.get("matchExpressions") or []
    fields = term.get("matchFields") or []
    if not exprs and not fields:
        return False
    return (all(node_requirement_matches(r, labels, name) for r in exprs)
            and all(node_requirement_matches(r, labels, name, field=True)
                    for r in fields))


def tolerates(tol: Dict, taint: Dict) -> bool:
    """v1.Toleration.ToleratesTaint."""
    eff = tol.get("effect") or ""
    if eff and eff != taint.get("effect"):
        return False
    key = tol.get("key") or ""
    if key and key != taint.get("key"):
        return False
    op = tol.get("operator") or "Equal"
    if op == "Exists":
        return True
    return (tol.get("value") or "") == (taint.get("value") or "")


# ---- objects ---------------------------------------------------------------

class PodSpec:
    """What the profile reads of one pod."""

    def __init__(self, d: Dict):
        meta, spec = d.get("metadata") or {}, d.get("spec") or {}
        self.ns = meta.get("namespace") or "default"
        self.labels = dict(meta.get("labels") or {})
        self.node_name = spec.get("nodeName") or ""
        req = [0, 0, 1]
        nz = [0, 0]
        for c in spec.get("containers") or []:
            r = (c.get("resources") or {}).get("requests") or {}
            cpu = quantity(r["cpu"], milli=True) if "cpu" in r else 0
            mem = quantity(r["memory"]) if "memory" in r else 0
            req[0] += cpu
            req[1] += mem
            nz[0] += cpu or NONZERO_CPU
            nz[1] += mem or NONZERO_MEM
        for c in spec.get("initContainers") or []:
            r = (c.get("resources") or {}).get("requests") or {}
            req[0] = max(req[0], quantity(r.get("cpu", 0), milli=True))
            req[1] = max(req[1], quantity(r.get("memory", 0)))
        self.req = req
        self.nz = nz
        self.ports = []
        for c in spec.get("containers") or []:
            for p in c.get("ports") or []:
                if p.get("hostPort"):
                    self.ports.append((p.get("protocol") or "TCP",
                                       int(p["hostPort"]),
                                       p.get("hostIP") or "0.0.0.0"))
        self.node_selector = dict(spec.get("nodeSelector") or {})
        self.tolerations = list(spec.get("tolerations") or [])
        aff = spec.get("affinity") or {}
        na = aff.get("nodeAffinity") or {}
        req_na = na.get("requiredDuringSchedulingIgnoredDuringExecution")
        self.node_terms = (req_na or {}).get("nodeSelectorTerms") if req_na else None
        self.node_pref = [(float(t["weight"]), t.get("preference") or {})
                          for t in na.get("preferredDuringSchedulingIgnoredDuringExecution") or []]
        pa = aff.get("podAffinity") or {}
        paa = aff.get("podAntiAffinity") or {}
        self.aff_req = list(pa.get("requiredDuringSchedulingIgnoredDuringExecution") or [])
        self.anti_req = list(paa.get("requiredDuringSchedulingIgnoredDuringExecution") or [])
        self.aff_pref = [(float(t["weight"]), t["podAffinityTerm"])
                         for t in pa.get("preferredDuringSchedulingIgnoredDuringExecution") or []]
        self.anti_pref = [(float(t["weight"]), t["podAffinityTerm"])
                          for t in paa.get("preferredDuringSchedulingIgnoredDuringExecution") or []]
        self.spread = list(spec.get("topologySpreadConstraints") or [])
        self.tol_unsched = any(
            tolerates(t, {"key": "node.kubernetes.io/unschedulable",
                          "effect": "NoSchedule"}) for t in self.tolerations)


def template_copies(template: Dict, count: int) -> List[Dict]:
    """The capacity question's new nodes: copies of the template, each
    its own host."""
    out = []
    for j in range(count):
        d = json.loads(json.dumps(template))
        d["metadata"]["name"] = f"new-{j}"
        out.append(d)
    return out


class Cluster:
    """Nodes (real ones, then the template's copies) and pods in order,
    with every selector and term resolved to columns."""

    def __init__(self, node_dicts: Sequence[Dict], pod_dicts: Sequence[Dict]):
        self.n = len(node_dicts)
        self.node_names = [d["metadata"]["name"] for d in node_dicts]
        self.node_labels = []
        for d in node_dicts:
            lab = dict((d.get("metadata") or {}).get("labels") or {})
            lab.setdefault(HOST, d["metadata"]["name"])  # kubelet sets it
            self.node_labels.append(lab)
        alloc = np.zeros((self.n, 3), np.int64)
        for i, d in enumerate(node_dicts):
            a = (d.get("status") or {}).get("allocatable") or {}
            alloc[i] = (quantity(a.get("cpu", 0), milli=True),
                        quantity(a.get("memory", 0)), quantity(a.get("pods", 0)))
        self.alloc = alloc
        self.taints = [list((d.get("spec") or {}).get("taints") or [])
                       for d in node_dicts]
        self.unsched = np.array([bool((d.get("spec") or {}).get("unschedulable"))
                                 for d in node_dicts])
        self.pods = [PodSpec(d) for d in pod_dicts]
        self.p = len(self.pods)
        self.req = np.array([p.req for p in self.pods], np.int64).reshape(-1, 3)
        self.nz = np.array([p.nz for p in self.pods], np.int64).reshape(-1, 2)
        self.node_index = {nm: i for i, nm in enumerate(self.node_names)}
        self._dom: Dict[str, Tuple[np.ndarray, int]] = {}
        self._cache: Dict[Tuple, np.ndarray] = {}
        self._build_columns()

    # ---- topology ----------------------------------------------------------

    def dom(self, key: str) -> Tuple[np.ndarray, int]:
        """(domain id per node, -1 without the label; number of domains)."""
        if key not in self._dom:
            if key == HOST:
                self._dom[key] = (np.arange(self.n), self.n)
            else:
                vals: Dict[str, int] = {}
                ids = np.full(self.n, -1, np.int64)
                for i, lab in enumerate(self.node_labels):
                    if key in lab:
                        ids[i] = vals.setdefault(lab[key], len(vals))
                self._dom[key] = (ids, max(len(vals), 1))
        return self._dom[key]

    def domain_sum(self, vec: np.ndarray, key: str, counted: np.ndarray) -> np.ndarray:
        """Per node: the sum of `vec` over the counted nodes of its domain
        (0 where the node lacks the key)."""
        ids, nd = self.dom(key)
        if key == HOST:
            return vec * counted.astype(vec.dtype)
        ok = counted & (ids >= 0)
        if vec.dtype == np.float64:
            sums = np.bincount(ids[ok], weights=vec[ok], minlength=nd)
        else:  # lower precision: accumulate in it
            sums = np.zeros(nd, dtype=vec.dtype)
            accumulate(sums, (ids[ok],), vec[ok])
        return sums[np.maximum(ids, 0)] * (ids >= 0).astype(vec.dtype)

    # ---- selector columns ---------------------------------------------------

    def _build_columns(self) -> None:
        """Group columns: which pods each selector (with its namespaces)
        matches; term vocabularies: which pods own each required anti- or
        affinity term and each preferred term."""
        groups: Dict[str, int] = {}
        self.group_sel: List[Tuple] = []

        def gid(sel, namespaces) -> int:
            key = json.dumps([sel, sorted(namespaces)], sort_keys=True)
            if key not in groups:
                groups[key] = len(groups)
                self.group_sel.append((sel, frozenset(namespaces), None))
            return groups[key]

        def conj(gs: Tuple[int, ...]) -> int:
            key = json.dumps(["and", list(gs)])
            if key not in groups:
                groups[key] = len(groups)
                self.group_sel.append((None, None, gs))
            return groups[key]

        def term_ns(term, pod) -> List[str]:
            if term.get("namespaceSelector") is not None:
                raise ValueError("namespaceSelector is not supported by the reference")
            return list(term.get("namespaces") or [pod.ns])

        # per pod: spread (g, key, skew, hard), required terms, preferred terms
        self.pod_spread, self.pod_aff, self.pod_anti, self.pod_pref = [], [], [], []
        self.pod_aff_all = []
        anti_vocab: Dict[Tuple[int, str], int] = {}
        aff_vocab: Dict[Tuple[int, str], int] = {}
        pref_vocab: Dict[Tuple[int, str], int] = {}
        own_anti, own_aff, own_pref = [], [], []
        for p in self.pods:
            self.pod_spread.append([
                (gid(c.get("labelSelector"), [p.ns]), c["topologyKey"],
                 int(c["maxSkew"]), c.get("whenUnsatisfiable") == "DoNotSchedule")
                for c in p.spread])
            aff = [(gid(t.get("labelSelector"), term_ns(t, p)), t["topologyKey"])
                   for t in p.aff_req]
            anti = [(gid(t.get("labelSelector"), term_ns(t, p)), t["topologyKey"])
                    for t in p.anti_req]
            pref = ([(w, gid(t.get("labelSelector"), term_ns(t, p)), t["topologyKey"])
                     for w, t in p.aff_pref]
                    + [(-w, gid(t.get("labelSelector"), term_ns(t, p)), t["topologyKey"])
                       for w, t in p.anti_pref])
            self.pod_aff.append(aff)
            self.pod_anti.append(anti)
            self.pod_pref.append(pref)
            self.pod_aff_all.append(conj(tuple(sorted({g for g, _ in aff}))) if aff else -1)
            own_anti.append([anti_vocab.setdefault(t, len(anti_vocab)) for t in anti])
            own_aff.append([aff_vocab.setdefault(t, len(aff_vocab)) for t in aff])
            own_pref.append([(pref_vocab.setdefault((g, k), len(pref_vocab)), w)
                             for w, g, k in pref])
        self.anti_terms = sorted(anti_vocab, key=anti_vocab.get)
        self.aff_terms = sorted(aff_vocab, key=aff_vocab.get)
        self.pref_terms = sorted(pref_vocab, key=pref_vocab.get)
        self.n_groups = len(self.group_sel)

        # match matrix, evaluated once per distinct (namespace, labels)
        sets: Dict[Tuple, int] = {}
        set_of = np.zeros(self.p, np.int64)
        for i, p in enumerate(self.pods):
            set_of[i] = sets.setdefault((p.ns, tuple(sorted(p.labels.items()))), len(sets))
        lm = np.zeros((len(sets), self.n_groups), bool)
        for (ns, items), s in sets.items():
            labels = dict(items)
            for g, (sel, nss, parts) in enumerate(self.group_sel):
                if parts is None:
                    lm[s, g] = ns in nss and label_selector_matches(sel, labels)
        for g, (sel, nss, parts) in enumerate(self.group_sel):
            if parts is not None:
                lm[:, g] = np.all(lm[:, list(parts)], axis=1)
        self.match = lm[set_of]                              # [P, G]

        # sparse per-pod rows for the state updates: (pod, column, value)
        def csr(rows):
            ptr = np.zeros(self.p + 1, np.int64)
            ptr[1:] = np.cumsum([len(r) for r in rows])
            col = np.array([c for r in rows for c in (x[0] if isinstance(x, tuple) else x for x in r)],
                           np.int64)
            val = np.array([x[1] if isinstance(x, tuple) else 1.0 for r in rows for x in r],
                           np.float64)
            return ptr, col, val

        self.ports_vocab: Dict[Tuple, int] = {}
        port_rows = [[self.ports_vocab.setdefault(pt, len(self.ports_vocab))
                      for pt in p.ports] for p in self.pods]
        self.sp_groups = csr([list(np.nonzero(self.match[i])[0]) for i in range(self.p)])
        self.sp_ports = csr(port_rows)
        self.sp_anti = csr(own_anti)
        self.sp_aff = csr(own_aff)
        self.sp_pref = csr(own_pref)
        self.n_ports = max(len(self.ports_vocab), 1)

    # ---- per-pod static masks (cached) ------------------------------------------

    def eligible(self, i: int) -> np.ndarray:
        """NodeAffinity's filter: nodeSelector and required node affinity."""
        p = self.pods[i]
        key = ("elig", json.dumps([p.node_selector, p.node_terms], sort_keys=True))
        if key not in self._cache:
            ok = np.ones(self.n, bool)
            for j, lab in enumerate(self.node_labels):
                if any(lab.get(k) != v for k, v in p.node_selector.items()):
                    ok[j] = False
                elif p.node_terms is not None and not any(
                        node_term_matches(t, lab, self.node_names[j]) for t in p.node_terms):
                    ok[j] = False
            self._cache[key] = ok
        return self._cache[key]

    def taint_ok(self, i: int) -> np.ndarray:
        """TaintToleration's filter: NoSchedule and NoExecute taints."""
        p = self.pods[i]
        key = ("taint", json.dumps(p.tolerations, sort_keys=True))
        if key not in self._cache:
            self._cache[key] = np.array([
                all(any(tolerates(t, tn) for t in p.tolerations)
                    for tn in taints if tn.get("effect") in ("NoSchedule", "NoExecute"))
                for taints in self.taints])
        return self._cache[key]

    def taint_prefer(self, i: int) -> np.ndarray:
        """TaintToleration's score input: intolerable PreferNoSchedule taints."""
        p = self.pods[i]
        tols = [t for t in p.tolerations if (t.get("effect") or "") in ("", "PreferNoSchedule")]
        key = ("prefer", json.dumps(tols, sort_keys=True))
        if key not in self._cache:
            self._cache[key] = np.array([
                sum(1 for tn in taints if tn.get("effect") == "PreferNoSchedule"
                    and not any(tolerates(t, tn) for t in tols))
                for taints in self.taints], np.float64)
        return self._cache[key]

    def node_pref(self, i: int) -> np.ndarray:
        """NodeAffinity's score input: the weights of matching preferred terms."""
        p = self.pods[i]
        key = ("npref", json.dumps(p.node_pref, sort_keys=True))
        if key not in self._cache:
            out = np.zeros(self.n, np.float64)
            for w, term in p.node_pref:
                out += w * np.array([node_term_matches(term, lab, self.node_names[j])
                                     for j, lab in enumerate(self.node_labels)])
            self._cache[key] = out
        return self._cache[key]

    def port_conflicts(self, i: int) -> List[int]:
        """Vocabulary columns that conflict with the pod's host ports."""
        out = []
        for proto, port, ip in self.pods[i].ports:
            for (q_proto, q_port, q_ip), q in self.ports_vocab.items():
                if q_proto == proto and q_port == port and (
                        q_ip == ip or "0.0.0.0" in (q_ip, ip)):
                    out.append(q)
        return out


def accumulate(arr: np.ndarray, index: Tuple[np.ndarray, ...], vals: np.ndarray) -> None:
    """arr[index] += vals with repeats, in order. float64 in one call; a
    lower precision one add (and one rounding) at a time, since
    ufunc.at on bfloat16 arrays is not safe."""
    if arr.dtype == np.float64:
        np.add.at(arr, index, vals)
        return
    for j, at in enumerate(zip(*index)):
        arr[at] += vals[j]


class State:
    """Everything the plugins read of the pods placed so far, per node."""

    def __init__(self, c: Cluster, dtype=np.float64):
        self.c = c
        self.dt = dtype
        n = c.n
        self.used = np.zeros((n, 3), dtype)
        self.used_nz = np.zeros((n, 2), dtype)
        self.cnt = np.zeros((n, c.n_groups), dtype)        # matching pods per group
        self.ports = np.zeros((n, c.n_ports), dtype)
        self.anti_own = np.zeros((n, max(len(c.anti_terms), 1)), dtype)
        self.aff_own = np.zeros((n, max(len(c.aff_terms), 1)), dtype)
        self.pref_own = np.zeros((n, max(len(c.pref_terms), 1)), dtype)
        self.next = 0

    def advance(self, nodes: np.ndarray, upto: int) -> None:
        """Bind pods [self.next, upto) where the program put them
        (negative = not placed)."""
        a, b = self.next, upto
        if b <= a:
            return
        c, dt = self.c, self.dt
        nb = nodes[a:b]
        placed = nb >= 0
        accumulate(self.used, (nb[placed],), c.req[a:b][placed].astype(dt))
        accumulate(self.used_nz, (nb[placed],), c.nz[a:b][placed].astype(dt))
        for arr, (ptr, col, val) in ((self.cnt, c.sp_groups), (self.ports, c.sp_ports),
                                     (self.anti_own, c.sp_anti), (self.aff_own, c.sp_aff),
                                     (self.pref_own, c.sp_pref)):
            lo, hi = ptr[a], ptr[b]
            if hi == lo:
                continue
            pod = np.repeat(np.arange(a, b), np.diff(ptr[a:b + 1]))
            node = nodes[pod]
            ok = node >= 0
            accumulate(arr, (node[ok], col[lo:hi][ok]), val[lo:hi][ok].astype(dt))
        self.next = b


def evaluate(c: Cluster, st: State, i: int, active: np.ndarray):
    """Filter and score pod i against the state, over the lane's active
    nodes. Returns (feasible [N] bool, total score [N] in st.dt, with
    -inf off the feasible set)."""
    dt = st.dt
    p = c.pods[i]
    hundred = dt(100.0)
    ok = active.copy()
    if not p.tol_unsched:
        ok &= ~c.unsched
    elig = c.eligible(i)
    ok &= elig
    ok &= c.taint_ok(i)
    for q in c.port_conflicts(i):
        ok &= st.ports[:, q] == 0
    free = c.alloc.astype(dt) - st.used
    ok &= np.all(c.req[i].astype(dt)[None, :] <= free, axis=1)

    # InterPodAffinity filter
    if c.pod_aff[i]:
        g_all = c.pod_aff_all[i]
        exist = np.ones(c.n, bool)
        any_count = False
        for g, key in c.pod_aff[i]:
            ids, _ = c.dom(key)
            dc = c.domain_sum(st.cnt[:, g_all], key, active)
            exist &= dc > 0
            ok &= ids >= 0                          # every key on the node
            any_count |= bool(np.any(st.cnt[active & (ids >= 0), g_all] > 0))
        self_match = all(c.match[i, g] for g, _ in c.pod_aff[i])
        if not (not any_count and self_match):
            ok &= exist
    for g, key in c.pod_anti[i]:
        ok &= ~(c.domain_sum(st.cnt[:, g], key, active) > 0)
    for a, (g, key) in enumerate(c.anti_terms):
        if c.match[i, g]:
            ok &= ~(c.domain_sum(st.anti_own[:, a], key, active) > 0)

    # PodTopologySpread filter (DoNotSchedule)
    spread = c.pod_spread[i]
    hard = [s for s in spread if s[3]]
    if hard:
        counted = active & elig
        for _, key, _, _ in hard:
            counted &= c.dom(key)[0] >= 0
        for g, key, skew, _ in hard:
            ids, _ = c.dom(key)
            dc = c.domain_sum(st.cnt[:, g], key, counted)
            in_pair = c.domain_sum(counted.astype(dt), key, counted) > 0
            if counted.any():
                min_match = np.min(dc[counted])
            else:
                min_match = np.inf
            match_num = dc * in_pair.astype(dt)
            self_m = dt(1.0) if c.match[i, g] else dt(0.0)
            ok &= (ids >= 0) & ~(match_num + self_m - min_match > skew)

    if not ok.any():
        return ok, np.full(c.n, -np.inf)

    # ---- scores over the feasible set ------------------------------------------
    alloc = c.alloc[:, :2].astype(dt)
    want = st.used_nz + c.nz[i].astype(dt)[None, :]
    cap_ok = alloc > 0
    safe = np.where(cap_ok, alloc, dt(1.0))
    least_r = np.where(cap_ok & (want <= alloc), (alloc - want) * hundred / safe, dt(0.0))
    least = (least_r[:, 0] + least_r[:, 1]) / dt(2.0)
    frac = np.minimum(np.where(cap_ok, want / safe, dt(0.0)), dt(1.0))
    balanced = (dt(1.0) - np.abs(frac[:, 0] - frac[:, 1]) / dt(2.0)) * hundred
    total = dt(W_FIT) * least + dt(W_BALANCED) * balanced

    na = c.node_pref(i).astype(dt)
    na_max = np.max(na[ok])
    if na_max > 0:
        total = total + dt(W_NODE_AFF) * (na * hundred / na_max)

    tt = c.taint_prefer(i).astype(dt)
    tt_max = np.max(tt[ok])
    total = total + dt(W_TAINT) * (hundred - tt * hundred / tt_max if tt_max > 0
                                   else np.full(c.n, hundred, dt))

    # InterPodAffinity score: the incoming pod's preferred terms over the
    # placed pods, and placed pods' hard and preferred terms over it
    ip = np.zeros(c.n, dt)
    touched = False
    for w, g, key in c.pod_pref[i]:
        ip = ip + dt(w) * c.domain_sum(st.cnt[:, g], key, active)
        touched = True
    for h, (g, key) in enumerate(c.aff_terms):
        if c.match[i, g]:
            ip = ip + dt(HARD_POD_AFFINITY_WEIGHT) * c.domain_sum(st.aff_own[:, h], key, active)
            touched = True
    for t, (g, key) in enumerate(c.pref_terms):
        if c.match[i, g]:
            ip = ip + c.domain_sum(st.pref_own[:, t], key, active)
            touched = True
    if touched:
        lo, hi = np.min(ip[ok]), np.max(ip[ok])
        if hi > lo:
            total = total + dt(W_INTERPOD) * ((ip - lo) * hundred / (hi - lo))

    # PodTopologySpread score (ScheduleAnyway)
    soft = [s for s in spread if not s[3]]
    if soft:
        keys = {key for _, key, _, _ in soft}
        scored = ok.copy()
        for key in keys:
            scored &= c.dom(key)[0] >= 0
        counted = active & elig
        for key in keys:
            counted &= c.dom(key)[0] >= 0
        raw = np.zeros(c.n, dt)
        for g, key, skew, _ in soft:
            ids, nd = c.dom(key)
            if key == HOST:
                size = int(scored.sum())
                cnt = st.cnt[:, g]
            else:
                pairs = np.zeros(nd, bool)
                pairs[ids[scored]] = True
                size = int(pairs.sum())
                dc = c.domain_sum(st.cnt[:, g], key, counted)
                cnt = dc * (pairs[np.maximum(ids, 0)] & (ids >= 0)).astype(dt)
            weight = dt(math.log(size + 2))
            raw = raw + cnt.astype(dt) * weight + dt(skew - 1)
        if scored.any():
            s_max = np.max(raw[scored])
            s_min = np.min(raw[scored])
            if s_max == 0:
                sp = np.full(c.n, hundred, dt)
            else:
                sp = hundred * (s_max + s_min - raw) / s_max
            total = total + dt(W_SPREAD) * np.where(scored, sp, dt(0.0))

    # Simon (open-simulator): the largest share the pod takes of a
    # node's allocatable left after it, min-max normalized
    req = c.req[i, :2].astype(dt)
    avail = alloc - req[None, :]
    share = np.where(avail != 0, req[None, :] / np.where(avail != 0, avail, dt(1.0)),
                     np.where(req[None, :] != 0, dt(1.0), dt(0.0)))
    share = np.minimum(share, dt(1.0)) * (req[None, :] > 0).astype(dt)
    si = np.max(share, axis=1) * hundred
    lo, hi = np.min(si[ok]), np.max(si[ok])
    if hi > lo:
        total = total + dt(W_SIMON) * ((si - lo) * hundred / (hi - lo))

    return ok, np.where(ok, total.astype(np.float64), -np.inf)
