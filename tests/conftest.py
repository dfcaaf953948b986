"""Test environment: the CPU backend with an 8-device virtual mesh.

Tests run on XLA:CPU (JAX_PLATFORMS=cpu); sharding tests exercise the
same pjit/GSPMD paths on 8 virtual CPU devices. The chip is reached only
through `chip_smoke.py`, and tests/test_chip_compile.py compiles for a
described v5e without one.
"""

import os
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# One on-disk XLA compilation cache for the whole suite — including every
# SERVER SUBPROCESS the lifecycle/serving/session/tune tests spawn, which
# otherwise each cold-compile programs an earlier child (or the parent)
# already built. Keyed by HLO hash, so identical programs dedupe and
# bit-identical contracts are untouched; env vars so children inherit it.
# (tests/test_exec_cache.py::test_persistent_cache_writes_executables
# exercises the same machinery.)
_XLA_CACHE_DIR = os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    # per-user: a world-shared fixed path breaks on multi-user hosts
    # (first user owns the dir, every later user's cache writes fail)
    os.path.join(tempfile.gettempdir(),
                 f"simon-tpu-test-xla-cache-{os.getuid()}"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.makedirs(_XLA_CACHE_DIR, exist_ok=True)

import pytest  # noqa: E402

from open_simulator_tpu.k8s.objects import Node, Pod  # noqa: E402


def make_node(name, cpu_m=4000, mem_mib=8192, pods=110, labels=None, taints=None,
              unschedulable=False, extra_alloc=None):
    alloc = {"cpu": f"{cpu_m}m", "memory": f"{mem_mib}Mi", "pods": pods}
    alloc.update(extra_alloc or {})
    return Node.from_dict({
        "metadata": {"name": name, "labels": labels or {}},
        "status": {"allocatable": alloc},
        "spec": {"taints": taints or [], "unschedulable": unschedulable},
    })


def make_pod(name, cpu="500m", mem="512Mi", ns="default", labels=None, annotations=None,
             node_selector=None, tolerations=None, affinity=None, node_name="",
             host_ports=None, spread=None, scheduler=None):
    containers = [{
        "name": "c", "image": "nginx",
        "resources": {"requests": {"cpu": cpu, "memory": mem}},
        "ports": [{"hostPort": p} for p in (host_ports or [])],
    }]
    spec = {"containers": containers}
    if node_selector:
        spec["nodeSelector"] = node_selector
    if tolerations:
        spec["tolerations"] = tolerations
    if affinity:
        spec["affinity"] = affinity
    if node_name:
        spec["nodeName"] = node_name
    if spread:
        spec["topologySpreadConstraints"] = spread
    if scheduler:
        spec["schedulerName"] = scheduler
    return Pod.from_dict({
        "metadata": {"name": name, "namespace": ns, "labels": labels or {},
                     "annotations": annotations or {}},
        "spec": spec,
    })


@pytest.fixture
def node_factory():
    return make_node


@pytest.fixture
def pod_factory():
    return make_pod
