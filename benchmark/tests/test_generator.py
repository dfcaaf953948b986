"""The benchmark's generator with seed 0 builds the program's synthetic
objects (testing/synthetic.py), so allops5k --seed 0 is PR 21's cluster."""

import pytest

from benchmark.generator import cluster_dicts


@pytest.mark.parametrize("kw", [dict(rich=True), dict(pools=32), dict(pools=8, bound=0.5),
                                dict()])
def test_seed_zero_is_the_programs_cluster(kw):
    from open_simulator_tpu.k8s.objects import Node, Pod
    from open_simulator_tpu.testing.synthetic import synthetic_objects

    nodes, pods, template = synthetic_objects(160, 700, **kw)
    nd, pd, td = cluster_dicts(0, 160, 700, **kw)
    assert [n.raw for n in nodes] == [Node.from_dict(d).raw for d in nd]
    assert [p.raw for p in pods] == [Pod.from_dict(d).raw for d in pd]
    assert template.raw == Node.from_dict(td).raw


def test_seeds_differ_and_repeat():
    a = cluster_dicts(2 ** 31 + 11, 32, 64, rich=True)
    assert a == cluster_dicts(2 ** 31 + 11, 32, 64, rich=True)
    assert a != cluster_dicts(12, 32, 64, rich=True)
    assert cluster_dicts(2 ** 40, 8, 8) == cluster_dicts(2 ** 40, 8, 8)
