"""Compile rehearsals for TPU v5e, run without the chip.

The TPU compiler installed here compiles for a described v5e topology,
so these tests catch what the chip's compiler would refuse (memory,
partitioning) and check the compiled HLO, at no chip time. The topology
is described inside a fixture only: describing it loads libtpu, which
one process at a time may hold, so it must never happen at import.
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A TPU executable written to the persistent cache cannot be read
    back without a chip; keep these compiles out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _lane_program(n_nodes, n_pods, max_new, lanes, rich=True, pools=0):
    """The sweep's batched executable inputs, as the product builds them:
    bucketed arrays (all ops by default), its wave plan, a zeros carry
    batch."""
    import jax

    from open_simulator_tpu.engine import exec_cache
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.engine.waves import waves_for
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    snap = synthetic_snapshot(n_nodes, n_pods, max_new=max_new, rich=rich,
                              pools=pools)
    cfg = make_config(snap)._replace(fail_reasons=False)
    arrs = exec_cache.pad_snapshot_arrays(
        snap.arrays, *exec_cache.bucket_shape(snap.n_nodes, snap.n_pods))
    waves = waves_for(snap.arrays, cfg, n_pods_total=int(arrs.req.shape[0]))
    carry = jax.eval_shape(
        lambda a: exec_cache._zeros_carry_batch(a, cfg, lanes), arrs)
    fn = exec_cache.batched_lane_fn(cfg, waves, False)
    return fn, arrs, carry, (lanes, arrs.alloc.shape[0]), waves


def _shape(x, sharding):
    import jax

    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


@pytest.fixture(scope="module")
def single_chip_default(topo, no_persistent_cache):
    """The `default` preset's executable (1,024 nodes x 2,048 all-ops
    pods x 256 lanes) compiled for one v5e chip."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    fn, arrs, carry, mask_shape, _ = _lane_program(1024, 2048, 8, 256)
    return _compile_one_chip(one, fn, arrs, carry, mask_shape)


def _compile_one_chip(one, fn, arrs, carry, mask_shape):
    import jax
    import jax.numpy as jnp

    tree = jax.tree_util.tree_map
    return jax.jit(fn, donate_argnums=(2,)).lower(
        tree(lambda x: _shape(x, one), arrs),
        jax.ShapeDtypeStruct(mask_shape, jnp.bool_, sharding=one),
        tree(lambda x: _shape(x, one), carry)).compile()


def test_single_chip_executable_fits_v5e(single_chip_default):
    mem = single_chip_default.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total


def test_mesh_executable_compiles_on_2x2(topo, no_persistent_cache):
    """run_mesh_cached's program on the four chips of a 2x2 v5e: lanes
    split over a 4-wide "scenario" axis, the snapshot replicated (the
    "node" axis stays 1, ROADMAP B3)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from open_simulator_tpu.engine.exec_cache import mesh_shardings

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("scenario", "node"))
    fn, arrs, carry, mask_shape, _ = _lane_program(32, 128, 8, 8)
    (arrs_sh, mask_sh, carry_sh, _), out_sh = mesh_shardings(arrs, carry, mesh)
    tree = jax.tree_util.tree_map
    compiled = jax.jit(
        fn, donate_argnums=(2,), in_shardings=(arrs_sh, mask_sh, carry_sh),
        out_shardings=out_sh,
    ).lower(tree(lambda x: _shape(x, None), arrs),
            jax.ShapeDtypeStruct(mask_shape, jnp.bool_),
            tree(lambda x: _shape(x, None), carry)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
    out = compiled.output_shardings
    assert out.node.spec[0] == "scenario"
    assert out.state.headroom.spec[0] == "scenario"


def test_scan_dots_run_at_highest_precision(single_chip_default):
    """The engine sums exact integer counts in f32 matmuls; the TPU's
    default precision rounds f32 operands to bf16 (exact only to 256),
    so every f32 dot/convolution must carry HIGHEST operand precision."""
    hlo = single_chip_default.as_text()
    dots = re.findall(r"= f32\[[^\n]*? (?:dot|convolution)\([^\n]*", hlo)
    assert dots, "no f32 dot in the compiled scan: the guard checks nothing"
    loose = [d[:160] for d in dots
             if "operand_precision={highest,highest}" not in d]
    assert not loose, loose


def _computations(hlo):
    """{name: body text} of every computation in an HLO module's text."""
    comps, name, lines = {}, None, []
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name, lines = head.group(1), []
        elif line == "}" and name is not None:
            comps[name] = "\n".join(lines)
            name = None
        elif name is not None:
            lines.append(line)
    return comps


@pytest.fixture(scope="module")
def wave_body(topo, no_persistent_cache):
    """lanes -> (loop body text, node bucket) of a GRID wave step compiled
    for one v5e: 32 tenant pools, waves of 32 pods, a 128-node bucket."""
    from jax.sharding import SingleDeviceSharding

    from open_simulator_tpu.engine.waves import GRID

    built = {}

    def get(lanes):
        if lanes not in built:
            fn, arrs, carry, mask_shape, waves = _lane_program(
                64, 128, 8, lanes, rich=False, pools=32)
            assert waves is not None and waves.segments == ((0, 128, GRID, 32),)
            hlo = _compile_one_chip(SingleDeviceSharding(topo.devices[0]),
                                    fn, arrs, carry, mask_shape).as_text()
            comps = _computations(hlo)
            bodies = [comps[b] for b in re.findall(r"body=%([\w.\-]+)", hlo)]
            assert len(bodies) == 1, "expected the one GRID wave loop"
            body = bodies[0]
            # one f32 per lane and pod: the normalizers, the best scores
            assert re.search(rf"= \(?f32\[{lanes},32\]", body), (
                "no [lanes, wave] f32 result in the wave body: the guard "
                "checks nothing")
            built[lanes] = body, arrs.alloc.shape[0]
        return built[lanes]

    return get


def test_wave_step_reduces_normalizers_without_relayout(wave_body):
    """A GRID wave step at 64 lanes (32 tenant pools, waves of 32 pods,
    a 128-node bucket: the smallest cluster at which the score
    normalizers once took the unfused road) reduces each normalizer row
    where the row is computed. No copy in the wave loop's body may
    relay out a [lanes, wave width, N] f32 row for a reduce: that road
    wrote and relaid out four such rows every wave, 71% of a 5,120-node
    64-lane sweep's device time on v5e."""
    lanes = 64
    body, n = wave_body(lanes)
    rows = re.findall(
        rf"%([\w.\-]+) = f32\[{lanes},32,{n}\]\{{[^}}]*\}} copy\(", body)
    reduced = [c for c in rows
               if re.search(rf" reduce\([^\n]*%{re.escape(c)}[,)]", body)]
    assert not reduced, reduced


@pytest.mark.parametrize("lanes", [8, 64])
def test_wave_step_broadcasts_domains_without_gather(wave_body, lanes):
    """The spread score reads each node's zone count from a [zones, wave]
    table per lane. No op in the wave loop's body may gather that table
    out to an [N, lanes, wave width] f32 row, nor copy such a row to
    relay it out: on a 5,120-node cluster that gather and its copy took
    52% of a 64-lane sweep's device time on v5e, and 42% of the wave
    loop at 8 lanes."""
    body, n = wave_body(lanes)
    dims = sorted((n, lanes, 32))
    rows = [line.strip()[:160] for line in body.splitlines()
            if (m := re.search(r"= f32\[([\d,]+)\]\S* (\w+)\(", line))
            and sorted(map(int, m.group(1).split(","))) == dims
            and (m.group(2) == "copy" or "gather" in line)]
    assert not rows, rows


@pytest.mark.parametrize("lanes", [8, 64])
def test_wave_step_scores_each_node_once(wave_body, lanes):
    """selectHost's min-index pass reads the masked score row that the
    max's pass wrote. When every score input is a small table, XLA would
    rather fuse the whole score into both reduces and compute it twice
    a wave: on a 5,120-node cluster at 64 lanes that second score took a
    third of the wave loop's device time on v5e."""
    body, n = wave_body(lanes)
    rows = set(re.findall(rf"%([\w.\-]+) = f32\[{lanes},32,{n}\]\{{", body))
    picks = re.findall(rf"= s32\[{lanes},32\]\{{[^}}]*\}} fusion\(([^)]*)\)",
                       body)
    assert picks, "no [lanes, wave] index fusion: the guard checks nothing"
    assert any(set(re.findall(r"%([\w.\-]+)", ops)) & rows for ops in picks)


@pytest.fixture(scope="module")
def narrowed_wave_body(topo, no_persistent_cache):
    """(loop body text, node bucket, plan) of the pools lane program at
    64 lanes and the benchmark's node count: 5,120 nodes in 32 tenant
    pools plus 64 template slots in pool p0 (a 6,144-node bucket),
    3,200 pods in waves of 32."""
    from jax.sharding import SingleDeviceSharding

    fn, arrs, carry, mask_shape, waves = _lane_program(
        5120, 3200, 64, 64, rich=False, pools=32)
    hlo = _compile_one_chip(SingleDeviceSharding(topo.devices[0]),
                            fn, arrs, carry, mask_shape).as_text()
    bodies = re.findall(r"body=%([\w.\-]+)", hlo)
    assert len(bodies) == 1, "expected the one GRID wave loop"
    return _computations(hlo)[bodies[0]], arrs.alloc.shape[0], waves


def test_pools_wave_step_runs_over_footprints(narrowed_wave_body):
    """Each pool's pods can only land on its 160 nodes (224 in p0, with
    the templates), so the plan narrows the GRID segment to 256 slots
    and no op of the wave loop's body may compute a [lanes, wave width,
    N] f32 row: those passes over all 6,144 node slots were 93% of a
    pools5k.sweep64 launch on v5e."""
    body, n, waves = narrowed_wave_body
    assert waves.narrow[0][0] == 256 and waves.narrowed_fraction > 0.75
    assert re.search(r"= \(?f32\[64,32,256\]", body), (
        "no [lanes, wave, K] f32 row in the wave body: the guard checks "
        "nothing")
    wide = [line.strip()[:160] for line in body.splitlines()
            if re.search(rf"f32\[64,32,{n}\]", line)]
    assert not wide, wide


def _ops_without_metadata(hlo):
    """Every computation's ops, without source locations."""
    return {name: re.sub(r", metadata=\{[^}]*\}", "", body)
            for name, body in _computations(hlo).items()}


@pytest.mark.parametrize("pools", [0, 32])
def test_lane_program_without_narrowing_is_unchanged(
        topo, no_persistent_cache, monkeypatch, pools):
    """A program the plan does not narrow compiles op for op as it did
    before narrowing existed: the sequential scan of a cluster with no
    wave plan (the spread5k shape), and a GRID plan whose width K (128
    slots for pools of 2 of 64 nodes) exceeds half of the node axis."""
    from jax.sharding import SingleDeviceSharding

    from open_simulator_tpu.engine import exec_cache
    from open_simulator_tpu.engine import waves as W

    one = SingleDeviceSharding(topo.devices[0])

    def build():
        fn, arrs, carry, mask_shape, waves = _lane_program(
            64, 128, 8, 8, rich=False, pools=pools)
        assert (waves is None) == (pools == 0)
        assert waves is None or not any(k for k, _ in waves.narrow)
        return _ops_without_metadata(
            _compile_one_chip(one, fn, arrs, carry, mask_shape).as_text())

    as_is = build()
    exec_cache.batched_lane_fn.cache_clear()
    W._PLAN_CACHE.clear()
    monkeypatch.setattr(W, "narrowing_exact", lambda cfg: False)
    assert build() == as_is
