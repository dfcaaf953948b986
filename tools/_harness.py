"""Argv parsing + jit-harness setup for tools/hlo_inventory.py, which
drives the north-star-shaped vmapped scan jit.
"""
import argparse


def parse_shape_args(description, nodes, pods, lanes, max_new,
                     extra_flags=(), argv=None):
    """Standard tool flags (--nodes/--pods/--lanes/--max-new) with the
    pre-argparse bare-positional form still accepted; `extra_flags` is a
    sequence of (name, kwargs) passed to add_argument."""
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--nodes", type=int, default=nodes, help="cluster nodes")
    p.add_argument("--pods", type=int, default=pods, help="pods to schedule")
    p.add_argument("--lanes", type=int, default=lanes,
                   help="vmapped what-if lanes")
    p.add_argument("--max-new", type=int, default=max_new,
                   help="sweep upper bound")
    for name, kwargs in extra_flags:
        p.add_argument(name, **kwargs)
    p.add_argument("legacy", nargs="*", type=int, metavar="INT",
                   help="legacy positional form: NODES PODS LANES MAX_NEW")
    args = p.parse_args(argv)
    for name, val in zip(("nodes", "pods", "lanes", "max_new"), args.legacy):
        setattr(args, name, val)
    if args.lanes < 1 or args.nodes < 1 or args.pods < 1 or args.max_new < 0:
        p.error("--nodes/--pods/--lanes must be >= 1 and --max-new >= 0")
    return args


def build_jit_harness(args):
    """(masks, fn) for the north-star shape: a vmapped+jitted
    schedule_pods over per-lane active masks, reasons off."""
    import jax
    import jax.numpy as jnp

    from open_simulator_tpu.engine.scheduler import (
        device_arrays,
        make_config,
        schedule_pods,
    )
    from open_simulator_tpu.parallel.sweep import active_masks_for_counts
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    snap = synthetic_snapshot(n_nodes=args.nodes, n_pods=args.pods,
                              max_new=args.max_new, rich=True)
    cfg = make_config(snap)._replace(fail_reasons=False)
    arrs = device_arrays(snap)
    counts = [min(i % (args.max_new + 1), args.max_new)
              for i in range(args.lanes)]
    masks = jnp.asarray(active_masks_for_counts(snap, counts))
    fn = jax.jit(jax.vmap(lambda a: schedule_pods(arrs, a, cfg)))
    return masks, fn
