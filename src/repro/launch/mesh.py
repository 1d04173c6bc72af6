"""Mesh construction.  Functions only -- importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """The one mesh constructor of the repo: every axis ``Auto``.

    The model code places intermediates with ``with_sharding_constraint``
    and leaves the rest to the partitioner, which needs Auto axes;
    ``jax.make_mesh``'s default (Explicit) axes make sharding part of every
    op's type and refuse e.g. the embedding gather."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data","model"); 2 pods adds a "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a (data, model) mesh."""
    n = len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0 and cand <= n:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))


def main(argv=None):
    """``python -m repro mesh``: build a mesh and describe it — the
    quickest way to check what geometry this host (or ``--shape``)
    yields before committing a dry-run or training launch to it."""
    import argparse

    ap = argparse.ArgumentParser(
        description="construct and describe a device mesh")
    ap.add_argument("--shape", default=None, metavar="N,M[,K]",
                    help="explicit mesh shape (default: host devices)")
    ap.add_argument("--axes", default=None, metavar="A,B[,C]",
                    help="axis names for --shape (default data,model[,pod])")
    ap.add_argument("--production", action="store_true",
                    help="the 16x16 production pod mesh (needs 256 chips)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="with --production: 2 pods (adds a 'pod' axis)")
    args = ap.parse_args(argv)

    if args.production:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif args.shape:
        shape = tuple(int(x) for x in args.shape.split(","))
        axes = (tuple(args.axes.split(",")) if args.axes
                else ("pod", "data", "model")[-len(shape):])
        mesh = make_mesh(shape, axes)
    else:
        mesh = make_host_mesh()
    print(f"mesh shape={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"devices={mesh.devices.size} "
          f"platform={mesh.devices.flat[0].platform}")
    return mesh


if __name__ == "__main__":   # deprecated spelling; kept as a shim
    import sys as _sys
    print("note: `python -m repro.launch.mesh` is now "
          "`python -m repro mesh`", file=_sys.stderr)
    main()
