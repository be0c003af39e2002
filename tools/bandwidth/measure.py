#!/usr/bin/env python
"""Collective-bandwidth harness (reference ``tools/bandwidth/
measure.py``†, rebuilt for XLA collectives): times in-graph psum /
all_gather / reduce_scatter / ppermute over the device mesh and prints
GB/s per collective — the ICI/DCN story the kvstore path rides.

Single real chip: trivially fast (no transport).  Multi-device: run
under the virtual CPU mesh or on a slice:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  JAX_PLATFORMS=cpu python tools/bandwidth/measure.py --mb 64
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mb", type=float, default=64.0,
                   help="payload megabytes per device")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--dtype", default="float32")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("x",))
    elems = int(args.mb * 1e6 / jnp.dtype(args.dtype).itemsize)
    elems -= elems % max(n, 1)
    x = jnp.ones((elems,), args.dtype)
    x = jax.device_put(x, NamedSharding(mesh, P("x")))
    nbytes = elems * jnp.dtype(args.dtype).itemsize

    def timed(fn, x):
        f = jax.jit(fn)
        out = f(x)
        jax.block_until_ready(out)
        float(jnp.sum(out))  # force a host sync even on async runtimes
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = f(x)
        float(jnp.sum(out))
        return (time.perf_counter() - t0) / args.iters

    shard_map = jax.shard_map

    def _psum(v):
        return jax.lax.psum(v, "x")

    def _ag(v):
        return jax.lax.all_gather(v, "x", tiled=True)

    def _ppermute(v):
        return jax.lax.ppermute(
            v, "x", [(i, (i + 1) % n) for i in range(n)])

    print(f"devices: {n} x {devs[0].device_kind}; payload "
          f"{nbytes / 1e6:.0f} MB total")
    for name, coll, spec_out in (
            ("psum (all-reduce)", _psum, P("x")),
            ("all_gather", _ag, P()),
            ("ppermute (ring hop)", _ppermute, P("x"))):
        fn = shard_map(coll, mesh=mesh, in_specs=P("x"),
                       out_specs=spec_out, check_vma=False)
        dt = timed(fn, x)
        # algorithm bytes: all-reduce moves 2(n-1)/n of payload per
        # device; gather/permute move the payload once
        factor = 2 * (n - 1) / max(n, 1) if "psum" in name else 1.0
        gbps = nbytes * factor / dt / 1e9
        print(f"{name:22s}: {dt * 1e3:8.2f} ms  ->  "
              f"{gbps:7.2f} GB/s (bus)")


if __name__ == "__main__":
    main()
