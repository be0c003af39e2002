"""One group of the registry-wide cpu<->tpu sweep.

``run_group(group_idx, group_size)`` is what ``test_tpu_sweep.py``
calls IN ITS OWN PROCESS: a chip belongs to one process at a time, and
a pytest parent that has initialised the accelerator cannot hand it to
a child.  The whole group runs as ONE jitted program per backend (fwd
+ grads for every case, inputs as runtime args so nothing
constant-folds) — one compile instead of ~2 per op.

Also a script, for running a group by hand on the chip:

    python tests/tpu_sweep_runner.py GROUP_IDX GROUP_SIZE [i,j,...]

prints one JSON line: {"results": [{name, case, status,
max_fwd_err, max_grad_err}...]}.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def run_group(group_idx, group_size, picks=None):
    """Results of one sweep group; ``picks`` (absolute case indices)
    overrides the group slice to isolate a single failing op."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ops.registry import get_op
    from tests.tpu_sweep_lib import build_cases

    cases, _ = build_cases()
    if picks:
        group = [cases[i] for i in picks]
    else:
        group = cases[group_idx * group_size:
                      (group_idx + 1) * group_size]
    if not group:
        return []

    def case_fwd(name, kw):
        op = get_op(name)

        def f(*aa):
            out = op(*aa, **kw)
            return [l.astype(jnp.float32)
                    if jnp.issubdtype(l.dtype, jnp.floating)
                    else l.astype(jnp.int32)
                    for l in jax.tree_util.tree_leaves(out)
                    if hasattr(l, "dtype")]
        return f

    def float_argnums(args):
        return tuple(i for i, a in enumerate(args)
                     if np.issubdtype(np.asarray(a).dtype,
                                      np.floating))

    # one traced program for the WHOLE group: flat arg list in,
    # flat list of (tagged) outputs out
    flat_args = []
    layout = []  # (name, case, n_args, want_grad, argnums)
    for (name, idx, args, kw) in group:
        argnums = float_argnums(args)
        want_grad = bool(argnums) and get_op(name).differentiable
        layout.append((name, idx, len(args), want_grad, argnums, kw))
        flat_args.extend(np.asarray(a) for a in args)

    def program(*flat):
        pos = 0
        outs = []
        for (name, idx, n_args, want_grad, argnums, kw) in layout:
            aa = flat[pos:pos + n_args]
            pos += n_args
            if (name, idx) in trace_errors:
                outs.append(None)
                outs.append(None)
                continue
            f = case_fwd(name, kw)
            outs.append(f(*aa))
            if want_grad:
                def scalar(*a2):
                    return sum(jnp.sum(l) for l in f(*a2)
                               if jnp.issubdtype(l.dtype,
                                                 jnp.floating))
                outs.append(list(
                    jax.grad(scalar, argnums=argnums)(*aa)))
            else:
                outs.append(None)
        return outs

    def run_backend(device):
        with jax.default_device(device):
            ja = [jnp.asarray(a) for a in flat_args]
            with jax.default_matmul_precision("highest"):
                res = jax.jit(program)(*ja)
            return jax.tree_util.tree_map(np.asarray, res)

    cpu = jax.local_devices(backend="cpu")[0]
    acc = jax.devices()[0]

    # cases whose fwd/grad trace fails must be dropped from the
    # program BEFORE compiling either backend (ONE bad trace would
    # otherwise fail the whole fused group); probe abstractly first
    # (cheap, no execution).  Dropped-fwd cases get their own error
    # entry in the results.
    trace_errors = {}
    for i, (name, idx, n_args, want_grad, argnums, kw) in \
            enumerate(layout):
        start = sum(l[2] for l in layout[:i])
        aa = flat_args[start:start + n_args]
        f = case_fwd(name, kw)
        try:
            jax.eval_shape(f, *aa)
        except Exception as e:
            trace_errors[(name, idx)] = \
                f"trace: {type(e).__name__}: {str(e)[:160]}"
            layout[i] = (name, idx, n_args, False, argnums, kw)
            continue
        if not want_grad:
            continue

        def scalar(*a2):
            return sum(jnp.sum(l) for l in f(*a2)
                       if jnp.issubdtype(l.dtype, jnp.floating))
        try:
            jax.eval_shape(jax.grad(scalar, argnums=argnums), *aa)
        except Exception:
            layout[i] = (name, idx, n_args, False, argnums, kw)

    def try_backend(device):
        try:
            return run_backend(device), None
        except Exception as e:
            return None, f"{type(e).__name__}: {str(e)[:300]}"

    ref, ref_err = try_backend(cpu)
    got, got_err = try_backend(acc)

    results = []
    if ref is None or got is None:
        status = "cpu_error" if ref is None else "tpu_error"
        err = ref_err or got_err
        for (name, idx, *_rest) in layout:
            results.append({"name": name, "case": idx,
                            "status": status, "error": err})
        return results

    def maxerr(a_list, b_list):
        if a_list is None or b_list is None:
            return None
        m = 0.0
        for a, b in zip(a_list, b_list):
            a = np.asarray(a, np.float64)
            b = np.asarray(b, np.float64)
            if a.shape != b.shape:
                return float("inf")
            if a.size:
                m = max(m, float((np.abs(a - b)
                                  / np.maximum(np.abs(a), 1.0)).max()))
        return m

    for i, (name, idx, n_args, want_grad, argnums, kw) in \
            enumerate(layout):
        if (name, idx) in trace_errors:
            results.append({"name": name, "case": idx,
                            "status": "trace_error",
                            "error": trace_errors[(name, idx)]})
            continue
        fwd_err = maxerr(ref[2 * i], got[2 * i])
        grad_err = maxerr(ref[2 * i + 1], got[2 * i + 1]) \
            if want_grad else None
        results.append({"name": name, "case": idx, "status": "ok",
                        "max_fwd_err": fwd_err,
                        "max_grad_err": grad_err})
    return results


def main():
    picks = [int(x) for x in sys.argv[3].split(",")] \
        if len(sys.argv) > 3 and sys.argv[3] else None
    print(json.dumps({"results": run_group(
        int(sys.argv[1]), int(sys.argv[2]), picks)}))


if __name__ == "__main__":
    main()
