"""ZeRO-1 sharded optimizer states in the compiled train step
(ISSUE 3 tentpole): on a dp mesh the step reduce-scatters gradients
per (shape, dtype) bucket, updates only the local 1/dp state shard,
and all-gathers fresh params — numerically identical to the
replicated all-reduce path (``zero=0``) for every supported
optimizer, with ~dp× less optimizer HBM.

Runs on the virtual 8-device CPU mesh conftest.py forces; the comm
signature is asserted on the compiled HLO itself (reduce-scatter +
all-gather present, no full-gradient all-reduce)."""

import jax
import numpy as np
import pytest

from mxtpu import nd, parallel
from mxtpu.base import MXNetError
from mxtpu.gluon import nn
from mxtpu.parallel import (plan_zero_buckets, restore_params,
                            snapshot_params)


def _mesh(n=8):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]), ("dp",))


def _make_net(x):
    net = nn.HybridSequential()
    # three Dense(16) → multi-param buckets for weights and biases,
    # plus singleton buckets from the output layer — exercises both
    # stack-axis and inner-axis sharding in one model
    net.add(nn.Dense(16, flatten=False), nn.Dense(16, flatten=False),
            nn.Dense(16, flatten=False), nn.Dense(4, flatten=False))
    net.initialize(init="xavier")
    net(x)
    return net


def _run(optname, oparams, zero, x, y, snap, steps=4,
         compute_dtype=None):
    """One training run on the dp8 mesh: ``zero=True`` is the ZeRO-1
    path, ``zero=False`` the replicated all-reduce path (``zero=0``:
    the exact pre-ZeRO program)."""
    net = _make_net(x)
    restore_params(net, snap)
    step = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), optname, dict(oparams),
        mesh=_mesh(), compute_dtype=compute_dtype, zero=int(zero))
    assert step.zero is zero
    losses = [float(step(x, y).asscalar()) for _ in range(steps)]
    return losses, snapshot_params(net), step


@pytest.fixture()
def _data():
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 16).astype(np.float32))
    y = nd.array(rng.randn(8, 4).astype(np.float32))
    snap = snapshot_params(_make_net(x))
    return x, y, snap


# ---------------------------------------------------------------------
# parity: ZeRO-1 vs the replicated path, every supported optimizer
# ---------------------------------------------------------------------
@pytest.mark.parametrize("optname,oparams", [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4}),
    ("rmsprop", {"learning_rate": 1e-3}),
    ("lamb", {"learning_rate": 1e-2, "wd": 1e-2}),
])
def test_zero_parity_all_optimizers(optname, oparams, _data):
    x, y, snap = _data
    lz, pz, _ = _run(optname, oparams, True, x, y, snap)
    lr, pr, _ = _run(optname, oparams, False, x, y, snap)
    np.testing.assert_allclose(lz, lr, rtol=1e-6, atol=1e-8)
    for a, b in zip(pz, pr):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("optname,oparams", [
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4}),
    ("lamb", {"learning_rate": 1e-2, "wd": 1e-2}),
])
def test_zero_parity_bf16_multi_precision(optname, oparams, _data):
    """bf16 compute + f32 master weights (the multi_precision recipe)
    under ZeRO: states stay f32, and sharding changes nothing beyond
    bf16 rounding.  The two programs reduce in different orders, so
    their f32 masters part by an ULP at the first step; a normalised
    update (adam's m/sqrt(v), LAMB's trust ratio over it) grows that,
    and once a master sits on the other side of a bf16 rounding
    boundary the next forward differs by 2^-8 of that weight.  Neither
    run is the true one, so each is judged against the float32 run of
    the same steps: the gap between the two is no larger than either's
    distance from it."""
    x, y, snap = _data
    lz, pz, _ = _run(optname, oparams, True, x, y, snap,
                     compute_dtype="bfloat16")
    lr, pr, _ = _run(optname, oparams, False, x, y, snap,
                     compute_dtype="bfloat16")
    l32, p32, _ = _run(optname, oparams, False, x, y, snap)

    def gap(a, b):
        return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))

    # the first loss comes from the same weights: only bf16 apart from
    # float32, and the same in both
    assert lz[0] == lr[0] and 0 < gap(lz[0], l32[0]) < 1e-2
    assert gap(lz, lr) <= min(gap(lz, l32), gap(lr, l32))
    for a, b, c in zip(pz, pr, p32):
        assert a.dtype == np.float32  # master weights stay f32
        assert gap(a, b) <= min(gap(a, c), gap(b, c)) + 1e-6, \
            (gap(a, b), gap(a, c), gap(b, c))
    # and bf16 stays bf16-close to float32 at all
    np.testing.assert_allclose(lz, l32, rtol=1e-2)


# ---------------------------------------------------------------------
# comm-layout smoke (tier-1): the compiled program itself proves the
# mechanism — asserted through mxtpu.analysis (ISSUE 6: one HLO
# parser in the tree) instead of regexing hlo_text
# ---------------------------------------------------------------------
def test_zero_comm_hlo_signature_and_parity(_data):
    """The acceptance shape of the tentpole, tier-1-safe: a dp8 step
    whose program contains reduce-scatter + all-gather and whose only
    all-reduces are scalar/small (loss, aux) — no full-gradient
    all-reduce — and which matches the replicated path step for step."""
    x, y, snap = _data
    lz, _, zstep = _run("adam", {"learning_rate": 1e-3}, True, x, y,
                        snap, steps=3)
    lr, _, rstep = _run("adam", {"learning_rate": 1e-3}, False, x, y,
                        snap, steps=3)
    np.testing.assert_allclose(lz, lr, rtol=1e-6, atol=1e-8)

    coll_z = zstep.program_summary(x, y)["collectives"]
    assert coll_z.get("reduce-scatter", {}).get("count", 0) > 0
    assert coll_z.get("all-gather", {}).get("count", 0) > 0
    # every gradient bucket in this net is > 16 elements; any surviving
    # all-reduce that big would mean a gradient bypassed the scatter
    big = coll_z.get("all-reduce", {}).get("max_elems", 0)
    assert big <= 16, \
        f"full-tensor all-reduce leaked into ZeRO HLO: {big} elems"

    # zero=0 restores the exact pre-ZeRO program shape: gradient
    # all-reduce, no scatter/gather collectives
    coll_r = rstep.program_summary(x, y)["collectives"]
    assert "reduce-scatter" not in coll_r
    assert coll_r.get("all-reduce", {}).get("count", 0) > 0


# ---------------------------------------------------------------------
# memory: the dp× saving, measured and planned
# ---------------------------------------------------------------------
def test_zero_opt_state_bytes_sharded(_data):
    """Per-device optimizer-state bytes under ZeRO must be ≈
    replicated/dp (× ≤1.15 padding allowance) and exactly match the
    plan_zero_buckets geometry."""
    x, y, snap = _data
    _, _, zstep = _run("adam", {"learning_rate": 1e-3}, True, x, y,
                       snap, steps=1)
    _, _, rstep = _run("adam", {"learning_rate": 1e-3}, False, x, y,
                       snap, steps=1)
    zsum = zstep.memory_summary(x, y)
    rsum = rstep.memory_summary(x, y)
    z = zsum["zero"]["opt_state_bytes"]
    r = rsum["zero"]["opt_state_bytes"]
    assert z <= r / 8 * 1.15, (z, r)
    # adam: two f32 leaves (m, v) per bucket, each 1/8 of the padded
    # stacked array — the plan_zero_buckets oracle memflow carries
    assert z == zsum["zero"]["planned_shard_bytes"], zsum["zero"]
    assert not [h for h in zsum["hazards"]
                if h["rule"] == "zero-replication"], zsum["hazards"]
    dec = zsum["programs"]["train_step"]
    assert dec["opt_state"] == z
    assert dec["peak_hbm"] >= 0


def test_zero_bucket_axis_geometry():
    """plan_zero_buckets picks the axis that kills padding: a
    BERT-style embedding singleton bucket must shard an inner axis
    pad-free instead of wasting 7/8 of a stack-axis row, and the
    planned footprint for BERT-Large-like sigs stays within the
    ≤ replicated/dp × 1.15 criterion."""
    sigs = ([((30522, 1024), "float32")] * 2        # embeddings
            + [((1024, 1024), "float32")] * 96      # attention proj
            + [((4096, 1024), "float32")] * 24      # FFN in
            + [((1024, 4096), "float32")] * 24      # FFN out
            + [((1024,), "float32")] * 146)         # biases/LN
    buckets = plan_zero_buckets(sigs, 8)
    by_shape = {b["shape"]: b for b in buckets}
    emb = by_shape[(30522, 1024)]
    assert emb["axis"] != 0 and emb["pad"] == 0, emb
    total = sum(b["param_bytes"] for b in buckets)
    per_dev = sum(b["padded_bytes"] // 8 for b in buckets)
    assert per_dev <= total / 8 * 1.15, (per_dev, total)
    # LAMB pins every bucket to the stack axis so per-row trust-ratio
    # norms stay device-local — padding is the price, locality the pin
    for b in plan_zero_buckets(sigs, 8, stack_axis_only=True):
        assert b["axis"] == 0


def test_zero_lamb_buckets_pinned_to_stack_axis(_data):
    """The built LAMB step must actually use the stack-axis-only plan
    (a non-stack shard would split trust-ratio norms across devices —
    silently wrong, which is why this is pinned by a test)."""
    x, y, snap = _data
    _, _, zstep = _run("lamb", {"learning_rate": 1e-2}, True, x, y,
                       snap, steps=1)
    assert all(b["axis"] == 0 for b in zstep._zero_buckets)
    # t rides per stacked row: one rank-1 int32 leaf per bucket
    for b, st in zip(zstep._zero_buckets, zstep._opt_state):
        assert st[2].dtype == np.int32
        assert st[2].shape == (b["padded_shape"][0],)


# ---------------------------------------------------------------------
# checkpoints: zero ↔ replicated, both directions
# ---------------------------------------------------------------------
@pytest.mark.parametrize("optname,oparams", [
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4}),
    ("lamb", {"learning_rate": 1e-2, "wd": 1e-2}),
])
def test_zero_checkpoint_interchangeable(optname, oparams, tmp_path,
                                         _data):
    """save_states always writes the canonical per-parameter layout,
    so a ZeRO checkpoint resumes on a replicated step (and vice versa)
    with identical continued losses."""
    x, y, snap = _data
    fname = str(tmp_path / "opt.states")

    # zero-save → replicated-load (and → fresh-zero-load)
    lz, pz, zstep = _run(optname, oparams, True, x, y, snap, steps=3)
    zstep.save_states(fname)
    cont_z = [float(zstep(x, y).asscalar()) for _ in range(2)]

    net_r = _make_net(x)
    restore_params(net_r, pz)
    rstep = parallel.build_train_step(
        net_r, lambda p, t: ((p - t) ** 2).mean(), optname,
        dict(oparams), mesh=_mesh(), zero=0)
    assert not rstep.zero
    rstep.load_states(fname, x_example=x)
    cont_r = [float(rstep(x, y).asscalar()) for _ in range(2)]
    np.testing.assert_allclose(cont_z, cont_r, rtol=1e-6, atol=1e-8)

    # replicated-save → zero-load
    rstep.save_states(fname)
    snap_r = snapshot_params(net_r)
    cont_r2 = [float(rstep(x, y).asscalar()) for _ in range(2)]

    net_z = _make_net(x)
    restore_params(net_z, snap_r)
    zstep2 = parallel.build_train_step(
        net_z, lambda p, t: ((p - t) ** 2).mean(), optname,
        dict(oparams), mesh=_mesh(), zero=1)
    assert zstep2.zero
    zstep2.load_states(fname, x_example=x)
    cont_z2 = [float(zstep2(x, y).asscalar()) for _ in range(2)]
    np.testing.assert_allclose(cont_r2, cont_z2, rtol=1e-6, atol=1e-8)


# ---------------------------------------------------------------------
# contract guards
# ---------------------------------------------------------------------
def test_zero_batch_must_divide_dp(_data):
    x, y, snap = _data
    net = _make_net(x)
    restore_params(net, snap)
    step = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), "adam",
        {"learning_rate": 1e-3}, mesh=_mesh(), zero=1)
    assert step.zero
    rng = np.random.RandomState(1)
    x6 = nd.array(rng.randn(6, 16).astype(np.float32))
    y6 = nd.array(rng.randn(6, 4).astype(np.float32))
    with pytest.raises(MXNetError, match="divisible"):
        step(x6, y6)


def test_zero_gating(_data):
    x, _, snap = _data
    net = _make_net(x)
    restore_params(net, snap)
    loss = lambda p, t: ((p - t) ** 2).mean()  # noqa: E731
    # no mesh: auto-off; forcing raises
    assert not parallel.build_train_step(net, loss, "adam").zero
    with pytest.raises(MXNetError, match="mesh"):
        parallel.build_train_step(net, loss, "adam", zero=1)
    # dp mesh: auto-on; zero=0 wins over the default
    assert parallel.build_train_step(net, loss, "adam",
                                     mesh=_mesh()).zero
    assert not parallel.build_train_step(net, loss, "adam",
                                         mesh=_mesh(), zero=0).zero
    # tensor-parallel param_spec_fn: ZeRO steps aside
    assert not parallel.build_train_step(
        net, loss, "adam", mesh=_mesh(),
        param_spec_fn=lambda p: None).zero


@pytest.mark.parametrize("precision", [
    {}, {"compute_dtype": "bfloat16"}, {"amp": True},
], ids=["float32", "bfloat16", "amp"])
@pytest.mark.parametrize("zero", [True, False],
                         ids=["zero", "unsharded"])
def test_zero_run_steps_scan_parity(zero, precision, _data):
    """The scanned multi-step path threads the state (sharded stacks
    under ZeRO-1, per-parameter tuples on one device; the loss scaler
    under amp) through lax.scan: ``run_steps(k)`` walks the trajectory
    of ``k`` calls, and the ZeRO scan that of the replicated scan."""
    x, y, snap = _data
    k = 6

    def build(optname, oparams, **kw):
        net = _make_net(x)
        restore_params(net, snap)
        return parallel.build_train_step(
            net, lambda p, t: ((p - t) ** 2).mean(), optname, oparams,
            **precision, **kw), net

    # momentum sgd: no bias correction folded into the lr, which
    # run_steps samples once a call
    sgd = ("sgd", {"learning_rate": 0.05, "momentum": 0.9})
    where = {"mesh": _mesh(), "zero": 1} if zero else {}
    scan, net_s = build(*sgd, **where)
    assert scan.zero is zero
    ls = scan.run_steps(x, y, steps=k, reuse_batch=True).asnumpy()
    calls, net_c = build(*sgd, **where)
    lc = [float(calls(x, y).asscalar()) for _ in range(k)]
    assert ls.shape == (k,) and ls[-1] < ls[0]
    # the scan body and the one-step program round bf16 alike only up
    # to the compiler's fusion choices
    tol = {"rtol": 1e-6, "atol": 1e-7} if not precision \
        else {"rtol": 2e-2, "atol": 2e-2}
    np.testing.assert_allclose(ls, lc, **tol)
    for a, b in zip(snapshot_params(net_s), snapshot_params(net_c)):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), **tol)
    if precision.get("amp"):
        assert scan.amp_stats() == calls.amp_stats()
        assert scan.amp_stats()["skipped_steps"] == 0
    if not zero:
        return
    adam = ("adam", {"learning_rate": 3e-3})
    zstep, _ = build(*adam, mesh=_mesh(), zero=1)
    rstep, _ = build(*adam, mesh=_mesh(), zero=0)
    lz = zstep.run_steps(x, y, steps=k, reuse_batch=True).asnumpy()
    lr = rstep.run_steps(x, y, steps=k, reuse_batch=True).asnumpy()
    assert lz.shape == (k,) and lz[-1] < lz[0]
    np.testing.assert_allclose(lz, lr, **tol)
    mem = zstep.last_memory_analysis()
    if mem is not None:  # backend reports on CPU/TPU AOT programs
        assert mem["hbm_peak"] >= 0
