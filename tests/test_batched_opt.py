"""Batched (shape/dtype-bucketed, stacked) optimizer update in the
compiled train step (ISSUE 2 tentpole 2) against a plain reference:
the same loss differentiated by ``jax.grad`` and the optimizer's rule
applied leaf by leaf, in a loop written here.  Every rule is
elementwise in (w, g, state), so a stack updates as its rows would
alone, up to the compiler's choice of fusion (an ULP); LAMB's per-slice
trust-ratio norms may differ by reduction order as well.
Also covers the new LAMB optimizer end to end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import autograd, gluon, nd, optimizer, parallel
from mxtpu.gluon import nn
from mxtpu.optimizer.functional import adam_bias_correction, opt_rule
from mxtpu.parallel import snapshot_params, restore_params


def _make_net(x):
    net = nn.HybridSequential()
    # three Dense(16) → a 3-param bucket for weights and one for
    # biases, plus singleton buckets from the in/out layers
    net.add(nn.Dense(16, flatten=False), nn.Dense(16, flatten=False),
            nn.Dense(16, flatten=False), nn.Dense(4, flatten=False))
    net.initialize(init="xavier")
    net(x)
    return net


def _run(optname, oparams, x, y, snap, steps=5, compute_dtype=None):
    """The compiled step: its (shape, dtype) groups updated stacked."""
    net = _make_net(x)
    restore_params(net, snap)
    step = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), optname, dict(oparams),
        compute_dtype=compute_dtype)
    losses = [float(step(x, y).asscalar()) for _ in range(steps)]
    return losses, snapshot_params(net)


def _reference(optname, oparams, x, y, snap, steps=5,
               compute_dtype=None):
    """The plain per-leaf loop: ``_make_net`` as four ``x W^T + b``,
    the loss's gradient from ``jax.grad``, and one application of the
    optimizer's rule per leaf and step — no groups, no stacks.
    ``compute_dtype`` is the step's recipe: weights and batch cast on
    the way in, the loss and the f32 masters' update in float32."""
    opt = optimizer.create(optname, **oparams)
    init, update = opt_rule(opt)
    ws = [jnp.asarray(a) for a in snap]
    states = [init(w) for w in ws]
    xr, yr = jnp.asarray(x.asnumpy()), jnp.asarray(y.asnumpy())

    @jax.jit
    @jax.value_and_grad
    def loss_and_grads(ws):
        h = xr
        if compute_dtype is not None:
            ws = [w.astype(compute_dtype) for w in ws]
            h = h.astype(compute_dtype)
        for w, b in zip(ws[0::2], ws[1::2]):
            h = jnp.matmul(h, w.T) + b
        return jnp.mean(((h - yr) ** 2).astype(jnp.float32))

    losses = []
    for t in range(1, steps + 1):
        loss, grads = loss_and_grads(ws)
        losses.append(float(loss))
        # the raw adam rule leaves bias correction to the lr
        lr = jnp.float32(opt.learning_rate * adam_bias_correction(opt, t))
        for j, g in enumerate(grads):
            ws[j], states[j] = update(ws[j], g, states[j], lr,
                                      jnp.float32(opt.wd))
    return losses, [np.asarray(w) for w in ws]


@pytest.fixture()
def _data():
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 16).astype(np.float32))
    y = nd.array(rng.randn(8, 4).astype(np.float32))
    snap = snapshot_params(_make_net(x))
    return x, y, snap


@pytest.mark.parametrize("optname,oparams", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.05}),
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4}),
])
@pytest.mark.parametrize("grouped", ["1", "0"],
                         ids=["grouped", "per-param"])
def test_batched_bit_identical_elementwise_rules(optname, oparams,
                                                 grouped, _data,
                                                 monkeypatch):
    """Elementwise rules: a stacked bucket is its rows' updates, bit
    for bit in the arithmetic — what is left between two compiled
    programs is the compiler's fusion order, an ULP.  Both settings of
    the partition's one switch (MXTPU_BATCHED_OPT: (shape, dtype)
    groups, or every group one parameter) are held to the reference,
    not to each other."""
    monkeypatch.setenv("MXTPU_BATCHED_OPT", grouped)
    x, y, snap = _data
    la, pa = _run(optname, oparams, x, y, snap)
    lb, pb = _reference(optname, oparams, x, y, snap)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-7)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_batched_lamb_matches_per_param(_data):
    x, y, snap = _data
    oparams = {"learning_rate": 1e-2, "wd": 1e-2}
    la, pa = _run("lamb", oparams, x, y, snap)
    lb, pb = _reference("lamb", oparams, x, y, snap)
    # trust-ratio norms reduce in a different order when stacked:
    # per-dtype tolerance, not bitwise
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-7)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("optname,oparams", [
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4}),
    ("lamb", {"learning_rate": 1e-2, "wd": 1e-2}),
])
def test_batched_multi_precision_bf16(optname, oparams, _data):
    """compute_dtype='bfloat16' (the multi_precision recipe: bf16
    fwd/bwd, f32 master weights + optimizer state) batched vs
    per-param."""
    x, y, snap = _data
    la, pa = _run(optname, oparams, x, y, snap,
                  compute_dtype="bfloat16")
    lb, pb = _reference(optname, oparams, x, y, snap,
                        compute_dtype="bfloat16")
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-7)
    for a, b in zip(pa, pb):
        assert a.dtype == np.float32  # master weights stay f32
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_batched_run_steps_scan_path(_data):
    """The scanned multi-step path threads the bucketed update through
    lax.scan and still converges."""
    x, y, snap = _data
    net = _make_net(x)
    restore_params(net, snap)
    step = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), "adam",
        {"learning_rate": 3e-3})
    losses = step.run_steps(x, y, steps=12, reuse_batch=True)
    ls = np.asarray(losses.asnumpy())
    assert ls.shape == (12,)
    assert ls[-1] < ls[0], ls


def test_batched_save_load_states_roundtrip(tmp_path, _data):
    x, y, snap = _data
    net = _make_net(x)
    restore_params(net, snap)
    step = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), "lamb",
        {"learning_rate": 1e-2})
    for _ in range(3):
        step(x, y)
    fname = str(tmp_path / "opt.states")
    step.save_states(fname)
    step.load_states(fname)
    l4 = float(step(x, y).asscalar())
    assert np.isfinite(l4)


def test_lamb_eager_trainer_converges(_data):
    """The eager gluon.Trainer path of the new LAMB optimizer."""
    x, y, snap = _data
    net = _make_net(x)
    restore_params(net, snap)
    tr = gluon.Trainer(net.collect_params(), "lamb",
                       {"learning_rate": 5e-3})
    losses = []
    for _ in range(20):
        with autograd.record():
            loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        tr.step(8)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_lamb_trust_ratio_scale_invariance():
    """LAMB's defining property: scaling the gradient does not change
    the step (trust ratio renormalizes) — exact up to the epsilon term
    in m̂/(√v̂+ε), hence the loose tolerance."""
    rng = np.random.RandomState(3)
    w = rng.randn(32, 32).astype(np.float32)
    g = rng.randn(32, 32).astype(np.float32)
    outs = []
    for scale in (1.0, 100.0):
        wn, m, v = nd.lamb_update(
            nd.array(w), nd.array(g * scale), nd.array(np.zeros_like(w)),
            nd.array(np.zeros_like(w)), nd.array(np.asarray(1, np.int32)),
            lr=0.1, wd=0.0)
        outs.append(np.asarray(wn.asnumpy()))
    np.testing.assert_allclose(outs[0], outs[1], rtol=5e-3, atol=1e-3)
    # and the update actually moved the weights
    assert np.abs(outs[0] - w).max() > 1e-3
