"""The compiled train step's optimizer update against a plain
reference: the same loss differentiated by ``jax.grad`` and the
optimizer's rule applied leaf by leaf, in a loop written here.  An
unsharded step updates one parameter a bucket and stacks nothing (the
(shape, dtype)-stacked "batched" update of ISSUE 2 went with its knob
in PR 31; the file keeps its name); only ZeRO-1, whose state lives
stacked, still buckets — the partition tests at the end hold both to
that.  Also covers the LAMB optimizer end to end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import autograd, gluon, nd, obs, optimizer, parallel, profiler
from mxtpu.gluon import nn
from mxtpu.optimizer.functional import adam_bias_correction, opt_rule
from mxtpu.parallel import snapshot_params, restore_params


def _make_net(x):
    net = nn.HybridSequential()
    # three Dense(16): repeated (shape, dtype) signatures — what a
    # stacking partition would group (ZeRO-1: a 3-row bucket of
    # weights, one of biases, singletons from the out layer)
    net.add(nn.Dense(16, flatten=False), nn.Dense(16, flatten=False),
            nn.Dense(16, flatten=False), nn.Dense(4, flatten=False))
    net.initialize(init="xavier")
    net(x)
    return net


def _run(optname, oparams, x, y, snap, steps=5, compute_dtype=None):
    """The compiled step, unsharded: one parameter an update."""
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
def test_step_matches_per_leaf_elementwise_rules(optname, oparams,
                                                 _data):
    """Elementwise rules: the compiled step is the per-leaf loop's
    arithmetic — what is left between two compiled programs is the
    compiler's fusion order, an ULP."""
    x, y, snap = _data
    la, pa = _run(optname, oparams, x, y, snap)
    lb, pb = _reference(optname, oparams, x, y, snap)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-7)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_lamb_step_matches_per_leaf(_data):
    x, y, snap = _data
    oparams = {"learning_rate": 1e-2, "wd": 1e-2}
    la, pa = _run("lamb", oparams, x, y, snap)
    lb, pb = _reference("lamb", oparams, x, y, snap)
    # trust-ratio norms reduce in the compiler's order: per-dtype
    # tolerance, not bitwise
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-7)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("optname,oparams", [
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4}),
    ("lamb", {"learning_rate": 1e-2, "wd": 1e-2}),
])
def test_multi_precision_bf16_matches_per_leaf(optname, oparams, _data):
    """compute_dtype='bfloat16' (the multi_precision recipe: bf16
    fwd/bwd, f32 master weights + optimizer state) against the
    per-leaf loop."""
    x, y, snap = _data
    la, pa = _run(optname, oparams, x, y, snap,
                  compute_dtype="bfloat16")
    lb, pb = _reference(optname, oparams, x, y, snap,
                        compute_dtype="bfloat16")
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-7)
    for a, b in zip(pa, pb):
        assert a.dtype == np.float32  # master weights stay f32
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_run_steps_scan_path(_data):
    """The scanned multi-step path threads the update through
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


def test_save_load_states_roundtrip(tmp_path, _data):
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


# ------------------------------------------------------- the partition
def _mse(p, t):
    return ((p - t) ** 2).mean()


def _step_of(x, y, snap, **kw):
    net = _make_net(x)
    restore_params(net, snap)
    return parallel.build_train_step(net, _mse, "adam",
                                     {"learning_rate": 1e-3}, **kw)


def _built(x, y, snap, **kw):
    """A step over ``_make_net`` built (by its first call) with the
    chrome-trace profiler on: ``(step, args of its compile region)``."""
    step = _step_of(x, y, snap, **kw)
    profiler.set_state("run")
    try:
        step(x, y)
        (built,) = [e for e in profiler.events()
                    if e["name"] == obs.SPAN_COMPILE]
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    return step, built["args"]


def _dp8():
    return jax.sharding.Mesh(np.array(jax.devices()[:8]), ("dp",))


@pytest.mark.parametrize("mesh", [None, "dp8"],
                         ids=["one-device", "replicated-dp8"])
def test_unsharded_partition_is_one_parameter_a_bucket(mesh, _data):
    """Without ZeRO — one device, or a replicated dp mesh — every
    trainable parameter is its own unstacked bucket although the net
    repeats shapes, and the ``compile`` region says so."""
    x, y, snap = _data
    step, args = _built(x, y, snap,
                        **({"mesh": _dp8(), "zero": 0} if mesh else {}))
    buckets, _, _ = step._partition()
    n = len(step._train_idx)
    assert n == 8 and len({s for _, s, _ in step.param_sigs()}) < n
    assert [b["jidx"] for b in buckets] == [[j] for j in range(n)]
    assert not any(b["stacked"] for b in buckets)
    assert (args["groups"], args["stacked_groups"]) == (n, 0)


def test_zero1_partition_keeps_its_stacked_buckets(_data):
    x, y, snap = _data
    step, args = _built(x, y, snap, mesh=_dp8(), zero=1)
    buckets, _, _ = step._partition()
    assert all(b["stacked"] for b in buckets)
    assert max(len(b["jidx"]) for b in buckets) == 3
    assert args["groups"] == args["stacked_groups"] \
        == len(step._zero_buckets) < len(step._train_idx)


def _packing_ops(text):
    """``concatenate`` instructions under the optimizer's scope: what
    ``jnp.stack`` of a bucket's rows lowers to."""
    return [ln for ln in text.splitlines()
            if " concatenate(" in ln and "train/optimizer" in ln]


def test_only_the_zero_step_packs_in_its_program(_data):
    """The program-level witness: an unsharded step's lowered text
    stacks nothing under ``train/optimizer``; ZeRO-1's still stacks
    the weights and the gradients of each bucket of several rows (a
    stack of one is a reshape)."""
    x, y, snap = _data
    plain = _step_of(x, y, snap)
    assert _packing_ops(plain.lowered_hlo_text(x, y)) == []
    zero = _step_of(x, y, snap, mesh=_dp8(), zero=1)
    assert len(_packing_ops(zero.lowered_hlo_text(x, y))) == 2 * sum(
        len(b["jidx"]) > 1 for b in zero._zero_buckets) == 4


def test_deleted_knob_changes_no_byte_of_the_program(_data, monkeypatch):
    """MXTPU_BATCHED_OPT is gone, not hidden: set either way in the
    environment, the step lowers to the same text."""
    x, y, snap = _data
    texts = []
    for value in (None, "0", "1"):
        if value is None:
            monkeypatch.delenv("MXTPU_BATCHED_OPT", raising=False)
        else:
            monkeypatch.setenv("MXTPU_BATCHED_OPT", value)
        texts.append(_step_of(x, y, snap).lowered_hlo_text(x, y))
    assert texts[0] == texts[1] == texts[2]
