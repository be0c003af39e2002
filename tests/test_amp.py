"""mxtpu.amp — policy-driven bf16 autocast with fp32 master weights,
dynamic loss scaling, and the bf16 ZeRO gradient exchange.

Parity tests run the SAME initial parameters through an AMP train
step and an f32 train step and require the loss trajectories to agree
to bf16 rounding; the contract tests pin the mechanics the ledgers
rely on (masters stay f32, params ride bf16, ``MXTPU_AMP=0`` produces
a byte-identical program, scaler state rides checkpoints)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import amp, nd, parallel
from mxtpu.gluon import nn
from mxtpu.parallel import restore_params, snapshot_params
from mxtpu.symbol import _is_aux_name


def _mesh(n=8):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]), ("dp",))


def _dense_net(x, batchnorm=False):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, flatten=False))
    if batchnorm:
        net.add(nn.BatchNorm(axis=-1))
    net.add(nn.Dense(4, flatten=False))
    net.initialize(init="xavier")
    net(x)
    return net


def _mse(p, t):
    return ((p - t) ** 2).mean()


# ----------------------------------------------------------------------
# loss scaler units
# ----------------------------------------------------------------------
def test_scaler_grow_backoff_skip():
    st = amp.scaler_init(1024.0)
    assert float(st[0]) == 1024.0
    # finite steps below the window: scale holds, good_steps counts up
    st = amp.scaler_update(st, True, window=3)
    st = amp.scaler_update(st, True, window=3)
    assert float(st[0]) == 1024.0 and int(st[1]) == 2
    # window reached: grow x2, counter resets
    st = amp.scaler_update(st, True, window=3)
    assert float(st[0]) == 2048.0 and int(st[1]) == 0
    # non-finite: halve, count a skip, reset the streak
    st = amp.scaler_update(st, True, window=3)
    st = amp.scaler_update(st, False, window=3)
    assert float(st[0]) == 1024.0
    assert int(st[1]) == 0 and int(st[2]) == 1


def test_scaler_cap_and_floor():
    st = amp.scaler_init(2.0 ** 24)
    st = amp.scaler_update(st, True, window=1)
    assert float(st[0]) == 2.0 ** 24  # capped
    st = amp.scaler_init(1.0)
    st = amp.scaler_update(st, False, window=1)
    assert float(st[0]) == 1.0  # floored


def test_all_finite():
    good = (jnp.ones(3), jnp.zeros((2, 2), jnp.bfloat16))
    bad = (jnp.ones(3), jnp.asarray([1.0, np.inf]))
    assert bool(amp.all_finite(good))
    assert not bool(amp.all_finite(bad))
    # integer leaves never poison the verdict
    assert bool(amp.all_finite((jnp.arange(3),)))


def test_resolve_kill_switch_precedence(monkeypatch):
    monkeypatch.setenv("MXTPU_AMP", "0")
    assert amp.resolve(True) is False  # env kill beats the argument
    monkeypatch.setenv("MXTPU_AMP", "1")
    assert amp.resolve(None) is True
    monkeypatch.delenv("MXTPU_AMP")
    assert amp.resolve(None) is False
    assert amp.resolve(True) is True


# ----------------------------------------------------------------------
# master weights / parameter storage
# ----------------------------------------------------------------------
def test_masters_f32_params_bf16():
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 8).astype(np.float32))
    y = nd.array(rng.randn(4, 4).astype(np.float32))
    net = _dense_net(x, batchnorm=True)
    step = parallel.build_train_step(net, _mse, "adam",
                                     {"learning_rate": 1e-3}, amp=True)
    step(x, y)
    for p in net.collect_params().values():
        want = jnp.float32 if _is_aux_name(p.name) else jnp.bfloat16
        assert p.data().dtype == want, p.name
    # every float optimizer-state leaf (momenta + the f32 master the
    # multi-precision rule seeds) stays full precision
    for leaf in jax.tree_util.tree_leaves(step._opt_state):
        dt = jnp.asarray(leaf).dtype
        if jnp.issubdtype(dt, jnp.floating):
            assert dt == jnp.float32
    stats = step.amp_stats()
    assert stats["skipped_steps"] == 0 and stats["loss_scale"] >= 1.0


@pytest.mark.parametrize("entry", ["call", "run_steps"])
@pytest.mark.parametrize("zero", [False, True],
                         ids=["unsharded", "zero"])
def test_nonfinite_batch_skips_update(zero, entry, monkeypatch):
    """A non-finite batch costs the step, never the weights: params
    and state are kept and the scale backs off — on every shard under
    ZeRO-1, where ONE shard's row is bad and all must agree to skip."""
    monkeypatch.setenv("MXTPU_AMP_LOSS_SCALE", "1024")
    rng = np.random.RandomState(1)
    x = nd.array(rng.randn(8, 8).astype(np.float32))
    y = nd.array(rng.randn(8, 4).astype(np.float32))
    net = _dense_net(x)
    where = {"mesh": _mesh(), "zero": 1} if zero else {}
    step = parallel.build_train_step(net, _mse, "sgd",
                                     {"learning_rate": 0.1}, amp=True,
                                     **where)
    assert step.zero is zero

    def go(xb, yb, k):
        if entry == "call":
            for _ in range(k):
                step(xb, yb)
        else:
            step.run_steps(xb, yb, steps=k, reuse_batch=True)

    go(x, y, 1)
    before = snapshot_params(net)
    state = jax.tree_util.tree_map(np.asarray, step._opt_state)
    bad = y.asnumpy().copy()
    bad[3] = np.inf          # one row: one shard's under dp8
    go(x, nd.array(bad), 2)
    for b, a in zip(before, snapshot_params(net)):
        np.testing.assert_array_equal(b, a)
    jax.tree_util.tree_map(np.testing.assert_array_equal, state,
                           jax.tree_util.tree_map(np.asarray,
                                                  step._opt_state))
    stats = step.amp_stats()
    assert stats["skipped_steps"] == 2
    assert stats["loss_scale"] == 256.0  # halved on each bad step
    # and the step still trains afterwards
    go(x, y, 1)
    assert step.amp_stats()["skipped_steps"] == 2
    assert any((b != a).any()
               for b, a in zip(before, snapshot_params(net)))


# ----------------------------------------------------------------------
# AMP vs f32 parity (the tentpole's correctness claim)
# ----------------------------------------------------------------------
def _parity_run(net_fn, x, y, loss, opt, oparams, steps=3, **kw):
    losses = {}
    for mode in ("f32", "amp"):
        net = net_fn()
        net(x)  # materialize deferred shapes before snapshot/restore
        if "snap" not in losses:
            losses["snap"] = snapshot_params(net)
        restore_params(net, losses["snap"])
        step = parallel.build_train_step(
            net, loss, opt, dict(oparams),
            amp=(mode == "amp") or None, **kw)
        losses[mode] = [float(step(x, y).asscalar())
                        for _ in range(steps)]
    np.testing.assert_allclose(losses["amp"], losses["f32"],
                               rtol=3e-2, atol=1e-2)
    return losses


def test_amp_parity_bert():
    from mxtpu.models.transformer import BERTModel
    from mxtpu.gluon import loss as gloss
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, 128, (4, 8)).astype(np.float32))
    ce = gloss.SoftmaxCrossEntropyLoss()

    def loss(pred, t):
        return ce(pred.reshape((-1, 128)), t.reshape((-1,)))

    def net_fn():
        net = BERTModel(128, 32, 64, 1, 1, max_length=16, dropout=0.0)
        net.initialize(init="xavier")
        return net

    _parity_run(net_fn, x, x, loss, "adam", {"learning_rate": 1e-3},
                cast_batch=False)


def test_amp_parity_resnet():
    # a compact conv-BN-dense stack stands in for resnet18 here: it
    # exercises the same AMP paths (amp.conv_general's custom VJP,
    # BatchNorm aux exemption, FullyConnected) at a fraction of the
    # double compile — the full resnet18 AMP lowering is pinned by the
    # resnet18_amp ledger / hlocheck target instead
    from mxtpu.gluon import loss as gloss
    from mxtpu.gluon import nn
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(2, 3, 16, 16).astype(np.float32))
    y = nd.array(rng.randint(0, 10, (2,)).astype(np.float32))

    def net_fn():
        net = nn.HybridSequential()
        net.add(nn.Conv2D(16, 3, padding=1),
                nn.BatchNorm(), nn.Activation("relu"),
                nn.Conv2D(32, 3, strides=2, padding=1),
                nn.BatchNorm(), nn.Activation("relu"),
                nn.GlobalAvgPool2D(), nn.Dense(10))
        net.initialize(init="xavier")
        return net

    _parity_run(net_fn, x, y, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                {"learning_rate": 0.05, "momentum": 0.9})


def test_amp_parity_transformer():
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.gluon import loss as gloss
    from mxtpu.models.transformer import TransformerModel
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, 128, (2, 16)).astype(np.float32))
    y = nd.array(rng.randint(0, 128, (2, 8)).astype(np.float32))
    ce = gloss.SoftmaxCrossEntropyLoss()

    def loss(pred, t):
        return ce(pred.reshape((-1, 128)), t.reshape((-1,)))

    class MTWrap(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.model = TransformerModel(
                128, units=32, hidden_size=64, num_layers=1,
                num_heads=2, max_length=32, dropout=0.0)

        def hybrid_forward(self, F, xx):
            src = F.slice_axis(xx, axis=1, begin=0, end=8)
            tgt = F.slice_axis(xx, axis=1, begin=8, end=None)
            return self.model(src, tgt)

    def net_fn():
        net = MTWrap()
        net.initialize(init="xavier")
        return net

    _parity_run(net_fn, x, y, loss, "adam", {"learning_rate": 1e-4},
                cast_batch=False)


# ----------------------------------------------------------------------
# kill switch / program identity
# ----------------------------------------------------------------------
def test_kill_switch_bit_identical_program(monkeypatch):
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 8).astype(np.float32))
    y = nd.array(rng.randn(4, 4).astype(np.float32))

    def lowered(amp_flag):
        net = _dense_net(x)
        step = parallel.build_train_step(
            net, _mse, "adam", {"learning_rate": 1e-3}, amp=amp_flag)
        return step.lowered_hlo_text(x, y)

    monkeypatch.setenv("MXTPU_AMP", "0")
    killed = lowered(True)   # amp requested, env kills it
    monkeypatch.delenv("MXTPU_AMP")
    off = lowered(None)
    assert killed == off     # byte-identical pre-opt program
    on = lowered(True)
    assert on != off and "bf16" in on and "bf16" not in off


def test_zero_reduce_scatter_rides_bf16():
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 8).astype(np.float32))
    y = nd.array(rng.randn(8, 4).astype(np.float32))

    def rs_lines(amp_flag):
        net = _dense_net(x)
        step = parallel.build_train_step(
            net, _mse, "adam", {"learning_rate": 1e-3},
            mesh=_mesh(), zero=1, amp=amp_flag)
        assert step.zero
        text = step.lowered_hlo_text(x, y)
        return [ln for ln in text.splitlines()
                if "reduce-scatter(" in ln]

    amp_rs = rs_lines(True)
    f32_rs = rs_lines(None)
    assert amp_rs and f32_rs
    # every AMP gradient exchange rides bf16; the f32 path none
    assert all("bf16[" in ln for ln in amp_rs)
    assert all("bf16[" not in ln for ln in f32_rs)


@pytest.mark.parametrize("entry", ["call", "run_steps"])
def test_zero_amp_parity(entry):
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 8).astype(np.float32))
    y = nd.array(rng.randn(8, 4).astype(np.float32))

    def run(amp_flag, snap):
        net = _dense_net(x)
        restore_params(net, snap)
        step = parallel.build_train_step(
            net, _mse, "adam", {"learning_rate": 1e-3},
            mesh=_mesh(), zero=1, amp=amp_flag)
        if entry == "run_steps":
            return step.run_steps(x, y, steps=3,
                                  reuse_batch=True).asnumpy()
        return [float(step(x, y).asscalar()) for _ in range(3)]

    snap = snapshot_params(_dense_net(x))
    np.testing.assert_allclose(run(True, snap), run(None, snap),
                               rtol=3e-2, atol=1e-2)


# ----------------------------------------------------------------------
# checkpointing
# ----------------------------------------------------------------------
def test_scaler_state_rides_checkpoint(tmp_path, monkeypatch):
    # window=1 so the scale moves every step — a fresh scaler would be
    # observably different after restore
    monkeypatch.setenv("MXTPU_AMP_SCALE_WINDOW", "1")
    monkeypatch.setenv("MXTPU_AMP_LOSS_SCALE", "256")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 8).astype(np.float32))
    y = nd.array(rng.randn(4, 4).astype(np.float32))

    def make():
        net = _dense_net(x)
        return net, parallel.build_train_step(
            net, _mse, "adam", {"learning_rate": 1e-3}, amp=True)

    net, step = make()
    snap = snapshot_params(net)
    for _ in range(2):
        step(x, y)
    assert step.amp_stats()["loss_scale"] == 1024.0  # 256 -> 512 -> 1024
    fname = str(tmp_path / "amp.states")
    step.save_states(fname)

    net2, step2 = make()
    restore_params(net2, snap)
    step2.load_states(fname, x_example=x)
    assert step2.amp_stats() == step.amp_stats()
    # the restored run continues the schedule, not a fresh scaler
    step2(x, y)
    assert step2.amp_stats()["loss_scale"] == 2048.0
