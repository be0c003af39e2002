"""Pallas kernels vs lax references (SURVEY §7 M6; the
check_consistency discipline applied to the kernel tier).  On CPU the
kernels run in interpreter mode via MXTPU_PALLAS=interpret."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.kernels import (layer_norm, flash_attention)
from mxtpu.kernels.layer_norm import (layer_norm_reference,
                                      _layer_norm_pallas)
from mxtpu.kernels.flash_attention import (attention_reference,
                                           _flash_attention_pallas)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    # force the blockwise backward kernels (auto mode would pick the
    # AD-through-reference path at these small test shapes)
    monkeypatch.setenv("MXTPU_FLASH_BWD", "pallas")


def test_layer_norm_forward_parity():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 64).astype(np.float32))
    g = jnp.asarray(rng.uniform(0.5, 1.5, 64).astype(np.float32))
    b = jnp.asarray(rng.randn(64).astype(np.float32))
    got = _layer_norm_pallas(x, g, b, 1e-5)
    ref = layer_norm_reference(x, g.reshape(1, -1), b.reshape(1, -1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_layer_norm_3d_and_odd_rows():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 7, 48).astype(np.float32))
    g = jnp.asarray(rng.uniform(0.5, 1.5, (1, 1, 48)).astype(np.float32))
    b = jnp.asarray(rng.randn(1, 1, 48).astype(np.float32))
    got = layer_norm(x, g, b)
    ref = layer_norm_reference(x, g, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_layer_norm_backward_parity():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    g = jnp.asarray(rng.uniform(0.5, 1.5, 32).astype(np.float32))
    b = jnp.asarray(rng.randn(32).astype(np.float32))
    dy = jnp.asarray(rng.randn(16, 32).astype(np.float32))

    def f_pallas(x, g, b):
        return jnp.sum(_layer_norm_pallas(x, g, b, 1e-5) * dy)

    def f_ref(x, g, b):
        return jnp.sum(layer_norm_reference(
            x, g.reshape(1, -1), b.reshape(1, -1)) * dy)

    gp = jax.grad(f_pallas, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, g, b)
    for a, e, name in zip(gp, gr, ["dx", "dgamma", "dbeta"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_layer_norm_op_integration():
    """nd.LayerNorm routes through the fused kernel and still matches
    the composite."""
    from mxtpu import nd
    rng = np.random.RandomState(3)
    x = rng.randn(4, 24).astype(np.float32)
    g = rng.uniform(0.5, 1.5, 24).astype(np.float32)
    b = rng.randn(24).astype(np.float32)
    out = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b))
    ref = layer_norm_reference(jnp.asarray(x),
                               jnp.asarray(g).reshape(1, -1),
                               jnp.asarray(b).reshape(1, -1))
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

def test_flash_attention_parity():
    rng = np.random.RandomState(4)
    B, H, T, D = 2, 3, 32, 16
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    got = _flash_attention_pallas(q, k, v, False, 1.0 / np.sqrt(D))
    ref = attention_reference(q, k, v, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_causal():
    rng = np.random.RandomState(5)
    B, H, T, D = 1, 2, 24, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    got = _flash_attention_pallas(q, k, v, True, 1.0 / np.sqrt(D))
    ref = attention_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # causality: output at t must not depend on future v
    v2 = v.at[:, :, -1].set(v[:, :, -1] + 100.0)
    got2 = _flash_attention_pallas(q, k, v2, True, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(got[:, :, :-1]),
                               np.asarray(got2[:, :, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_cross_lengths():
    """Tk > Tq (decoding with cache) incl. causal diagonal alignment."""
    rng = np.random.RandomState(6)
    B, H, Tq, Tk, D = 1, 2, 8, 32, 16
    q = jnp.asarray(rng.randn(B, H, Tq, D).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32))
    for causal in (False, True):
        got = _flash_attention_pallas(q, k, v, causal, 1.0 / np.sqrt(D))
        ref = attention_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"causal={causal}")


def test_flash_attention_grad():
    rng = np.random.RandomState(7)
    B, H, T, D = 1, 1, 16, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    do = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) * do)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) * do)

    gp = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e, name in zip(gp, gr, ["dq", "dk", "dv"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_flash_attention_grad_multiblock():
    """Backward with several q and kv blocks (T=256 → 2×128 blocks),
    causal and not — exercises the blockwise dq/dkv accumulation and
    the causal block-skip in both backward kernels."""
    rng = np.random.RandomState(9)
    B, H, T, D = 1, 2, 256, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    do = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    for causal in (False, True):
        def f(q, k, v):
            return jnp.sum(_flash_attention_pallas(
                q, k, v, causal, 1.0 / np.sqrt(D)) * do)

        def f_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal) * do)

        gp = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, e, name in zip(gp, gr, ["dq", "dk", "dv"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(e), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} causal={causal}")


def test_flash_attention_grad_cross_lengths():
    """Tk != Tq backward (cached decoding shapes), causal diagonal
    offset included."""
    rng = np.random.RandomState(10)
    B, H, Tq, Tk, D = 1, 1, 8, 32, 8
    q = jnp.asarray(rng.randn(B, H, Tq, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32))
    do = jnp.asarray(rng.randn(B, H, Tq, D).astype(np.float32))
    for causal in (False, True):
        def f(q, k, v):
            return jnp.sum(_flash_attention_pallas(
                q, k, v, causal, 1.0 / np.sqrt(D)) * do)

        def f_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal) * do)

        gp = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, e, name in zip(gp, gr, ["dq", "dk", "dv"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(e), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} causal={causal}")


def test_flash_attention_causal_tq_gt_tk():
    """Tq > Tk causal: the first Tq-Tk rows have NO visible key.
    Convention: those rows output 0 with zero gradients (kernel and
    reference agree); regression for the lse-sentinel-absorption bug
    that inflated their backward by Tk×."""
    rng = np.random.RandomState(12)
    B, H, Tq, Tk, D = 1, 1, 16, 8, 8
    q = jnp.asarray(rng.randn(B, H, Tq, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32))
    do = jnp.asarray(rng.randn(B, H, Tq, D).astype(np.float32))
    got = _flash_attention_pallas(q, k, v, True, 1.0 / np.sqrt(D))
    ref = attention_reference(q, k, v, True)
    # fully-masked rows are exactly zero in both
    assert np.all(np.asarray(got)[:, :, :Tq - Tk] == 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def f(q, k, v):
        return jnp.sum(_flash_attention_pallas(
            q, k, v, True, 1.0 / np.sqrt(D)) * do)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, True) * do)

    gp = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e, name in zip(gp, gr, ["dq", "dk", "dv"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    # masked rows contribute zero dq
    assert np.all(np.asarray(gp[0])[:, :, :Tq - Tk] == 0.0)


def test_flash_attention_grad_dispatch_modes(monkeypatch):
    """'auto' (→ ref path at small T) and 'ref' agree with 'pallas';
    unknown modes raise.  Covers the dispatch predicate the autouse
    fixture otherwise pins to 'pallas'."""
    rng = np.random.RandomState(11)
    B, H, T, D = 1, 1, 16, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    do = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))

    def grads():
        def f(q, k, v):
            return jnp.sum(_flash_attention_pallas(
                q, k, v, True, 1.0 / np.sqrt(D)) * do)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    results = {}
    for mode in ("pallas", "auto", "ref"):
        monkeypatch.setenv("MXTPU_FLASH_BWD", mode)
        results[mode] = grads()
    for mode in ("auto", "ref"):
        for a, e in zip(results[mode], results["pallas"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=1e-4, atol=1e-4)
    monkeypatch.setenv("MXTPU_FLASH_BWD", "blockwise")
    with pytest.raises(ValueError):
        grads()


def test_flash_attention_op():
    from mxtpu import nd
    rng = np.random.RandomState(8)
    q = rng.randn(1, 2, 16, 8).astype(np.float32)
    k = rng.randn(1, 2, 16, 8).astype(np.float32)
    v = rng.randn(1, 2, 16, 8).astype(np.float32)
    out = nd.flash_attention(nd.array(q), nd.array(k), nd.array(v),
                             causal=True)
    ref = attention_reference(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), True)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_unaligned_pads_not_falls_back():
    """T not a multiple of 8 (e.g. the observed T=12) keeps the fused
    kernel via exact pad-and-mask — no warning, reference parity."""
    import warnings
    rng = np.random.RandomState(9)
    B, H, D = 2, 3, 16
    cases = [(12, 12, True), (12, 12, False), (5, 5, True),
             (7, 19, False), (12, 20, True),
             (12, 16, True), (13, 7, True)]  # incl. Tq ≢ Tk mod 8
    for Tq, Tk, causal in cases:
        q = jnp.asarray(rng.randn(B, H, Tq, D).astype(np.float32)) * 0.5
        k = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32)) * 0.5
        v = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            got = flash_attention(q, k, v, causal=causal)
        assert not w, (Tq, Tk, causal, [str(x.message) for x in w])
        ref = attention_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"Tq={Tq} Tk={Tk} "
                                           f"causal={causal}")


def test_flash_attention_unaligned_causal_no_future_leak():
    """Padded causal run stays causal: perturbing future keys/values
    must not change earlier outputs."""
    rng = np.random.RandomState(10)
    B, H, T, D = 1, 2, 12, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    got = flash_attention(q, k, v, causal=True)
    v2 = v.at[:, :, -1].set(v[:, :, -1] + 100.0)
    got2 = flash_attention(q, k, v2, causal=True)
    np.testing.assert_allclose(np.asarray(got[:, :, :-1]),
                               np.asarray(got2[:, :, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_unaligned_grad():
    """Gradients flow through the pad-and-mask path and match the
    reference."""
    rng = np.random.RandomState(11)
    B, H, T, D = 1, 2, 12, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    for causal in (True, False):
        gp = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, e, name in zip(gp, gr, ["dq", "dk", "dv"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{name} causal={causal}")


def test_flash_attention_unaligned_causal_cross_hits_kernel():
    """Causal cross lengths with Tq % 8 != Tk % 8 used to warn and
    fall back (plain padding would shift the diagonal); the static
    valid_kv mask + explicit delta now keep them on the fused kernel:
    no warning, reference parity for values AND grads."""
    import warnings
    rng = np.random.RandomState(12)
    B, H, D = 1, 2, 8
    for Tq, Tk in ((12, 16), (13, 7), (5, 30)):
        q = jnp.asarray(rng.randn(B, H, Tq, D).astype(np.float32)) * 0.5
        k = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32)) * 0.5
        v = jnp.asarray(rng.randn(B, H, Tk, D).astype(np.float32))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            got = flash_attention(q, k, v, causal=True)
        assert not w, [str(x.message) for x in w]
        ref = attention_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        gp = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, e, name in zip(gp, gr, ["dq", "dk", "dv"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-4,
                err_msg=f"{name} Tq={Tq} Tk={Tk}")


def test_transformer_model_odd_seq_hits_kernel():
    """Model-layer guarantee: an encoder forward at an odd sequence
    length emits no fallback warning and matches the reference
    attention semantics (ISSUE 2 tentpole 3)."""
    import warnings
    from mxtpu import nd
    from mxtpu.models.transformer import TransformerEncoder
    rng = np.random.RandomState(13)
    net = TransformerEncoder(1, 32, 64, 4, dropout=0.0)
    net.initialize()
    x = nd.array(rng.randn(2, 13, 32).astype(np.float32))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        y = net(x)
    fallback = [x for x in w if "falling back" in str(x.message)]
    assert not fallback, [str(x.message) for x in fallback]
    assert y.shape == (2, 13, 32)


# ------------------------------------------------------ ssm_update
def _bits(a):
    return np.asarray(a).view(np.uint32)


def _state_step(rng, layers=3, B=5, H=4, P=8, N=128):
    """A small ``ssm`` table and one token a lane: lane 0 takes its
    first token, lane 2 is idle (``length`` 0), lane 4 is idle at
    ``step`` 0 (a free slot)."""
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return dict(
        table=arr(layers, B, H, P, N), x=arr(B, 1, H * P),
        dt=arr(B, 1, H), b_mat=arr(B, 1, N), c_mat=arr(B, 1, N),
        a_log=arr(H), d_skip=arr(H), dt_bias=arr(H),
        step=jnp.asarray([0.0, 5.0, 3.0, 9.0, 0.0][:B]),
        length=jnp.asarray([1.0, 1.0, 0.0, 1.0, 0.0][:B]))


def _scan(monkeypatch, kernel, layer, **over):
    """``ssm_scan``'s one-token step by the kernel or by XLA's form;
    returns ``(y, table, kernel call sites)``."""
    from mxtpu.kernels import ssm_update
    from mxtpu.ndarray import rnn_impl
    with monkeypatch.context() as m:
        if not kernel:
            m.setattr(rnn_impl, "_state_in_whole_tiles", lambda t: False)
        with ssm_update.call_sites() as traced:
            y, table = rnn_impl._ssm_scan_op(layer=layer, **over)
    return y, table, traced[0]


@pytest.mark.parametrize("heads,p,block_heads", [
    (4, 8, 4), (4, 16, 2), (6, 8, 1), (2, 64, 2),
], ids=["one-block", "two-blocks", "a-head-a-block", "p64"])
@pytest.mark.parametrize("layer", [0, 2])
def test_ssm_update_equals_the_xla_form(monkeypatch, heads, p, block_heads,
                                        layer):
    """The kernel against XLA's one-token form through the op: the
    written plane and ``y`` to float32 rounding (the sum over the state
    axis runs in another order), every other layer's plane bit for bit
    what it was, one traced call site."""
    from mxtpu.kernels import ssm_update
    monkeypatch.setattr(ssm_update, "_BLOCK_BYTES",
                        block_heads * p * 128 * 4)
    ssm_update._update.clear_cache()
    ins = _state_step(np.random.default_rng(3), H=heads, P=p)
    want_y, want, none = _scan(monkeypatch, False, layer, **ins)
    got_y, got, sites = _scan(monkeypatch, True, layer, **ins)
    ssm_update._update.clear_cache()
    assert (none, sites) == (0, 1)
    assert got_y.dtype == want_y.dtype and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[layer]),
                               np.asarray(want[layer]), rtol=1e-6,
                               atol=1e-6)
    assert (_bits(got[layer]) != _bits(ins["table"][layer])).any()
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(_bits(got[other]),
                                      _bits(ins["table"][other]))


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_ssm_update_keeps_an_idle_lane_bit_for_bit(monkeypatch, kernel):
    """``length`` 0 is ``dt`` 0: no decay, no input.  The lane's state,
    a zero among it, is the bits it was — whether the lane's ``step``
    is 0 (a free slot) or not."""
    ins = _state_step(np.random.default_rng(4))
    ins["table"] = ins["table"].at[1, 2, 0, 0, 0].set(0.0)
    _, got, sites = _scan(monkeypatch, kernel, 1, **ins)
    assert sites == int(kernel)
    for lane in (2, 4):
        np.testing.assert_array_equal(_bits(got[1, lane]),
                                      _bits(ins["table"][1, lane]))
    assert (_bits(got[1, 1]) != _bits(ins["table"][1, 1])).any()


@pytest.mark.parametrize("held", [np.nan, np.inf, 7.0],
                         ids=["nan", "inf", "finite"])
def test_ssm_update_starts_a_first_token_from_zeros(monkeypatch, held):
    """A lane with ``step`` 0 and ``length`` 1 starts from zeros
    whatever it held: a select, not a product with 0."""
    ins = _state_step(np.random.default_rng(5))
    clean = dict(ins, table=ins["table"].at[1, 0].set(0.0))
    dirty = dict(ins, table=ins["table"].at[1, 0].set(held))
    want_y, want, _ = _scan(monkeypatch, True, 1, **clean)
    got_y, got, sites = _scan(monkeypatch, True, 1, **dirty)
    assert sites == 1
    np.testing.assert_array_equal(_bits(got[1]), _bits(want[1]))
    np.testing.assert_array_equal(_bits(got_y), _bits(want_y))
    assert np.isfinite(np.asarray(got[1])).all()
    assert np.abs(np.asarray(got[1, 0])).max() > 0


def test_ssm_update_applies_d_skip(monkeypatch):
    """``y = S C + d_skip x``: the skip term is added to the kernel's
    read-out, head by head."""
    ins = _state_step(np.random.default_rng(6))
    y, _, _ = _scan(monkeypatch, True, 0, **ins)
    bare, _, sites = _scan(monkeypatch, True, 0,
                           **dict(ins, d_skip=jnp.zeros(4)))
    assert sites == 1
    skip = np.repeat(np.asarray(ins["d_skip"]), 8) * np.asarray(ins["x"])
    assert np.abs(skip).max() > 0.1
    np.testing.assert_allclose(np.asarray(y) - np.asarray(bare), skip,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("why,shape,dtype", [
    ("state-of-96", (2, 3, 4, 8, 96), jnp.float32),
    ("p-of-4", (2, 3, 4, 4, 128), jnp.float32),
    ("bfloat16-table", (2, 3, 4, 16, 128), jnp.bfloat16),
])
def test_ssm_update_is_refused_what_is_not_whole_tiles(monkeypatch, why,
                                                       shape, dtype):
    """A state that does not fill whole (8, 128) float32 tiles takes
    XLA's form, decided from the table alone."""
    from mxtpu.ndarray import rnn_impl
    layers, B, H, P, N = shape
    ins = _state_step(np.random.default_rng(7), layers, B, H, P, N)
    ins["table"] = ins["table"].astype(dtype)
    assert not rnn_impl._state_in_whole_tiles(ins["table"])
    y, got, sites = _scan(monkeypatch, True, 1, **ins)
    want_y, want, _ = _scan(monkeypatch, False, 1, **ins)
    assert sites == 0 and got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


def test_ssm_update_is_taken_by_what_is_observed(monkeypatch):
    """One token a lane, Pallas kernels on, a float32 table the device
    keeps with ``N`` minor: those together, and nothing a caller
    sets; ``T`` > 1 keeps the chunked scan."""
    from jax.experimental.layout import Layout
    from mxtpu import kernels
    from mxtpu.ndarray import rnn_impl
    table = jnp.zeros((1, 2, 2, 8, 128))
    assert rnn_impl._state_in_whole_tiles(table)    # interpreter, row-major
    for order, whole in (((0, 1, 2, 3, 4), True), ((0, 1, 2, 4, 3), False),
                         ((0, 1, 3, 4, 2), False)):
        monkeypatch.setattr(
            rnn_impl, "_resident_layout",
            lambda x, order=order: Layout(major_to_minor=order,
                                          tiling=((8, 128),)))
        assert rnn_impl._state_in_whole_tiles(table) is whole
    monkeypatch.undo()
    monkeypatch.setattr(kernels, "pallas_enabled", lambda: False)
    assert not rnn_impl._state_in_whole_tiles(table)
    monkeypatch.undo()
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    ins = _state_step(np.random.default_rng(8))
    two = {k: jnp.concatenate([ins[k]] * 2, axis=1)
           for k in ("x", "dt", "b_mat", "c_mat")}
    _, _, sites = _scan(monkeypatch, True, 0, **dict(ins, **two))
    assert sites == 0
