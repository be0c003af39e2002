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
                                           _flash_attention_pallas,
                                           cached_attention,
                                           cached_call_sites)


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


# ----------------------------------------------------------------------
# cached_attention's chunk path: the blockwise kernel that stops at each
# row's frontier, held against the op's XLA form
# ----------------------------------------------------------------------
def _exact_attention(q, k, v, step, window):
    """Float64 attention of the op's masks: the reference both forms'
    errors are measured against."""
    q, k, v = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
               for a in (q, k, v))
    B, H, T, D = q.shape
    Hk, L = k.shape[1], k.shape[2]
    out = np.zeros_like(q)
    for b in range(B):
        pos = int(step[b]) + np.arange(T)[:, None]
        col = np.arange(L)[None, :]
        back = (pos - col) % L
        seen = ((back < window) & (back <= pos)) if window else col <= pos
        for h in range(H):
            s = np.where(seen, q[b, h] @ k[b, h * Hk // H].T / np.sqrt(D),
                         -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, h] = (p / p.sum(-1, keepdims=True)) @ v[b, h * Hk // H]
    return out


def _as_the_chip_feeds(q, k, v, step, window):
    """The op's XLA form as the chip computes it: its float32 products
    at the default precision take bfloat16 multiplicands (the CPU's
    backend takes them whole)."""
    from mxtpu.ndarray import rnn_impl
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    real = jnp.einsum

    def einsum(spec, a, b, **kw):
        return real(spec, bf(a), bf(b), **kw)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(rnn_impl, "_attends_by_kernel", lambda q, k, block=1: False)
        m.setattr(jnp, "einsum", einsum)
        return rnn_impl._cached_attention_op(q, k, v, step, window=window)


# (g, D, table dtype, T, L, window, the rows' steps): Mellum's heads
# (8 a group, 128) and granite's (4, 64, keys with L minor) among them; a
# ring of 640 for a window of 512 is Mellum's 1,280 / 1,024 halved
_CHUNKS = {
    "g1-d64-f32-step0-and-last-block": (1, 64, jnp.float32, 64, 512, 0,
                                        (0, 448)),
    "g8-d128-bf16-t256-last-block": (8, 128, jnp.bfloat16, 256, 1024, 0,
                                     (768, 0)),
    "g8-d128-bf16-mid-table": (8, 128, jnp.bfloat16, 64, 1024, 0,
                               (300, 517)),
    "g1-d128-bf16-blocks-apart": (1, 128, jnp.bfloat16, 256, 1536, 0,
                                  (0, 1024)),
    "g4-d64-bf16-t256": (4, 64, jnp.bfloat16, 256, 768, 0, (100, 512)),
    "g8-d64-f32-t256": (8, 64, jnp.float32, 256, 512, 0, (256, 0)),
    "g8-d128-bf16-ring-no-wrap": (8, 128, jnp.bfloat16, 64, 640, 512,
                                  (0, 200)),
    "g8-d128-bf16-ring-wrapped": (8, 128, jnp.bfloat16, 64, 640, 512,
                                  (1900, 600)),
    "g4-d64-bf16-ring-straddles": (4, 64, jnp.bfloat16, 128, 640, 512,
                                   (576, 2500)),
    "g1-d128-f32-ring-wrapped": (1, 128, jnp.float32, 64, 640, 512,
                                 (1300, 0)),
}


def _chunk_inputs(g, D, dtype, T, L, steps, seed=11):
    rng = np.random.default_rng(seed)
    B, Hk = len(steps), 2
    q = jnp.asarray(rng.normal(size=(B, g * Hk, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hk, L, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hk, L, D)), dtype)
    return q, k, v, jnp.asarray(steps, jnp.int32)


def _table_of(k, v):
    """A slot table of two layers whose layer 1 holds ``k`` and ``v``
    and whose layer 0 is NaN: a read of the wrong layer shows."""
    planes = jnp.stack([k, v])
    return jnp.stack([jnp.full_like(planes, jnp.nan), planes])


@pytest.mark.parametrize("keys_minor", [False, True],
                         ids=["head-minor", "capacity-minor"])
@pytest.mark.parametrize("case", sorted(_CHUNKS))
def test_cached_attention_kernel_matches_the_xla_form(monkeypatch, case,
                                                      keys_minor):
    """The kernel through the op, reading layer 1's planes of a slot
    table as a model's graph hands it, against the op's XLA form, for
    keys bound as they lie either way: a float32 table to float32
    rounding; a bfloat16 table, whose products both forms feed the
    chip's matrix unit at bfloat16, no further from float64 attention
    than the XLA form as the chip computes it (mean and largest error),
    and within that rounding of it as the CPU computes it.  Rows at step
    0, in mid-table and with a frontier in the last block, rows whose
    frontiers lie blocks apart, a scratch row (step 0), a ring with and
    without a wrap and a chunk that straddles it; queries past a row's
    valid length are positions like any other."""
    from mxtpu.ndarray import rnn_impl
    g, D, dtype, T, L, window, steps = _CHUNKS[case]
    q, k, v, step = _chunk_inputs(g, D, dtype, T, L, steps)
    table = _table_of(k, v)
    monkeypatch.setattr(rnn_impl, "_keys_are_minor", lambda t: keys_minor)
    monkeypatch.setattr(rnn_impl, "_SCORES_FOR_KERNEL", 0)
    with cached_call_sites() as sites:
        got = rnn_impl._cached_attention_op(q, table, table, step,
                                            window=window, layer=1)
    assert sites[0] == int(dtype == jnp.bfloat16)
    if dtype == jnp.float32:        # the rule keeps XLA's form: call it
        got = cached_attention(
            q, jnp.swapaxes(table, -1, -2) if keys_minor else table, step,
            1 / np.sqrt(D), 1, window=window, keys_minor=keys_minor)
    with monkeypatch.context() as m:
        m.setattr(rnn_impl, "_attends_by_kernel", lambda q, k, block=1: False)
        xla = rnn_impl._cached_attention_op(q, k, v, step, window=window)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, xla = np.asarray(got), np.asarray(xla)
    exact = _exact_attention(q, k, v, steps, window)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, xla, rtol=2e-6, atol=2e-6)
        return
    chip = np.asarray(_as_the_chip_feeds(q, k, v, step, window))
    err, chip_err = np.abs(got - exact), np.abs(chip - exact)
    assert err.mean() <= chip_err.mean() and err.max() <= chip_err.max(), \
        (err.mean(), chip_err.mean(), err.max(), chip_err.max())
    np.testing.assert_allclose(got, xla, atol=4 * chip_err.max())


def test_cached_attention_planes_alone_keep_the_xla_form(monkeypatch):
    """The kernel reads a layer's planes where the slot table lies, so
    only the table form takes it: the op handed the planes alone (as
    ``kv_cache_read`` slices them) keeps XLA's form."""
    from mxtpu.ndarray import rnn_impl
    monkeypatch.setattr(rnn_impl, "_SCORES_FOR_KERNEL", 0)
    q, k, v, step = _chunk_inputs(4, 64, jnp.bfloat16, 64, 640, (0, 900))
    table = _table_of(k, v)
    with cached_call_sites() as sites:
        alone = rnn_impl._cached_attention_op(q, k, v, step, window=512)
    assert sites[0] == 0
    with cached_call_sites() as sites:
        whole = rnn_impl._cached_attention_op(q, table, table, step,
                                              window=512, layer=1)
    assert sites[0] == 1
    with pytest.MonkeyPatch.context() as m:
        m.setattr(rnn_impl, "_attends_by_kernel", lambda q, k, block=1: False)
        xla = rnn_impl._cached_attention_op(q, table, table, step,
                                            window=512, layer=1)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(xla))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(xla),
                               atol=2e-2)


def test_cached_attention_kernel_reads_no_block_past_a_frontier():
    """What a row cannot see is never read: keys and values past the
    block of a row's last query (NaN here) leave its output finite and
    equal to the same table with them zeroed; a ring that has wrapped
    is read whole."""
    q, k, v, step = _chunk_inputs(8, 128, jnp.bfloat16, 64, 1024, (0, 300))
    past = (jnp.arange(1024) >= 512)[None, None, :, None]
    poisoned = _table_of(*[jnp.where(past, jnp.nan, a).astype(a.dtype)
                           for a in (k, v)])
    zeroed = _table_of(*[jnp.where(past, 0, a).astype(a.dtype)
                         for a in (k, v)])
    got = cached_attention(q, poisoned, step, 0.1, 1)
    want = cached_attention(q, zeroed, step, 0.1, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ring = cached_attention(q, poisoned, jnp.asarray([1100, 300]), 0.1, 1,
                            window=512)
    assert np.isnan(np.asarray(ring[0])).all()
    assert np.isfinite(np.asarray(ring[1])).all()


@pytest.mark.parametrize("why,shape,dtype,takes", [
    ("mellum-chunk", ((1, 32, 256, 128), (1, 4, 8448, 128)), jnp.bfloat16,
     True),
    ("mellum-chunk-of-128", ((1, 32, 128, 128), (1, 4, 8448, 128)),
     jnp.bfloat16, True),
    ("granite-rung-8", ((8, 32, 128, 64), (8, 8, 1280, 64)), jnp.bfloat16,
     True),
    ("olmo-rung-4", ((4, 30, 256, 128), (4, 30, 2304, 128)), jnp.bfloat16,
     True),
    ("mellum-ring", ((1, 32, 256, 128), (1, 4, 1280, 128)), jnp.bfloat16,
     False),
    ("olmo-one-row", ((1, 30, 256, 128), (1, 30, 2304, 128)), jnp.bfloat16,
     False),
    ("granite-rung-2", ((2, 32, 256, 64), (2, 8, 1280, 64)), jnp.bfloat16,
     False),
    ("decode-step", ((24, 32, 1, 128), (24, 4, 8448, 128)), jnp.bfloat16,
     False),
    ("float32-table", ((2, 16, 128, 64), (2, 16, 512, 64)), jnp.float32,
     False),
    ("chunk-of-8", ((1, 4, 8, 16), (1, 2, 256, 16)), jnp.bfloat16, False),
    ("ring-of-12", ((1, 4, 16, 16), (1, 2, 12, 16)), jnp.bfloat16, False),
])
def test_cached_attention_takes_the_kernel_by_shape_and_dtype(why, shape,
                                                              dtype, takes):
    """The rule, from both sides: a chunk of a bfloat16 table that tiles
    and whose float32 scores under XLA's form would reach 128 MiB takes
    the kernel (Mellum's full table, a granite rung of 8, an Olmo rung
    of 4); a chunk whose scores XLA holds on the chip (a ring of 1,280,
    Olmo's one row, a granite rung of 2), a decode step, a float32
    table (bertgen's) and shapes that do not tile keep XLA's form — and
    so does every shape where Pallas kernels do not run."""
    from mxtpu.ndarray import rnn_impl
    q, k = (jax.ShapeDtypeStruct(s, dtype) for s in shape)
    assert rnn_impl._attends_by_kernel(q, k) is takes
    with pytest.MonkeyPatch.context() as m:
        m.setenv("MXTPU_PALLAS", "0")
        assert rnn_impl._attends_by_kernel(q, k) is False


def test_cached_attention_binds_keys_as_the_device_lays_them(monkeypatch):
    """A table the device keeps with ``L`` minor is bound with its last
    two axes swapped; one kept with ``head_dim`` minor as it is."""
    from jax.experimental.layout import Layout
    from mxtpu.ndarray import rnn_impl
    table = jnp.zeros((2, 2, 1, 2, 256, 64), jnp.bfloat16)
    assert not rnn_impl._keys_are_minor(table)        # the CPU: row-major
    for order, minor in (((0, 1, 2, 3, 5, 4), True),
                         ((0, 1, 2, 3, 4, 5), False),
                         ((0, 1, 2, 4, 3, 5), False)):
        monkeypatch.setattr(rnn_impl, "_resident_layout",
                            lambda x, order=order: Layout(
                                major_to_minor=order,
                                tiling=((8, 128), (2, 1))))
        assert rnn_impl._keys_are_minor(table) is minor


@pytest.mark.parametrize("window", [0, 512], ids=["full", "ring"])
def test_cached_attention_table_form_is_the_planes_program_in_decode(
        window):
    """A decode step (one query a row) handed the slot table and
    ``layer=`` lowers to the program of the same step handed the planes
    ``kv_cache_read`` slices out — the graph's form before the table was
    handed whole — metadata aside: the decode programs are what they
    were."""
    import re
    from mxtpu import analysis
    from mxtpu.ndarray import rnn_impl
    q = jax.ShapeDtypeStruct((3, 8, 1, 128), jnp.float32)
    table = jax.ShapeDtypeStruct((2, 2, 3, 2, 640, 128), jnp.bfloat16)
    step = jax.ShapeDtypeStruct((3,), jnp.float32)

    def whole(q, t, s):
        return rnn_impl._cached_attention_op(q, t, t, s, window=window,
                                             layer=1)

    def planes(q, t, s):
        return rnn_impl._cached_attention_op(
            q, rnn_impl._kv_cache_read_op(t, layer=1, plane=0),
            rnn_impl._kv_cache_read_op(t, layer=1, plane=1), s,
            window=window)

    texts = [re.sub(r", metadata=\{[^}]*\}|HloModule \S+", "",
                    analysis.lowered_text(f, q, table, step))
             for f in (whole, planes)]
    assert texts[0] == texts[1]
