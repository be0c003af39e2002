"""Compile the main path's Pallas kernels for the real chip, here.

The TPU compiler is installed in the CPU sandbox and compiles for a
chip that is *described*, not attached (``v5e:2x2``), so every case
below hands a kernel at its production width to the same compiler the
chip run uses: a slice misaligned to the tiling, too much VMEM, or an
op Mosaic cannot lower fails here at no chip time.  Nothing runs —
results and times come only from ``chip_smoke.py`` on the chip.

This is the ONE test file that loads the TPU compiler (only one
process at a time may hold libtpu, and a second file could land on a
second xdist worker).  The topology is described inside a fixture,
never at import, so every worker collects the same tests.

``pallas_enabled()``/``interpret_mode()`` ask ``jax.default_backend()``
and see the CPU here, so the tests steer them to the TPU answer by
monkeypatch — the program grows no option for it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to JAX's persistent
    cache but cannot be read back without the chip (the next run warns
    and recompiles), so the cache is off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernel dispatch as it is on the chip: Pallas on, interpreter
    off."""
    from mxtpu import kernels
    monkeypatch.setattr(kernels, "pallas_enabled", lambda: True)
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)


@pytest.fixture
def chip_layouts(topo, monkeypatch):
    """``rnn_impl._resident_layout`` asks the default device, which is
    the CPU here; the described chip answers in its place, as the chip
    does for a table's shape and dtype (PERF.md, PR 26 and PR 29)."""
    import numpy as np
    from jax.experimental.layout import Layout
    from mxtpu.ndarray import rnn_impl
    chip = topo.devices[0]
    monkeypatch.setattr(
        rnn_impl, "_resident_layout",
        lambda x: Layout.from_pjrt_layout(chip.client.get_default_layout(
            np.dtype(x.dtype), tuple(x.shape), chip)))
    # the lanes' loop keeps its traces: none made with the other
    # answer may serve here, nor this one serve a later test
    rnn_impl._write_lanes.clear_cache()
    yield
    rnn_impl._write_lanes.clear_cache()


def _attention(q_shape, k_shape, causal, grad):
    from mxtpu.kernels import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    return (fwd_bwd if grad else fwd), \
        [(q_shape, jnp.bfloat16), (k_shape, jnp.bfloat16),
         (k_shape, jnp.bfloat16)]


def _layer_norm():
    from mxtpu.kernels import layer_norm

    def fwd_bwd(x, g, b):
        return jax.grad(
            lambda *a: layer_norm(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(x, g, b)

    return fwd_bwd, [((4096, 1024), jnp.bfloat16),
                     ((1024,), jnp.float32), ((1024,), jnp.float32)]


def _fused_residual_ln(p):
    from mxtpu.kernels.layer_norm import fused_residual_layer_norm

    def fwd_bwd(h, bias, res, g, b, key_data):
        return jax.grad(
            lambda *a: fused_residual_layer_norm(
                *a, key_data, p=p, training=True)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4))(h, bias, res, g, b)

    return fwd_bwd, [((4096, 1024), jnp.bfloat16),
                     ((1024,), jnp.bfloat16),
                     ((4096, 1024), jnp.bfloat16),
                     ((1024,), jnp.float32), ((1024,), jnp.float32),
                     ((2,), jnp.uint32)]


# name -> builder of (fn, [(shape, dtype), ...]); the widths are
# BERT-Large's (16 heads x 64, hidden 1024) at the benchmark shapes
# b32 x s128 and b8 x s512, plus the long-context and decode shapes
_CASES = {
    "flash_fwd_b32_h16_t128_d64": lambda: _attention(
        (32, 16, 128, 64), (32, 16, 128, 64), False, grad=False),
    "flash_fwd_b8_h16_t512_d64": lambda: _attention(
        (8, 16, 512, 64), (8, 16, 512, 64), False, grad=False),
    "flash_fwd_bwd_b2_h16_t1024_d64": lambda: _attention(
        (2, 16, 1024, 64), (2, 16, 1024, 64), False, grad=True),
    "flash_fwd_bwd_b2_h16_t1024_d64_causal": lambda: _attention(
        (2, 16, 1024, 64), (2, 16, 1024, 64), True, grad=True),
    "flash_fwd_bwd_padded_t12": lambda: _attention(
        (2, 16, 12, 64), (2, 16, 12, 64), False, grad=True),
    "flash_fwd_tq1_tk512_causal": lambda: _attention(
        (4, 16, 1, 64), (4, 16, 512, 64), True, grad=False),
    "layer_norm_fwd_bwd_4096x1024": _layer_norm,
    "fused_residual_ln_fwd_bwd_4096x1024_keep0.9":
        lambda: _fused_residual_ln(0.1),
    "fused_residual_ln_fwd_bwd_4096x1024_keep1.0":
        lambda: _fused_residual_ln(0.0),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_compiles_for_v5e(case, one_chip, on_tpu,
                                 no_persistent_cache):
    fn, specs = _CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    from mxtpu import analysis
    calls = analysis.compiled_summary(fn, *args)["custom_calls"]
    assert calls.get("tpu_custom_call", {}).get("count", 0) >= 1, \
        f"{case}: no Pallas custom call in the compiled program — " \
        f"the lax reference was taken: {calls}"


@pytest.mark.parametrize(
    "dtype,slots,heads,query_heads,cap,tiles",
    [(jnp.float32, 32, 16, 16, 512, ((8, 128),)),
     (jnp.bfloat16, 49, 8, 32, 1280, ((8, 128), (2, 1)))],
    ids=["bertgen-f32", "hybrid-bf16"])
def test_kv_table_is_written_in_place_on_v5e(
        dtype, slots, heads, query_heads, cap, tiles, one_chip, on_tpu,
        chip_layouts, no_persistent_cache):
    """A serve cell's slot table at its real widths — bertgen: 32
    slots, 16 heads, 512 positions, float32; the hybrid: 49 slots, 8
    key/value heads under 32 query heads, 1280 positions, bfloat16;
    head_dim 64, two of the layers — written by ``kv_cache_write``
    one token a lane, read by ``cached_attention``, and handed back.
    The described chip says where it keeps such a table: ``L`` minor,
    so the writes take the column-store kernel.  The chip's compiler
    must alias the donated table to the result and need less than one
    cache plane of temporaries — a table copied, a swap of the last
    two axes that is not a bitcast, or a table carried through a loop
    in another layout shows as a table's worth — and the program holds
    one custom call a write and no loop."""
    from mxtpu.ndarray import rnn_impl
    layers, dim = 2, 64
    shape = (layers, 2, slots, heads, cap, dim)
    held = rnn_impl._resident_layout(jax.ShapeDtypeStruct(shape, dtype))
    assert held.major_to_minor == (0, 1, 2, 3, 5, 4)
    assert held.tiling == tiles

    def step(table, k, v, q, at):
        # as in the model, a layer's keys and values come from the
        # layer below: its reads of the table end before they are made
        out = jnp.zeros_like(q)
        for i in range(layers):
            below = out[:, ::query_heads // heads]
            table = rnn_impl._kv_cache_write_op(table, k[i] + below, at,
                                                layer=i, plane=0)
            table = rnn_impl._kv_cache_write_op(table, v[i] + below, at,
                                                layer=i, plane=1)
            out = rnn_impl._cached_attention_op(
                q + out,
                rnn_impl._kv_cache_read_op(table, layer=i, plane=0),
                rnn_impl._kv_cache_read_op(table, layer=i, plane=1), at)
        return out, table

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    from mxtpu import analysis
    text, mem = analysis.compiled_artifact(
        step, sds(*shape, dtype=dtype),
        sds(layers, slots, heads, 1, dim),
        sds(layers, slots, heads, 1, dim), sds(slots, query_heads, 1, dim),
        sds(slots), donate_argnums=0)
    plane = slots * heads * cap * dim * jnp.dtype(dtype).itemsize
    assert mem["alias_size_in_bytes"] == layers * 2 * plane
    assert mem["temp_size_in_bytes"] < plane, mem
    calls = analysis.summarize(text, mem)["custom_calls"]
    assert calls["tpu_custom_call"]["count"] == layers * 2, calls
    assert " while(" not in text


def test_a_prefill_write_keeps_the_lanes_loop_on_v5e(
        one_chip, on_tpu, chip_layouts, no_persistent_cache):
    """Sixty-four positions a lane are runs along the minor axis, not
    columns: on the same table the write stays the loop of
    ``dynamic_update_slice``, in place."""
    from mxtpu.ndarray import rnn_impl
    shape = (2, 2, 8, 16, 512, 64)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    from mxtpu import analysis
    text, mem = analysis.compiled_artifact(
        lambda t, n, at: rnn_impl._kv_cache_write_op(t, n, at, 1, 1),
        sds(*shape), sds(8, 16, 64, 64), sds(8), donate_argnums=0)
    assert mem["alias_size_in_bytes"] == 2 * 2 * 8 * 16 * 512 * 64 * 4
    assert mem["temp_size_in_bytes"] < 8 * 16 * 512 * 64 * 4, mem
    assert " while(" in text and "tpu_custom_call" not in text


def _recurrent_step(one_chip):
    """The hybrid cell's recurrent state at its real widths (48 slots,
    64 heads of 64 with state 128, 4352 convolution channels; two of
    the 36 layers): one decode step through ``ssm_conv`` and
    ``ssm_scan`` that replaces each layer's plane of both tables.
    ``(compiled text, memory, bytes of an ssm plane, of both tables)``
    of that step on the described chip, the tables donated."""
    from mxtpu.ndarray import rnn_impl
    layers, slots, heads, p, n, k = 2, 48, 64, 64, 128, 4
    chan = heads * p + 2 * n

    def step(ssm, conv, xbc, dt, w, b, a_log, d_skip, dt_bias, at, length):
        out = jnp.zeros((slots, 1, heads * p), jnp.float32)
        for i in range(layers):
            mixed, conv = rnn_impl._ssm_conv_op(
                conv, xbc[i] + jnp.pad(out, ((0, 0), (0, 0), (0, 2 * n))),
                w, b, at, length, layer=i)
            out, ssm = rnn_impl._ssm_scan_op(
                ssm, mixed[..., :heads * p], dt[i],
                mixed[..., heads * p:heads * p + n],
                mixed[..., heads * p + n:], a_log, d_skip, dt_bias, at,
                length, layer=i)
        return out, ssm, conv

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    from mxtpu import analysis
    text, mem = analysis.compiled_artifact(
        step, sds(layers, slots, heads, p, n), sds(layers, slots, k - 1, chan),
        sds(layers, slots, 1, chan), sds(layers, slots, 1, heads),
        sds(chan, k), sds(chan), sds(heads), sds(heads), sds(heads),
        sds(slots), sds(slots), donate_argnums=(0, 1))
    plane = slots * heads * p * n * 4
    return text, mem, plane, layers * (plane + slots * (k - 1) * chan * 4)


def test_recurrent_state_is_updated_in_place_on_v5e(one_chip,
                                                    no_persistent_cache):
    """XLA's form of the one-token state update (what a table the
    kernel is refused takes; here the CPU's answers stand, so no Pallas
    kernel is asked for): the chip's compiler must alias the donated
    tables to the results and need less than one ``ssm`` plane (100 MB)
    of temporaries — a plane rebuilt beside the table shows as a
    plane's worth, a table copied as 75 MB a lane."""
    text, mem, plane, tables = _recurrent_step(one_chip)
    assert mem["alias_size_in_bytes"] == tables
    assert mem["temp_size_in_bytes"] < plane, mem
    assert "tpu_custom_call" not in text


def test_recurrent_state_kernel_compiles_in_place_on_v5e(
        one_chip, on_tpu, chip_layouts, no_persistent_cache):
    """The same step as the chip takes it (Pallas on, the chip's own
    layout of the table: ``N`` minor in (8, 128) tiles): each layer's
    update is the Mosaic kernel ``ssm_state_update`` — one traced
    kernel, two call sites, a whole lane (2 MB) a block — the donated
    tables are aliased to the results through it, and nothing of a
    plane's size is laid beside them."""
    from mxtpu.kernels import ssm_update
    from mxtpu.ndarray import rnn_impl
    held = rnn_impl._resident_layout(
        jax.ShapeDtypeStruct((2, 48, 64, 64, 128), jnp.float32))
    assert held.major_to_minor == (0, 1, 2, 3, 4)
    assert tuple(held.tiling[0]) == (8, 128)
    with ssm_update.call_sites() as traced:
        text, mem, plane, tables = _recurrent_step(one_chip)
    assert traced[0] == 2
    assert text.count("tpu_custom_call") == 2 and "ssm_state_update" in text
    assert mem["alias_size_in_bytes"] == tables
    assert mem["temp_size_in_bytes"] < plane, mem


def _described_runner(monkeypatch, topo, one_chip, config, inputs, cut=None,
                      **runner_kw):
    """A ``GenerateRunner`` of the model of ``benchmark/configs/<config>``
    at its published widths (``cut``: a slice of its layers) on the
    described chip: nothing can be put on such a device, so the weights
    are bfloat16 shapes and ``jax.device_put`` hands shapes back.
    ``runner_kw['spec']`` is called with the net for the state spec,
    ``runner_kw['counters']`` likewise where given.  Returns the
    runner."""
    import json
    import os
    from mxtpu import symbol as sym_mod
    from mxtpu.models.hybrid import HybridDecoderModel
    from mxtpu.serving import GenerateRunner
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config)) as f:
        cfg = json.load(f)
    if cut is not None:
        cfg["layer_types"] = cfg["layer_types"][cut]
    net = HybridDecoderModel.from_config(cfg)
    out = net(*[sym_mod.var(f"data{i}") for i in range(inputs)])

    class Leaf:
        def __init__(self, shape):
            self.shape = tuple(shape)

        def asnumpy(self):
            return jax.ShapeDtypeStruct(self.shape, jnp.bfloat16,
                                        sharding=one_chip)

    put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda v, d=None: v if isinstance(
        v, jax.ShapeDtypeStruct) else put(v, d))
    monkeypatch.setattr(GenerateRunner, "_as_np",
                        staticmethod(lambda v: v.asnumpy()))
    spec = runner_kw.pop("spec")(net)
    if "counters" in runner_kw:
        runner_kw["counters"] = runner_kw["counters"](net)
    return GenerateRunner(
        sym_mod.Group(list(out)),
        {p.name: Leaf(p.shape) for p in net.collect_params().values()},
        spec, amp=True, device=topo.devices[0], cache=None, **runner_kw)


def test_hybrid_decode_program_holds_the_kernel_and_nothing_else_moves_on_v5e(
        topo, one_chip, on_tpu, chip_layouts, no_persistent_cache,
        monkeypatch):
    """The hybrid cell's decode program WHOLE (granite-4.0-h-micro: 40
    layers at published widths, 48 slots, bfloat16 weights as shapes),
    built by ``GenerateRunner``'s own ``_entry``: 36 state updates by
    the kernel, the tables aliased, and the program round them left as
    it was.  Two things a chain of such calls did to it on the chip
    (PERF.md, PR 35): the compiler's rematerialization pass, which
    counts each call's table out as a second table, re-laid the
    ``conv`` table before and after every layer (``remat`` in 70
    instruction names, 20 ms a step) — ``ssm_update.COMPILER_OPTIONS``
    keeps it off; and the kernel's operand layout spread to the
    projections and the convolution, a row a tile — ``_held_as_rows``
    stops it at the mixer's edge.  Only at full depth beside 6.4 GB of
    weights does the first show."""
    import re
    r = _described_runner(
        monkeypatch, topo, one_chip, "granite_4_0_h_micro.json", 6,
        spec=lambda net: net.state_spec(47, 1280, kv_dtype="bfloat16"),
        prompt_buckets=(128,), max_prefill_batch=1)
    # the tables are donated where the backend honours it
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        entry = r._entry(("decode", (48,)))
    assert (entry["ssm_kernel_updates"], entry["kv_kernel_writes"]) == (36, 8)
    from mxtpu import analysis
    text, mem = analysis.executable_artifact(entry["compiled"])
    tables = sum(r.state_bytes().values())
    assert tables == 4_217_438_208
    assert mem["alias_size_in_bytes"] == tables
    assert mem["temp_size_in_bytes"] < 100e6, mem     # XLA's form: 84 MB
    assert "remat" not in text
    assert not re.search(r"= f32\[36,48,3,4352\]\S* copy\(", text)
    kernels_ = [line for line in text.splitlines()
                if "tpu_custom_call" in line and "ssm_state_update" in line]
    assert len(kernels_) == 36
    assert all("/ssm/state_update/" in line for line in kernels_)
    # the input projection's product a lane a row, not a row a tile
    assert len(re.findall(r"= f32\[48,1,8512\]\{2,0,1:T\(8,128\)\S*\} "
                          r"fusion\(", text)) == 36


def test_delta_state_is_updated_in_place_on_v5e(one_chip,
                                                no_persistent_cache):
    """The Gated DeltaNet cell's state at its real widths (32 slots, 30
    heads, keys of 96, values of 192, 11520 convolution channels; two of
    the 12 linear layers of the stage): one decode step through
    ``ssm_conv`` (no bias) and ``delta_rule`` replaces each layer's
    plane of both tables.  The chip holds a last axis of 192 as 256 (a
    plane of 70.8 MB takes 94.4), aliases the donated tables to the
    results and needs less than one plane of temporaries: laid out with
    heads x 192 minor the same update wrote each head's keys out over a
    whole plane and needed 2.4 planes' worth (PERF.md, PR 32)."""
    from mxtpu.ndarray import rnn_impl
    layers, slots, heads, dk, dv, k = 2, 32, 30, 96, 192, 4
    chan = heads * (2 * dk + dv)

    def step(delta, conv, qkv, g, beta, w, at, length):
        out = jnp.zeros((slots, 1, heads * dv), jnp.float32)
        for i in range(layers):
            mixed, conv = rnn_impl._ssm_conv_op(
                conv, qkv[i] + jnp.pad(out, ((0, 0), (0, 0),
                                             (2 * heads * dk, 0))),
                w, at, length, layer=i, no_bias=True)
            out, delta = rnn_impl._delta_rule_op(
                delta, mixed[..., :heads * dk],
                mixed[..., heads * dk:2 * heads * dk],
                mixed[..., 2 * heads * dk:], g[i], beta[i], at, length,
                layer=i)
        return out, delta, conv

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    from mxtpu import analysis
    _, mem = analysis.compiled_artifact(
        step, sds(layers, slots, heads, dk, dv),
        sds(layers, slots, k - 1, chan), sds(layers, slots, 1, chan),
        sds(layers, slots, 1, heads), sds(layers, slots, 1, heads),
        sds(chan, k), sds(slots), sds(slots), donate_argnums=(0, 1))
    held = slots * heads * dk * 256 * 4          # 192 in tiles of 128
    tables = layers * (held + slots * (k - 1) * chan * 4)
    assert mem["alias_size_in_bytes"] == tables
    assert mem["temp_size_in_bytes"] < held, mem


def test_a_prefill_reads_its_lanes_where_they_lie_on_v5e(
        one_chip, on_tpu, chip_layouts, no_persistent_cache):
    """The Gated DeltaNet cell's table of keys and values (32 slots, 30
    heads of 128, 2304 positions, bfloat16: 1.13 GB a layer; one layer
    here): the chip keeps it with ``head_dim`` minor, so a one-token
    write is a run, not a column, and stays the lanes' loop; and four
    lanes taken out by ``read_whole_lanes`` and put back by
    ``write_whole_lanes`` need the four lanes twice over, not the
    slices of the whole table that ``jnp.take`` brings (5.0 GB at the
    cell's four layers: PERF.md, PR 32)."""
    from mxtpu.kernels import kv_write
    from mxtpu.ndarray import rnn_impl
    shape = (1, 2, 32, 30, 2304, 128)
    held = rnn_impl._resident_layout(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert held.major_to_minor == (0, 1, 2, 3, 4, 5)

    def prefill(idx, table):
        lanes = idx.astype(jnp.int32)
        small = rnn_impl.read_whole_lanes(table, lanes, 2)
        return rnn_impl.write_whole_lanes(table, small + jnp.bfloat16(1),
                                          lanes, 2)

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    from mxtpu import analysis
    text, mem = analysis.compiled_artifact(
        prefill, sds(4), sds(*shape, dtype=jnp.bfloat16), donate_argnums=1)
    lane = 2 * 30 * 2304 * 128 * 2
    assert mem["alias_size_in_bytes"] == 32 * lane
    assert mem["temp_size_in_bytes"] <= 2.2 * 4 * lane, mem
    assert "mini-gather" not in text

    def decode(table, new, at):
        return rnn_impl._kv_cache_write_op(table, new, at, layer=0, plane=1)

    with kv_write.call_sites() as traced:
        text, mem = analysis.compiled_artifact(
            decode, sds(*shape, dtype=jnp.bfloat16),
            sds(32, 30, 1, 128), sds(32), donate_argnums=0)
    assert traced[0] == 0 and "tpu_custom_call" not in text
    assert mem["alias_size_in_bytes"] == 32 * lane
    assert mem["temp_size_in_bytes"] < lane, mem


@pytest.fixture
def mellum_stage(topo, one_chip, monkeypatch):
    """A ``GenerateRunner`` of the routed-experts cell's model at its
    published widths, cut to two layers (a sliding one and a full one),
    on the described chip (``_described_runner``)."""
    return _described_runner(
        monkeypatch, topo, one_chip, "mellum2_12b_a2_5b.json", 5,
        cut=slice(2, 4),
        spec=lambda net: net.state_spec(23, 8448, kv_dtype="bfloat16",
                                        max_chunk=256),
        counters=lambda net: net.counter_spec(),
        prompt_buckets=(128, 256), max_prefill_batch=1)


@pytest.mark.parametrize("bucket", [("decode", (24,)), ("prefill", (1, 256))],
                         ids=["decode", "prefill-1x256"])
def test_routed_experts_beside_a_ring_compile_for_v5e(
        bucket, mellum_stage, on_tpu, chip_layouts, no_persistent_cache):
    """The routed-experts cell's decode step (24 slots) and one prefill
    call (a row of 256) at the published widths — hidden 2,304, 32 query
    over 4 key/value heads of 128, 64 experts of 896 of which a token
    takes 8, a window of 1,024 on a ring of 1,280 beside a full table of
    8,448 positions, bfloat16 — two of the twelve layers, the model's own
    graph through ``GenerateRunner``'s own program.  The chip's compiler
    must take the grouped products as the grouped-matmul kernel (a dense
    product over all 64 experts would be eight times the work), alias the
    donated tables to the results, and need far less than a table of
    temporaries (a prefill row's float32 scores over the full table are
    277 MB); the prefill's write of 256 positions into the ring, which
    may straddle the wrap, compiles as one masked store."""
    from mxtpu import analysis
    r = mellum_stage
    fn = r._prefill_pure() if bucket[0] == "prefill" else r._decode_pure()
    structs = r._structs(bucket)
    text, mem = analysis.compiled_artifact(
        fn, *structs, r._param_structs, donate_argnums=(len(structs) - 1,))
    tables = sum(r.state_bytes().values())
    assert tables == 24 * 2 * 4 * (8448 + 1280) * 128 * 2
    assert mem["alias_size_in_bytes"] == tables
    assert mem["temp_size_in_bytes"] < \
        (0.05 if bucket[0] == "decode" else 1.0) * tables, mem
    grouped = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "/moe/experts/" in line]
    assert len(grouped) == 4 and "ragged-dot" not in text, \
        "the experts' products are not the grouped-matmul kernel"
    assert ("kv_ring_write" in text) == (bucket[0] == "prefill")
    for scope in ("window_attention", "cached_attention", "rope",
                  "moe/route", "moe/dispatch", "moe/experts",
                  "moe/combine"):
        assert "/" + scope + "/" in text, scope
