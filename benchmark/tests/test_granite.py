"""The hybrid state-space cell's benchmark files, on the CPU at a tiny
size: the reference against a NumPy loop written from the equations,
the counts against hand counts, and the driver's judge with a sound run,
both controls and a fault (``correct`` has to be able to fail, and the
driver has to refuse to print a line it cannot stand behind).
"""
import time

import numpy as np
import pytest

from benchmark import flops_granite, harness, reference_granite
from benchmark import weights_granite
from benchmark.drivers import generate_hybrid

from test_correct import BENCH, Device, failing

TINY = {"name": "tiny_hybrid", "vocab_size": 97, "hidden_size": 64,
        "shared_intermediate_size": 128, "intermediate_size": 128,
        "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
        "mamba_n_groups": 1, "mamba_expand": 1, "rms_norm_eps": 1e-5,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.0625, "logits_scaling": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "param_dtype": "float32", "kv_cache_dtype": "float32"}
TINY_MIX = {
    "kind": "generate_hybrid", "lanes": 3, "kv_capacity": 32,
    "prompt_buckets": [4, 8], "warm_batch_rungs": [1, 2],
    "loop": "closed", "clients": 6, "ramp_s": 0.3, "drain_s": 30.0,
    "prompt_len": {"dist": "loguniform", "lo": 2, "hi": 20},
    "output_len": {"dist": "loguniform", "lo": 3, "hi": 12},
    "pool": 64, "check": {"requests": 24, "block": 4},
    # the tiny float32 program reads 0 (its argmax IS the reference's);
    # a control that moves one token reads 1e-8 and more
    "limits": {"served_token_gap": None, "served_token_gap_mean": None,
               "served_token_gap_sq": 1e-10}}
CELL = "granite-4.0-h-micro-chat-backlog"


def context(seconds=1.5, trace=0, tmp_path=None, mix=TINY_MIX):
    return harness.Context({"name": CELL, "chips": 1}, TINY, mix,
                           2 ** 31 + 21, seconds, trace, time.perf_counter(),
                           trace_dir=str(tmp_path) if tmp_path else None)


def drive(tamper=None, **kw):
    from benchmark import run as run_mod
    return run_mod.run_cell(context(**kw), BENCH, Device(), tamper)


# -- the reference -----------------------------------------------------
def _numpy_forward(cfg, w, tokens):
    """The equations of ``reference_granite``'s docstring as loops over
    positions, heads and channels, in float64."""
    w = {k: np.asarray(v.astype("float32"), np.float64) for k, v in w.items()}
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    d, heads, p = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, inner = d // hq, heads * p
    norm = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g
    silu = lambda x: x / (1.0 + np.exp(-x))
    softplus = lambda x: np.log1p(np.exp(x))
    x = w["embed"][np.asarray(tokens)] * cfg["embedding_multiplier"]
    t_len = x.shape[0]
    for i, kind in enumerate(cfg["layer_types"]):
        g = lambda name: w[f"l{i}.{name}"]
        h = norm(x, g("norm1"))
        if kind == "attention":
            q, key, val = h @ g("q").T, h @ g("k").T, h @ g("v").T
            out = np.zeros((t_len, hq * dh))
            for head in range(hq):
                kv = head // (hq // hk)
                qs = q[:, head * dh:(head + 1) * dh]
                ks = key[:, kv * dh:(kv + 1) * dh]
                vs = val[:, kv * dh:(kv + 1) * dh]
                for t in range(t_len):
                    s = qs[t] @ ks[:t + 1].T * cfg["attention_multiplier"]
                    pr = np.exp(s - s.max())
                    out[t, head * dh:(head + 1) * dh] = pr / pr.sum() @ vs[:t + 1]
            mixed = out @ g("o").T
        else:
            zxd = h @ g("in_proj").T
            z, xbc, dt = np.split(zxd, [inner, 2 * inner + 2 * n], axis=-1)
            conv = np.zeros_like(xbc)
            for t in range(t_len):
                acc = g("conv_b").copy()
                for j in range(k):
                    if t - (k - 1) + j >= 0:
                        acc += g("conv_w")[:, j] * xbc[t - (k - 1) + j]
                conv[t] = silu(acc)
            xs, bm, cm = np.split(conv, [inner, inner + n], axis=-1)
            dt = softplus(dt + g("dt_bias"))
            a = -np.exp(g("a_log"))
            y = np.zeros((t_len, inner))
            for head in range(heads):
                state = np.zeros((p, n))
                for t in range(t_len):
                    xh = xs[t, head * p:(head + 1) * p]
                    state = np.exp(dt[t, head] * a[head]) * state \
                        + dt[t, head] * np.outer(xh, bm[t])
                    y[t, head * p:(head + 1) * p] = \
                        state @ cm[t] + g("d_skip")[head] * xh
            mixed = norm(y * silu(z), g("ssm_norm")) @ g("out_proj").T
        x = x + r * mixed
        gate, val = np.split(norm(x, g("norm2")) @ g("mlp_in").T, 2, axis=-1)
        x = x + r * ((silu(gate) * val) @ g("mlp_out").T)
    return norm(x, w["final_norm"]) @ w["embed"].T / cfg["logits_scaling"]


def test_reference_follows_the_equations():
    w = weights_granite.make(TINY, 2 ** 31 + 9)
    tokens = np.random.default_rng(3).integers(1, 97, 19)
    want = _numpy_forward(TINY, w, tokens)
    got = np.asarray(reference_granite.forward(TINY, w, tokens[None]))[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_weights_follow_the_published_initialiser():
    w = weights_granite.make(TINY, 11)
    again = weights_granite.make(TINY, 11)
    other = weights_granite.make(TINY, 12)
    assert list(w) == list(weights_granite.leaf_shapes(TINY))
    for name, shape in weights_granite.leaf_shapes(TINY).items():
        assert w[name].shape == shape and str(w[name].dtype) == "bfloat16"
        assert (np.asarray(w[name]) == np.asarray(again[name])).all()
    assert (np.asarray(w["l0.in_proj"]) != np.asarray(other["l0.in_proj"])).any()
    f = lambda a: np.asarray(a.astype("float32"))
    assert (f(w["l1.d_skip"]) == 1).all() and (f(w["l4.norm1"]) == 1).all()
    a = np.exp(f(w["l0.a_log"]))
    assert (a >= 0.99).all() and (a <= 16.1).all()
    dt = np.log1p(np.exp(f(w["l0.dt_bias"])))
    assert (dt > 9e-4).all() and (dt < 0.11).all()
    assert np.abs(f(w["l0.conv_w"])).max() <= 0.5
    assert 0.015 < f(w["l0.in_proj"]).std() < 0.025


@pytest.fixture
def long_memory(monkeypatch):
    """At the tiny size the published initialiser leaves the recurrent
    state a millionth of the logits, and a control that rounds it has
    nothing to move: a matrix of std 0.02 has a gain of 0.16 at hidden
    64 where the published widths give 0.9 to 1.8, and dt of 1e-3 to
    1e-1 sums over a state of 16, not 128.  Projections five times as
    large and steps twenty times as long give the state the weight it
    has at the published widths; the program and the reference are
    given the same leaves, as always."""
    import jax.numpy as jnp
    make = weights_granite.make

    def longer(cfg, seed):
        w = make(cfg, seed)
        f32 = lambda v: v.astype(jnp.float32)
        return {k: (f32(v) + 3.0).astype(v.dtype) if k.endswith("dt_bias")
                else (f32(v) * 5.0).astype(v.dtype)
                if v.ndim == 2 and k != "embed" and "conv" not in k else v
                for k, v in w.items()}

    monkeypatch.setattr(weights_granite, "make", longer)


def test_reference_controls_differ_from_it(long_memory):
    w = weights_granite.make(TINY, 5)
    tokens = np.random.default_rng(4).integers(1, 97, (2, 24))
    exact = np.asarray(reference_granite.forward(TINY, w, tokens))
    for cast in ("ssm_bfloat16", "fp8"):
        low = np.asarray(reference_granite.forward(TINY, w, tokens, cast=cast))
        assert 1e-5 < np.abs(low - exact).max() < 0.1
    with pytest.raises(ValueError):
        reference_granite.forward(TINY, w, tokens, cast="bfloat16")


def test_token_gaps_are_in_the_rows_order():
    w = weights_granite.make(TINY, 5)
    rng = np.random.default_rng(6)
    rows = [(rng.integers(1, 97, p).tolist(), rng.integers(1, 97, n).tolist())
            for p, n in ((9, 3), (2, 7), (14, 5), (5, 2), (3, 3))]
    got = reference_granite.token_gaps(TINY, w, rows, block=2, pad_to=8)
    assert [len(g) for g in got] == [3, 7, 5, 2, 3]
    for (prompt, served), gaps in zip(rows, got):
        logits = np.asarray(reference_granite.forward(
            TINY, w, np.asarray(prompt + served)[None]))[0]
        for j, tok in enumerate(served):
            at = logits[len(prompt) - 1 + j]
            assert gaps[j] == pytest.approx(at.max() - at[tok], abs=1e-6)


# -- the counts --------------------------------------------------------
def test_flops_granite_against_hand_counts():
    cfg = harness.load_json(harness.os.path.join(
        harness.HERE, "configs", "granite_4_0_h_micro.json"))
    assert cfg["reduced"] == [] and cfg["departures"] == []
    mamba = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    mlp = 2048 * 16384 + 8192 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    total = 36 * (mamba + mlp + 4096) + 4 * (attn + mlp + 4096) \
        + 100352 * 2048 + 2048
    assert total == 3_191_396_096
    assert flops_granite.param_count(cfg) == total
    gemm = 36 * (2048 * 8512 + 4096 * 2048 + mlp) + 4 * (attn + mlp) \
        + 100352 * 2048
    assert flops_granite.gemm_params(cfg) == gemm
    assert flops_granite.state_bytes_per_lane(cfg) == \
        36 * (64 * 64 * 128 * 4 + 3 * 4352 * 4)
    assert flops_granite.kv_bytes_per_token(cfg) == 4 * 2 * 8 * 64 * 2 == 8192
    scan = 36 * (5 * 4096 * 128 + 2 * 4 * 4352)
    assert flops_granite.scan_flops_per_token(cfg) == scan
    assert flops_granite.decode_flops_per_token(cfg, 1000) == \
        2 * gemm + scan + 4 * 4 * 1000 * 2048
    assert flops_granite.forward_flops_per_token(cfg, 256) == \
        2 * gemm + scan + 4 * 4 * 256 * 256 * 2048 * 0.5 * (1 + 1 / 256) / 256
    # a step of 47 lanes that hold 30,000 positions between them
    assert flops_granite.decode_step_bytes(cfg, 47, 30_000) == \
        2 * total + 2 * 47 * 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 4) \
        + 30_000 * 8192
    assert flops_granite.decode_step_flops(cfg, 47, 30_000) == \
        47 * (2 * gemm + scan) + 4 * 4 * 30_000 * 2048
    assert flops_granite.state_update_bytes(cfg, 48) == \
        2 * 48 * 36 * 64 * 64 * 128 * 4
    # the step is bound by memory: 14 GB at 819 GB/s, 17 ms
    least, bound = flops_granite.roofline_seconds(
        flops_granite.decode_step_flops(cfg, 47, 30_000),
        flops_granite.decode_step_bytes(cfg, 47, 30_000), 197e12, 819e9)
    assert bound == "memory" and 0.016 < least < 0.018


def test_the_cells_files_agree_with_each_other():
    cell, cfg, mix, bench = harness.load_cell(CELL)
    assert cell["chips"] == 1 and mix["kind"] == "generate_hybrid"
    assert cfg["layer_types"].count("mamba") == 36
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert mix["kv_capacity"] >= mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert mix["kv_capacity"] % max(mix["prompt_buckets"]) == 0
    assert mix["clients"] > mix["lanes"]
    assert set(mix["limits"]) == {"served_token_gap", "served_token_gap_mean",
                                  "served_token_gap_sq"}
    assert mix["limits"]["served_token_gap_sq"] is not None
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            assert harness.os.path.exists(harness.os.path.join(
                harness.HERE, "metrics", m["name"] + ".py"))


# -- the driver and its judge ------------------------------------------
def test_run_is_correct_and_reports_the_cells_metrics(monkeypatch):
    res = drive()
    assert res["correct"] is True and res["attempted"] > 10
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    monkeypatch.setattr(harness, "peaks_for", lambda kind: harness.load_json(
        harness.os.path.join(harness.HERE, "peaks.json"))["TPU v5 lite"])
    # the readers of the program's spans look where a run leaves its
    # trace (an ignored directory of the checkout), nowhere else
    res = drive(trace=1, tmp_path=harness.os.path.join(harness.HERE, ".trace",
                                                       CELL))
    assert res["correct"] is True
    from benchmark import run as run_mod
    want = {m["name"] for m in run_mod.metrics_of(
        BENCH, "per_layer", CELL, {"serve_tokens_per_s", "setup_s"})}
    assert {"hybrid_decode_step_roofline", "ssm_device_share_pct",
            "ssm_state_update_roofline", "serve_step_mfu",
            "prefill_call_ms", "lane_occupancy_pct"} <= want
    assert set(res["metrics"]) == want


def test_token_altered_comes_out_not_correct(monkeypatch):
    from mxtpu.serving import generate as prog_generate
    real = prog_generate.sample_token

    def altered(logits, *, position, seed=0, top_k=1):
        if position % 5 == 0:
            return int(np.argsort(np.asarray(logits).reshape(-1))[-2])
        return real(logits, position=position, seed=seed, top_k=top_k)

    monkeypatch.setattr(prog_generate, "sample_token", altered)
    res = drive()
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["served_token_gap_sq"]["ok"] is False


def test_controls_go_through_the_runs_own_judge(long_memory):
    """``run.py --readings … --control ssm_bfloat16,fp8``: the state
    table kept in bfloat16, and fp8 matrix products, each in the
    program's place on the same prompts and tokens.  fp8 comes out not
    correct on every seed.  The rounded state is read through the same
    judge, but over 32 positions and 97 words it moves no first choice:
    what it reads at the published widths is in PERF.md."""
    import argparse
    from benchmark import run as run_mod
    seeds = [5, 2 ** 31 + 6]
    args = argparse.Namespace(readings=",".join(map(str, seeds)),
                              control="ssm_bfloat16,fp8", fault=None)
    got = run_mod.read_seeds(context(), args)["readings"]
    for seed in seeds:
        one = got[str(seed)]
        assert one["program"]["correct"] is True
        assert one["control:fp8"]["correct"] is False
        assert failing(one["control:fp8"]) == {"served_token_gap_sq"}
        assert set(one["control:ssm_bfloat16"]["compared"]) == \
            set(one["program"]["compared"])


def test_no_line_without_a_finished_request():
    """Nothing due in the window finishes (every request is refused):
    the driver exits, it does not print ``attempted: 0`` or a NaN."""
    def refuse(submit):
        def call(prompt, max_tokens, on_token):
            raise RuntimeError("refused")
        return call
    with pytest.raises(SystemExit) as stop:
        drive(tamper=refuse)
    assert "no result line" in str(stop.value.code)


def test_a_shared_constant_does_not_vote_for_its_scope():
    """XLA shares one constant among scopes under the name of whichever
    wrote it first; the hybrid readers take the name off constants
    before a fusion is judged, so the state update's fusion is wholly
    the state update's."""
    from benchmark import program_spans, region_ops
    hlo = """
%fused_update (p0: f32[4,8], p1: f32[8]) -> f32[4,8] {
  %p0 = f32[4,8] parameter(0)
  %p1 = f32[8] parameter(1)
  %zero = s32[] constant(0), metadata={op_name="jit(fn)/ssm/conv/gather"}
  %mul = f32[4,8] multiply(%p0, %p0), metadata={op_name="jit(fn)/ssm/state_update/mul"}
  ROOT %dus = f32[4,8] dynamic-update-slice(%p0, %mul, %zero, %zero), metadata={op_name="jit(fn)/ssm/state_update/scatter"}
}

ENTRY %main (a: f32[4,8], b: f32[8]) -> f32[4,8] {
  %a = f32[4,8] parameter(0)
  %b = f32[8] parameter(1)
  ROOT %update_fusion = f32[4,8] fusion(%a, %b), kind=kLoop, calls=%fused_update, metadata={op_name="jit(fn)/ssm/state_update/scatter"}
}
"""
    inside, mixed = program_spans.ops_by_scope(hlo, "ssm/state_update")
    assert "update_fusion" in mixed
    inside, mixed = program_spans.ops_by_scope(
        region_ops._constants_unnamed(hlo), "ssm/state_update")
    assert "update_fusion" in inside and "update_fusion" not in mixed
