"""The Gated DeltaNet cell's benchmark files, on the CPU at a tiny size:
the reference against a NumPy loop written from the equations, the
counts against hand counts, and the driver's judge with a sound run,
both controls and a fault (``correct`` has to be able to fail, and the
driver has to refuse to print a line it cannot stand behind).
"""
import time

import numpy as np
import pytest

from benchmark import flops_olmo_hybrid as counts
from benchmark import harness, reference_olmo_hybrid, weights_olmo_hybrid
from benchmark.drivers import generate_olmo_hybrid  # noqa: F401

from test_correct import BENCH, Device, failing

TINY = {"name": "tiny_olmo_hybrid", "model_type": "olmo_hybrid",
        "vocab_size": 97, "hidden_size": 64, "intermediate_size": 128,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 4, "linear_num_key_heads": 4,
        "linear_num_value_heads": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "linear_chunk_size": 8,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "attention_bias": False, "rope_parameters": {"rope_theta": None},
        "param_dtype": "float32", "kv_cache_dtype": "float32"}
TINY_MIX = {
    "kind": "generate_olmo_hybrid", "lanes": 3, "kv_capacity": 32,
    "prompt_buckets": [4, 8], "warm_batch_rungs": [1, 2],
    "loop": "closed", "clients": 6, "ramp_s": 0.3, "drain_s": 30.0,
    "prompt_len": {"dist": "loguniform", "lo": 2, "hi": 20},
    "output_len": {"dist": "loguniform", "lo": 3, "hi": 12},
    "pool": 64, "check": {"requests": 24, "block": 4, "pad_to": 8},
    # the tiny float32 program reads 0 (its argmax IS the reference's);
    # a control that moves one token reads 1e-8 and more
    "limits": {"served_token_gap": None, "served_token_gap_mean": None,
               "served_token_gap_sq": 1e-10}}
CELL = "olmo-hybrid-7b-rag-backlog"


def context(seconds=1.5, trace=0, tmp_path=None, mix=TINY_MIX):
    return harness.Context({"name": CELL, "chips": 1}, TINY, mix,
                           2 ** 31 + 23, seconds, trace, time.perf_counter(),
                           trace_dir=str(tmp_path) if tmp_path else None)


def drive(tamper=None, **kw):
    from benchmark import run as run_mod
    return run_mod.run_cell(context(**kw), BENCH, Device(), tamper)


# -- the reference -----------------------------------------------------
def _numpy_forward(cfg, w, tokens):
    """The equations of ``reference_olmo_hybrid``'s docstring as loops
    over positions and heads, in float64."""
    w = {k: np.asarray(v.astype("float32"), np.float64) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    heads, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv, k = cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    dh = d // hq
    norm = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g
    silu = lambda x: x / (1.0 + np.exp(-x))
    softplus = lambda x: np.log1p(np.exp(x))
    unit = lambda x: x / np.sqrt(np.sum(x * x) + 1e-6)
    x = w["embed"][np.asarray(tokens)]
    t_len = x.shape[0]
    for i, kind in enumerate(cfg["layer_types"]):
        g = lambda name: w[f"l{i}.{name}"]
        if kind == "full_attention":
            q = norm(x @ g("q").T, g("q_norm"))
            key = norm(x @ g("k").T, g("k_norm"))
            val = x @ g("v").T
            out = np.zeros((t_len, d))
            for head in range(hq):
                cols = slice(head * dh, (head + 1) * dh)
                for t in range(t_len):
                    s = q[t, cols] @ key[:t + 1, cols].T / np.sqrt(dh)
                    pr = np.exp(s - s.max())
                    out[t, cols] = pr / pr.sum() @ val[:t + 1, cols]
            mixed = out @ g("o").T
        else:
            qkv = np.concatenate([x @ g("q").T, x @ g("k").T, x @ g("v").T],
                                 axis=-1)
            conv = np.zeros_like(qkv)
            for t in range(t_len):
                acc = np.zeros(qkv.shape[1])
                for j in range(k):
                    if t - (k - 1) + j >= 0:
                        acc += g("conv_w")[:, j] * qkv[t - (k - 1) + j]
                conv[t] = silu(acc)
            q, key, val = np.split(conv, [heads * dk, 2 * heads * dk], axis=-1)
            beta = 1.0 / (1.0 + np.exp(-(x @ g("b").T)))
            if cfg["linear_allow_neg_eigval"]:
                beta = 2.0 * beta
            decay = np.exp(-np.exp(g("a_log"))
                           * softplus(x @ g("a").T + g("dt_bias")))
            out = np.zeros((t_len, heads * dv))
            for head in range(heads):
                state = np.zeros((dk, dv))
                for t in range(t_len):
                    q_t = unit(q[t, head * dk:(head + 1) * dk]) / np.sqrt(dk)
                    k_t = unit(key[t, head * dk:(head + 1) * dk])
                    v_t = val[t, head * dv:(head + 1) * dv]
                    state = decay[t, head] * state
                    r_t = v_t - state.T @ k_t
                    state = state + beta[t, head] * np.outer(k_t, r_t)
                    out[t, head * dv:(head + 1) * dv] = \
                        norm(state.T @ q_t, g("o_norm"))
            mixed = (out * silu(x @ g("g").T)) @ g("o").T
        x = x + norm(mixed, g("norm1"))
        gate, up = np.split(x @ g("mlp_in").T, 2, axis=-1)
        x = x + norm((silu(gate) * up) @ g("mlp_out").T, g("norm2"))
    return norm(x, w["final_norm"]) @ w["head"].T


def test_reference_follows_the_equations():
    w = weights_olmo_hybrid.make(TINY, 2 ** 31 + 9)
    tokens = np.random.default_rng(3).integers(1, 97, 19)
    want = _numpy_forward(TINY, w, tokens)
    got = np.asarray(reference_olmo_hybrid.forward(TINY, w, tokens[None]))[0]
    assert np.abs(want).max() > 0.1
    # float32 against float64 through eight layers of output norms, unit
    # vectors and r = v - S^T k: rounding reads 3e-5; a dropped term 1e-2
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_weights_follow_the_stated_initialiser():
    w = weights_olmo_hybrid.make(TINY, 11)
    again = weights_olmo_hybrid.make(TINY, 11)
    other = weights_olmo_hybrid.make(TINY, 12)
    assert list(w) == list(weights_olmo_hybrid.leaf_shapes(TINY))
    for name, shape in weights_olmo_hybrid.leaf_shapes(TINY).items():
        assert w[name].shape == shape and str(w[name].dtype) == "bfloat16"
        assert (np.asarray(w[name]) == np.asarray(again[name])).all()
    assert (np.asarray(w["l0.q"]) != np.asarray(other["l0.q"])).any()
    f = lambda a: np.asarray(a.astype("float32"))
    for name in ("l1.o_norm", "l3.q_norm", "l4.norm1", "final_norm"):
        assert (f(w[name]) == 1).all()
    a = np.exp(f(w["l0.a_log"]))
    assert (a >= 0.99).all() and (a <= 16.1).all()
    dt = np.log1p(np.exp(f(w["l0.dt_bias"])))
    assert (dt > 9e-4).all() and (dt < 0.11).all()
    assert np.abs(f(w["l0.conv_w"])).max() <= 0.5
    assert 0.015 < f(w["l0.v"]).std() < 0.025
    assert 0.015 < f(w["l0.b"]).std() < 0.025
    assert f(w["l0.a"]).std() < 0.025 / 8
    # about half of the positions correct with beta > 1
    tokens = np.random.default_rng(5).integers(1, 97, (2, 24))
    share = reference_olmo_hybrid.beta_share_above_one(TINY, w, tokens)
    assert 0.3 < share < 0.7
    off = dict(TINY, linear_allow_neg_eigval=False)
    assert reference_olmo_hybrid.beta_share_above_one(off, w, tokens) == 0.0


def test_reference_controls_differ_from_it():
    w = weights_olmo_hybrid.make(TINY, 5)
    tokens = np.random.default_rng(4).integers(1, 97, (2, 24))
    exact = np.asarray(reference_olmo_hybrid.forward(TINY, w, tokens))
    # at hidden 64 the output norms carry fp8's rounding of every
    # product at full size from layer to layer: it moves the logits by
    # as much as they are; the rounded state moves them by a tenth
    for cast, most in (("delta_bfloat16", 0.2), ("fp8", 2.0)):
        low = np.asarray(reference_olmo_hybrid.forward(TINY, w, tokens,
                                                       cast=cast))
        assert np.isfinite(low).all()
        assert 1e-5 < np.abs(low - exact).max() < most
    with pytest.raises(ValueError):
        reference_olmo_hybrid.forward(TINY, w, tokens, cast="bfloat16")


def test_token_gaps_are_in_the_rows_order():
    w = weights_olmo_hybrid.make(TINY, 5)
    rng = np.random.default_rng(6)
    rows = [(rng.integers(1, 97, p).tolist(), rng.integers(1, 97, n).tolist())
            for p, n in ((9, 3), (2, 7), (14, 5), (5, 2), (3, 3))]
    got = reference_olmo_hybrid.token_gaps_of(TINY, w, rows, (None,),
                                              block=2, pad_to=8)[None]
    assert [len(g) for g in got] == [3, 7, 5, 2, 3]
    for (prompt, served), gaps in zip(rows, got):
        logits = np.asarray(reference_olmo_hybrid.forward(
            TINY, w, np.asarray(prompt + served)[None]))[0]
        for j, tok in enumerate(served):
            at = logits[len(prompt) - 1 + j]
            assert gaps[j] == pytest.approx(at.max() - at[tok], abs=1e-6)


# -- the counts --------------------------------------------------------
def test_flops_olmo_hybrid_against_hand_counts():
    cfg = harness.load_json(harness.os.path.join(
        harness.HERE, "configs", "olmo_hybrid_7b.json"))
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 32
    mixer = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 \
        + 11520 * 4 + 2 * 30 + 192
    assert mixer == 88_750_332
    mlp = 3 * 3840 * 11008
    linear, full = mixer + mlp + 2 * 3840, 4 * 3840 ** 2 + 2 * 3840 + mlp \
        + 2 * 3840
    assert (linear, full) == (215_570_172, 185_809_920)
    total = 12 * linear + 4 * full + 2 * 100352 * 3840 + 3840
    assert total == 4_100_788_944
    assert counts.param_count(cfg) == total
    gemm = 12 * (mixer - 11520 * 4 - 60 - 192 + mlp) \
        + 4 * (4 * 3840 ** 2 + mlp) + 100352 * 3840
    assert counts.gemm_params(cfg) == gemm
    state = 30 * 96 * 192
    assert counts.state_bytes_per_lane(cfg) == 12 * (state * 4 + 3 * 11520 * 4)
    assert counts.kv_bytes_per_token(cfg) == 4 * 2 * 30 * 128 * 2 == 61_440
    assert counts.lane_bytes(cfg, 2304) == 2304 * 61_440 + 12 * (
        state * 4 + 3 * 11520 * 4)
    scan = 12 * (7 * state + 2 * 4 * 11520)
    assert counts.scan_flops_per_token(cfg) == scan
    assert counts.decode_flops_per_token(cfg, 1000) == \
        2 * gemm + scan + 4 * 4 * 1000 * 3840
    assert counts.forward_flops_per_token(cfg, 256) == \
        2 * gemm + scan + 4 * 4 * 256 * 256 * 3840 * 0.5 * (1 + 1 / 256) / 256
    # a step of 31 lanes that hold 25,000 positions between them: the
    # embedding's rows are looked up, not read whole
    weights = 2 * (total - 100352 * 3840)
    assert counts.decode_step_bytes(cfg, 31, 25_000) == \
        weights + 2 * 31 * 12 * (state * 4 + 3 * 11520 * 4) + 25_000 * 61_440
    assert counts.decode_step_flops(cfg, 31, 25_000) == \
        31 * (2 * gemm + scan) + 4 * 4 * 25_000 * 3840
    assert counts.state_update_bytes(cfg, 32) == 2 * 32 * 12 * state * 4
    assert counts.state_update_flops(cfg, 32) == 7 * 32 * 12 * state
    # the step is bound by memory: 11 GB at 819 GB/s, 13 ms
    least, bound = counts.roofline_seconds(
        counts.decode_step_flops(cfg, 31, 25_000),
        counts.decode_step_bytes(cfg, 31, 25_000), 197e12, 819e9)
    assert bound == "memory" and 0.012 < least < 0.015
    # a prefill call of 4 rows x 256 positions: 4 chunks of 64 a row
    per_chunk = 4 * 64 * 64 * 96 + 64 * 64 * (192 + 96) \
        + 2 * 64 * 64 * 192 + 6 * 64 * 96 * 192
    assert counts.chunk_flops(cfg, 4, 256) == 12 * 4 * 4 * 30 * per_chunk
    assert counts.chunk_flops(cfg, 1, 65) == 12 * 2 * 30 * per_chunk
    assert counts.chunk_bytes(cfg, 4, 256) == 12 * 4 * (
        256 * (2 * 2880 + 2 * 5760 + 60) * 4 + 2 * state * 4)


def test_the_cells_files_agree_with_each_other():
    cell, cfg, mix, bench = harness.load_cell(CELL)
    assert cell["chips"] == 1 and mix["kind"] == "generate_olmo_hybrid"
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 4
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 16
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert conf["reduced"] == cfg["reduced"]
    assert mix["kv_capacity"] >= mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert mix["kv_capacity"] % max(mix["prompt_buckets"]) == 0
    assert mix["clients"] > mix["lanes"]
    assert set(mix["limits"]) == {"served_token_gap", "served_token_gap_mean",
                                  "served_token_gap_sq"}
    assert mix["limits"]["served_token_gap_sq"] is not None
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == ["delta_chunk_roofline", "delta_device_share_pct",
                            "delta_hybrid_decode_step_roofline",
                            "delta_state_update_roofline"]
    for name in mine:
        assert harness.os.path.exists(harness.os.path.join(
            harness.HERE, "metrics", name + ".py"))
    serve = {m["name"]: m for m in bench["end_to_end"]}["serve_tokens_per_s"]
    assert serve["workloads"][-1] == CELL


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
    assert {"delta_hybrid_decode_step_roofline", "delta_device_share_pct",
            "delta_state_update_roofline", "delta_chunk_roofline",
            "serve_step_mfu", "prefill_call_ms",
            "lane_occupancy_pct"} <= want
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


def test_controls_go_through_the_runs_own_judge():
    """``run.py --readings … --control delta_bfloat16,fp8``: the state
    table kept in bfloat16, and fp8 matrix products, each in the
    program's place on the same prompts and tokens.  fp8 comes out not
    correct on every seed; the rounded state is read through the same
    judge (what it reads at the published widths is in PERF.md)."""
    import argparse
    from benchmark import run as run_mod
    seeds = [5, 2 ** 31 + 6]
    args = argparse.Namespace(readings=",".join(map(str, seeds)),
                              control="delta_bfloat16,fp8", fault=None)
    got = run_mod.read_seeds(context(), args)["readings"]
    for seed in seeds:
        one = got[str(seed)]
        assert one["program"]["correct"] is True
        assert one["control:fp8"]["correct"] is False
        assert failing(one["control:fp8"]) == {"served_token_gap_sq"}
        assert set(one["control:delta_bfloat16"]["compared"]) == \
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


def test_a_program_without_the_mixer_fails_at_the_drivers_import():
    """The parent commit under this PR's benchmark files: the driver's
    first import names what only this change has."""
    import ast
    path = harness.os.path.join(harness.HERE, "drivers",
                                "generate_olmo_hybrid.py")
    first = next(n for n in ast.parse(open(path).read()).body
                 if isinstance(n, (ast.Import, ast.ImportFrom)))
    assert first.module == "mxtpu.models.hybrid"
    assert [a.name for a in first.names] == ["GatedDeltaNetMixer"]
