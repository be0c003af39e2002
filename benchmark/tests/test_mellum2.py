"""The routed-experts / sliding-window cell's benchmark files, on the CPU
at a tiny size: the reference against a NumPy loop written from the
equations, its rotary tables against numbers worked out here, the counts
against hand counts, and the driver's judge with a sound run, the controls and
a fault (``correct`` has to be able to fail, and the driver has to
refuse to print a line it cannot stand behind).
"""
import time

import numpy as np
import pytest

from benchmark import flops_mellum2 as counts
from benchmark import harness, reference_mellum2, weights_mellum2
from benchmark.drivers import generate_mellum2

from test_correct import BENCH, Device, failing

YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 4,
        "beta_slow": 1, "attention_factor": 1.1386}
TINY = {"name": "tiny_mellum", "model_type": "mellum", "vocab_size": 97,
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 4,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 4, "moe_intermediate_size": 32,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "sliding_window": 8, "use_sliding_window": True,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "attention_bias": False,
        "rope_parameters": {
            "full_attention": YARN,
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}},
        "param_dtype": "float32", "kv_cache_dtype": "float32"}
TINY_MIX = {
    "kind": "generate_mellum2", "lanes": 3, "kv_capacity": 40,
    "prompt_buckets": [4], "warm_batch_rungs": [1, 2],
    "loop": "closed", "clients": 6, "ramp_s": 0.3, "drain_s": 30.0,
    # every prompt past the window of 8, the longest past two wraps of
    # the ring of 12
    "prompt_len": {"dist": "loguniform", "lo": 9, "hi": 28},
    "output_len": {"dist": "loguniform", "lo": 3, "hi": 12},
    "pool": 16, "check": {"requests": 12, "block": 2, "pad_to": 8},
    # the tiny float32 program reads 0 (its argmax IS the reference's);
    # a control that moves one token reads 1e-8 and more; its logits lie
    # 1e-6 of the reference's away, a control's 1e-2 and more
    "limits": {"served_token_gap": None, "served_token_gap_mean": None,
               "served_token_gap_sq": 1e-10, "served_logit_err": 1e-4,
               "served_logit_err_q1": 1e-4}}
CELL = "mellum2-12b-a2.5b-code-context-backlog"


def context(seconds=1.5, trace=0, tmp_path=None, mix=TINY_MIX):
    return harness.Context({"name": CELL, "chips": 1}, TINY, mix,
                           2 ** 31 + 23, seconds, trace, time.perf_counter(),
                           trace_dir=str(tmp_path) if tmp_path else None)


def drive(tamper=None, **kw):
    from benchmark import run as run_mod
    return run_mod.run_cell(context(**kw), BENCH, Device(), tamper)


# -- the reference -----------------------------------------------------
def _yarn_by_hand():
    """The tiny full-attention table, step by step: dim(r) = 16 ln(16 /
    (2 pi r)) / (2 ln 10000); dim(4) < 0 so lo = 0; dim(1) = 0.81 so
    hi = 1; the ramp is 0 at j = 0 and 1 from j = 1 on."""
    e = 10000.0 ** (-2.0 * np.arange(8) / 16)
    return np.concatenate([e[:1], e[1:] / 4.0])


def test_rotary_tables_against_numbers_worked_out_here():
    f, a = reference_mellum2.rotary_table(TINY, "sliding_attention")
    np.testing.assert_allclose(f, 10000.0 ** (-np.arange(8) / 8.0), rtol=1e-12)
    assert a == 1.0
    f, a = reference_mellum2.rotary_table(TINY, "full_attention")
    np.testing.assert_allclose(f, _yarn_by_hand(), rtol=1e-12)
    assert a == 1.1386
    # the published table: theta 500000, 128 a head, factor 16 over 8192;
    # dim(32) = 128 ln(8192 / (64 pi)) / (2 ln 500000) = 18.08 -> lo 18,
    # dim(1) = 128 ln(8192 / (2 pi)) / (2 ln 500000) = 34.98 -> hi 35
    cfg = harness.load_json(harness.os.path.join(
        harness.HERE, "configs", "mellum2_12b_a2_5b.json"))
    f, a = reference_mellum2.rotary_table(cfg, "full_attention")
    e = 500000.0 ** (-np.arange(64) / 64.0)
    assert a == 1.2772588722239782
    np.testing.assert_allclose(f[:19], e[:19], rtol=1e-12)
    np.testing.assert_allclose(f[35:], e[35:] / 16.0, rtol=1e-12)
    mid = (26 - 18) / (35 - 18)
    np.testing.assert_allclose(f[26], e[26] / 16 * mid + e[26] * (1 - mid),
                               rtol=1e-12)


def _numpy_forward(cfg, w, tokens, top7=False, window_off=False):
    """The equations of ``reference_mellum2``'s docstring as loops over
    positions, heads and a token's experts, in float64."""
    w = {k: np.asarray(v.astype("float32"), np.float64) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    hq, hk, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    top_k, window = cfg["num_experts_per_tok"], cfg["sliding_window"]
    norm = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g
    silu = lambda x: x / (1.0 + np.exp(-x))
    tables = {"sliding_attention": (10000.0 ** (-np.arange(8) / 8.0), 1.0),
              "full_attention": (_yarn_by_hand(), 1.1386)}

    def rot(u, p, freq, a):
        c, s = np.cos(p * freq) * a, np.sin(p * freq) * a
        u1, u2 = u[:dh // 2], u[dh // 2:]
        return np.concatenate([u1 * c - u2 * s, u2 * c + u1 * s])

    x = w["embed"][np.asarray(tokens)]
    t_len = x.shape[0]
    for i, kind in enumerate(cfg["layer_types"]):
        g = lambda name: w[f"l{i}.{name}"]
        freq, a = tables[kind]
        h = norm(x, g("norm1"))
        q, key, val = h @ g("q").T, h @ g("k").T, h @ g("v").T
        out = np.zeros((t_len, hq * dh))
        for head in range(hq):
            kv = head // (hq // hk)
            qs = [rot(norm(q[t, head * dh:(head + 1) * dh], g("q_norm")),
                      t, freq, a) for t in range(t_len)]
            ks = [rot(norm(key[t, kv * dh:(kv + 1) * dh], g("k_norm")),
                      t, freq, a) for t in range(t_len)]
            for t in range(t_len):
                lo = 0 if kind == "full_attention" or window_off \
                    else max(0, t - window + 1)
                s = np.array([qs[t] @ ks[j] for j in range(lo, t + 1)]) \
                    / np.sqrt(dh)
                pr = np.exp(s - s.max())
                out[t, head * dh:(head + 1) * dh] = \
                    pr / pr.sum() @ val[lo:t + 1, kv * dh:(kv + 1) * dh]
        x = x + out @ g("o").T
        h = norm(x, g("norm2"))
        logits = h @ g("router")
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        add = np.zeros_like(x)
        for t in range(t_len):
            chosen = np.argsort(-probs[t], kind="stable")[:top_k]
            total = probs[t, chosen].sum()
            for e in chosen[:top_k - 1] if top7 else chosen:
                gate, up = np.split(h[t] @ g("w_in")[e], 2)
                add[t] += probs[t, e] / total \
                    * ((silu(gate) * up) @ g("w_out")[e])
        x = x + add
    return norm(x, w["final_norm"]) @ w["head"].T


def test_reference_follows_the_equations():
    w = weights_mellum2.make(TINY, 2 ** 31 + 9)
    tokens = np.random.default_rng(3).integers(1, 97, 24)
    want = _numpy_forward(TINY, w, tokens)
    got = np.asarray(reference_mellum2.forward(TINY, w, tokens[None]))[0]
    assert np.abs(want).max() > 0.1
    # float32 against float64 through four layers: rounding reads 1e-6;
    # a dropped term (an expert, the window, the rotation) 1e-3 and more
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    for cast, kw in (("top7", {"top7": True}),
                     ("window_off", {"window_off": True})):
        low = np.asarray(reference_mellum2.forward(TINY, w, tokens[None],
                                                   cast=cast))[0]
        np.testing.assert_allclose(low, _numpy_forward(TINY, w, tokens, **kw),
                                   atol=2e-5, rtol=0)


def test_weights_follow_the_stated_initialiser():
    w = weights_mellum2.make(TINY, 11)
    again = weights_mellum2.make(TINY, 11)
    other = weights_mellum2.make(TINY, 12)
    assert list(w) == list(weights_mellum2.leaf_shapes(TINY))
    for name, shape in weights_mellum2.leaf_shapes(TINY).items():
        assert w[name].shape == shape and str(w[name].dtype) == "bfloat16"
        assert (np.asarray(w[name]) == np.asarray(again[name])).all()
    assert (np.asarray(w["l0.q"]) != np.asarray(other["l0.q"])).any()
    f = lambda a: np.asarray(a.astype("float32"))
    for name in ("l1.q_norm", "l3.k_norm", "l2.norm1", "l0.norm2",
                 "final_norm"):
        assert (f(w[name]) == 1).all()
    for name in ("l0.v", "l1.router", "l2.w_in", "l3.w_out", "head"):
        assert 0.015 < f(w[name]).std() < 0.025
    # a leaf depends on the seed and its place alone: a deeper cut holds
    # the same first layers
    deeper = weights_mellum2.make(
        dict(TINY, layer_types=TINY["layer_types"] * 2), 11)
    assert (np.asarray(deeper["l2.w_in"]) == np.asarray(w["l2.w_in"])).all()
    assert (np.asarray(deeper["head"]) == np.asarray(w["head"])).all()


def test_the_programs_leaves_are_the_references():
    from mxtpu.models.hybrid import HybridDecoderModel
    net = HybridDecoderModel.from_config(TINY)
    assert {k: tuple(p.shape) for k, p in net.named_leaves().items()} \
        == weights_mellum2.leaf_shapes(TINY)
    cfg = harness.load_json(harness.os.path.join(
        harness.HERE, "configs", "mellum2_12b_a2_5b.json"))
    shapes = weights_mellum2.leaf_shapes(cfg)
    assert shapes["l11.w_in"] == (64, 2304, 1792)
    assert shapes["l0.w_out"] == (64, 896, 2304)
    assert shapes["l3.router"] == (2304, 64)
    assert shapes["l3.q"] == (4096, 2304) and shapes["l3.k"] == (512, 2304)
    assert shapes["l3.q_norm"] == (128,) and "l12.q" not in shapes


def test_reference_controls_differ_from_it():
    w = weights_mellum2.make(TINY, 5)
    tokens = np.random.default_rng(4).integers(1, 97, (2, 24))
    exact = np.asarray(reference_mellum2.forward(TINY, w, tokens))
    for cast, least, most in (("fp8", 1e-4, 2.0), ("top7", 1e-4, 1.0),
                              ("window_off", 1e-5, 1.0)):
        low = np.asarray(reference_mellum2.forward(TINY, w, tokens,
                                                   cast=cast))
        assert np.isfinite(low).all()
        assert least < np.abs(low - exact).max() < most
        # the window is seen only past it: the first 8 positions agree
        if cast == "window_off":
            np.testing.assert_allclose(low[:, :8], exact[:, :8], atol=1e-6)
    with pytest.raises(ValueError):
        reference_mellum2.forward(TINY, w, tokens, cast="bfloat16")


def test_near_ties_are_counted():
    w = weights_mellum2.make(TINY, 5)
    tokens = np.random.default_rng(4).integers(1, 97, (1, 24))
    none, pairs = reference_mellum2.near_ties(TINY, w, tokens, 20, margin=0.0)
    every, _ = reference_mellum2.near_ties(TINY, w, tokens, 20, margin=1.0)
    assert (none, every, pairs) == (0, 80, 80)


def test_token_gaps_are_in_the_rows_order():
    w = weights_mellum2.make(TINY, 5)
    rng = np.random.default_rng(6)
    rows = [(rng.integers(1, 97, p).tolist(), rng.integers(1, 97, n).tolist())
            for p, n in ((9, 3), (2, 7), (14, 5), (5, 2), (3, 3))]
    cols = generate_mellum2.columns(97)
    got = reference_mellum2.token_gaps_of(TINY, w, rows, (None, "top7"),
                                          cols[::9], block=2, pad_to=8)
    assert [len(g) for g, _ in got[None]] == [3, 7, 5, 2, 3]
    for cast in (None, "top7"):
        for (prompt, served), (gaps, kept) in zip(rows, got[cast]):
            exact = np.asarray(reference_mellum2.forward(
                TINY, w, np.asarray(prompt + served)[None]))[0]
            seen = exact if cast is None else np.asarray(
                reference_mellum2.forward(
                    TINY, w, np.asarray(prompt + served)[None], cast=cast))[0]
            assert kept.shape == (len(served), 11)
            for j, tok in enumerate(served):
                at = len(prompt) - 1 + j
                if cast is not None:
                    tok = int(np.argmax(seen[at]))
                assert gaps[j] == pytest.approx(
                    exact[at].max() - exact[at][tok], abs=1e-6)
                np.testing.assert_allclose(kept[j], seen[at][cols[::9]],
                                           atol=1e-6)


# -- the counts --------------------------------------------------------
def test_flops_mellum2_against_hand_counts():
    cfg = harness.load_json(harness.os.path.join(
        harness.HERE, "configs", "mellum2_12b_a2_5b.json"))
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 28
    assert counts.layer_counts(cfg) == (3, 9)
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert attn == counts.attention_params(cfg) == 21_233_664
    expert = 3 * 2304 * 896
    assert expert == counts.expert_params(cfg) == 6_193_152
    layer = attn + 2304 * 64 + 64 * expert + 2 * 2304 + 2 * 128
    assert layer == 417_747_712
    total = 12 * layer + 2 * 98304 * 2304 + 2304
    assert counts.param_count(cfg) == total == 5_465_959_680
    active = 12 * (attn + 2304 * 64 + 8 * expert) + 98304 * 2304
    assert counts.active_gemm_params(cfg) == active
    assert counts.active_gemm_params(cfg, head=False) == \
        active - 98304 * 2304
    # a query at 0-based position 4999 reads 5000 keys in each of the 3
    # full layers and 1024 in each of the 9 sliding ones
    assert counts.attended(cfg, 4999) == 3 * 5000 + 9 * 1024
    assert counts.attended(cfg, 99) == 12 * 100
    assert counts.decode_flops_per_token(cfg, 5000) == \
        2 * active + 4 * 4096 * (3 * 5000 + 9 * 1024)
    # a prompt of 2000: full layers 1 + ... + 2000; sliding 1 + ... + 1024
    # and then 1024 for each of the other 976
    keys = 3 * (2000 * 2001 // 2) + 9 * (1024 * 1025 // 2 + 976 * 1024)
    assert counts.prompt_flops(cfg, 2000) == \
        2 * 2000 * (active - 98304 * 2304) + 2 * 98304 * 2304 \
        + 4 * 4096 * keys
    assert counts.kv_bytes_per_position(cfg) == 2 * 4 * 128 * 2 == 2048
    assert counts.expert_bytes(cfg) == 12_386_304
    dense = 2 * (12 * (attn + 2304 * 64 + 2 * 2304 + 256) + 2304
                 + 98304 * 2304)
    assert counts.dense_weight_bytes(cfg) == dense
    # 23 lanes that hold 80,000 positions between them, 730 of the 768
    # experts touched: every lane is past the window
    kv = (3 * 80_000 + 9 * 23 * 1024) * 2048
    assert counts.decode_step_bytes(cfg, 23, 80_000, 730) == \
        dense + 730 * 12_386_304 + kv
    assert counts.decode_step_flops(cfg, 23, 80_000) == \
        23 * 2 * active + 4 * 4096 * (3 * 80_000 + 9 * 23 * 1024)
    # the step is bound by memory: 10.5 GB at 819 GB/s
    least, bound = counts.roofline_seconds(
        counts.decode_step_flops(cfg, 23, 80_000),
        counts.decode_step_bytes(cfg, 23, 80_000, 730), 197e12, 819e9)
    assert bound == "memory" and 0.012 < least < 0.014
    # the experts' products of a prefill call of 4 x 256 valid tokens:
    # 8,192 pairs a layer, every expert touched: bound by compute
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.routed_flops(cfg, 8192) == 2 * 8192 * expert
    assert counts.experts_roofline_seconds(cfg, 8192, 768, peaks) == \
        pytest.approx(max(12 * 2 * 8192 * expert / 197e12,
                          768 * 12_386_304 / 819e9))
    # and of a decode step of 23 lanes: 184 pairs, bound by the weights
    assert counts.experts_roofline_seconds(cfg, 184, 730, peaks) == \
        pytest.approx(730 * 12_386_304 / 819e9)
    assert counts.window_attention_bytes(cfg, 23, 80_000) == \
        9 * 23 * 1024 * 2048
    assert counts.window_attention_bytes(cfg, 2, 300) == 9 * 300 * 2048
    assert counts.lane_bytes(cfg, 8448, 1280) == \
        (3 * 8448 + 9 * 1280) * 2048 == 75_497_472


def test_the_cells_files_agree_with_each_other():
    cell, cfg, mix, bench = harness.load_cell(CELL)
    assert cell["chips"] == 1 and mix["kind"] == "generate_mellum2"
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 3
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) \
        == len(cfg["mlp_layer_types"]) == 12
    # every published width as the catalog row has it
    for key, value in (("hidden_size", 2304), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("num_experts", 64), ("num_experts_per_tok", 8),
                       ("moe_intermediate_size", 896),
                       ("sliding_window", 1024), ("vocab_size", 98304)):
        assert cfg[key] == value
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert conf["reduced"] == cfg["reduced"]
    assert mix["kv_capacity"] == mix["prompt_len"]["hi"] \
        + mix["output_len"]["hi"]
    assert mix["kv_capacity"] % max(mix["prompt_buckets"]) == 0
    assert mix["prompt_len"]["lo"] >= cfg["sliding_window"]
    assert mix["clients"] > mix["lanes"] and mix["pool"] == 32
    assert set(mix["limits"]) == {
        "served_token_gap", "served_token_gap_mean", "served_token_gap_sq",
        "served_logit_err", "served_logit_err_q1"}
    # what parts a token's eighth expert left out from rounding is held
    for held in ("served_token_gap_sq", "served_logit_err",
                 "served_logit_err_q1"):
        assert mix["limits"][held] is not None
    assert {"fp8", "top7"} <= set(mix["controls"])
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == [
        "moe_decode_step_roofline", "moe_device_share_pct",
        "moe_dispatch_share_pct", "moe_expert_decode_roofline",
        "moe_expert_prefill_roofline", "window_attention_roofline"]
    for name in mine:
        assert harness.os.path.exists(harness.os.path.join(
            harness.HERE, "metrics", name + ".py"))
    serve = {m["name"]: m for m in bench["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in serve["workloads"]


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
    assert {"moe_decode_step_roofline", "moe_device_share_pct",
            "moe_expert_decode_roofline", "moe_expert_prefill_roofline",
            "moe_dispatch_share_pct", "window_attention_roofline",
            "serve_step_mfu", "prefill_call_ms",
            "lane_occupancy_pct"} <= want
    assert set(res["metrics"]) == want


def test_the_sample_holds_the_two_longest():
    class R:
        def __init__(self, i, p, n, done=True):
            self.index, self.prompt, self.max_tokens = i, [1] * p, n
            self.tokens, self.error = [2] * (n if done else n - 1), None
    rs = [R(0, 10, 3), R(1, 30, 5), R(2, 29, 9), R(3, 40, 9, done=False),
          R(4, 12, 2), R(5, 11, 2)]
    got = generate_mellum2.sample_for_check(rs, 7, 4)
    assert [r.index for r in got[:2]] == [2, 1] and len(got) == 4
    assert 3 not in {r.index for r in got}
    assert generate_mellum2.sample_for_check(rs[3:4], 7, 4) == []


def test_kept_decode_logits_are_found_by_prompt_slot_and_position():
    """Prefill and decode calls over three slots: slot 1 is taken twice
    by requests of the SAME prompt length (the pool sends each size
    several times) whose first decode steps read alike, slot 2 decodes
    a third such request at the same time, slot 0 idles.  Each request
    gets the rows of its own calls, in order, by the prompt that went
    into its slot; one whose tokens the calls do not read gets None."""
    class Runner:
        n = 0

        def decode(self, tokens, step, kv, length=None):
            self.n += 1
            rows = np.zeros((3, 1, 256), np.float32)
            rows[:, 0, :] = 1000 * step[:, None] + tokens + self.n / 4
            return type("L", (), {"rows": rows})(), kv

        def prefill(self, tokens, step, lane_idx, kv, length=None):
            return None, kv
    runner = Runner()
    kept = generate_mellum2.DecodeLogits(runner, 256)
    assert len(generate_mellum2.columns(256)) == 128
    f = lambda a: np.asarray(a, np.float32)
    prompts = {"a": list(range(20, 29)), "b": list(range(30, 39)),
               "c": list(range(40, 49))}

    def prefill(name, lane):      # a chunk of 4, then the last 5 of 9
        p = prompts[name]
        runner.prefill(f([p[:4] + [0]]), f([0]), f([lane]), None, f([4]))
        runner.prefill(f([p[4:]]), f([4]), f([lane]), None, f([5]))

    def decode(tok, step, on):
        runner.decode(f(tok)[:, None], f(step), None, f(on))
    prefill("a", 1)
    prefill("c", 2)
    decode([0, 7, 7], [0, 9, 9], [0, 1, 1])
    decode([0, 8, 8], [0, 10, 10], [0, 1, 1])
    prefill("b", 1)               # slot 1 taken anew
    decode([0, 7, 5], [0, 9, 11], [0, 1, 1])
    decode([0, 8, 6], [0, 10, 12], [0, 1, 1])
    R = lambda name, toks: type("R", (), {"prompt": prompts[name],
                                          "tokens": toks, "index": 0})()
    a, b, c, lost = kept.of([R("a", [7, 8, 4]), R("b", [7, 8, 2]),
                             R("c", [7, 8, 5, 6, 1]), R("a", [7, 9, 9])])
    assert a[:, 0].tolist() == [9007.25, 10008.5] and a.shape == (2, 128)
    assert b[:, 0].tolist() == [9007.75, 10009.0]     # slot 1, calls 3-4
    assert c[:, 0].tolist() == [9007.25, 10008.5, 11005.75, 12007.0]
    assert lost is None
    kept.clear()
    assert kept.of([R("a", [7, 8, 4])]) == [None]


def test_logit_numbers_tell_a_rare_miss_from_a_piece_left_out():
    """A twentieth of the tokens far off (a tie broken the other way)
    moves the root of the squares and leaves the first quartile at the
    rounding; every token a little off moves both."""
    rng = np.random.default_rng(0)
    exact = [rng.normal(size=(400, 128)), rng.normal(size=(200, 128))]
    noise = lambda a, by: a + by * rng.normal(size=a.shape)
    rare = [noise(e, 1e-3) for e in exact]
    rare[0][::20] = noise(exact[0][::20], 0.2)
    every = [noise(e, 0.04) for e in exact]
    a, b = (generate_mellum2.logit_numbers(x, exact) for x in (rare, every))
    assert a["logit_tokens"] == 600 and a["logit_columns"] == 128
    assert a["logit_err"] > 0.03 and a["logit_err_q1"] < 2e-3
    assert b["logit_err"] == pytest.approx(0.04, rel=0.1)
    assert b["logit_err_q1"] == pytest.approx(0.04, rel=0.15)


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
    """``run.py --readings … --control fp8,top7,window_off``: fp8 matrix
    products, and a token's eighth expert left out, each in the
    program's place on the same prompts and tokens, come out not
    correct on every seed; what the open window reads goes through the
    same judge."""
    import argparse
    from benchmark import run as run_mod
    seeds = [5, 2 ** 31 + 6]
    args = argparse.Namespace(readings=",".join(map(str, seeds)),
                              control="fp8,top7,window_off", fault=None)
    got = run_mod.read_seeds(context(), args)["readings"]
    for seed in seeds:
        one = got[str(seed)]
        assert one["program"]["correct"] is True
        for control in ("control:fp8", "control:top7"):
            assert one[control]["correct"] is False
            assert failing(one[control]) == {
                "served_token_gap_sq", "served_logit_err",
                "served_logit_err_q1"}
        assert set(one["control:window_off"]["compared"]) == \
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


def test_a_program_without_routed_experts_fails_at_the_drivers_import():
    """The parent commit under this PR's benchmark files: the driver's
    first import names what only this change has."""
    import ast
    path = harness.os.path.join(harness.HERE, "drivers",
                                "generate_mellum2.py")
    first = next(n for n in ast.parse(open(path).read()).body
                 if isinstance(n, (ast.Import, ast.ImportFrom)))
    assert first.module == "mxtpu.models.hybrid"
    assert [a.name for a in first.names] == ["SparseMLP"]
