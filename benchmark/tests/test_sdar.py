"""The block-diffusion cell's benchmark files, on the CPU at a tiny size:
the counts against hand counts, the two new readers on a constructed
trace, the traffic's draws, the cell's files against the catalog row,
and the driver's judge with a sound run and both controls (``correct``
has to be able to fail).
"""
import argparse
import time

import numpy as np
import pytest

from benchmark import flops_sdar as counts
from benchmark import harness, loadgen
from benchmark import run as run_mod
from benchmark.drivers import generate_sdar

from test_correct import BENCH, Device, failing
from test_program_spans import made, read

CELL = "sdar-30b-a3b-chat-blocks-backlog"
TINY = {"name": "tiny_sdar", "model_type": "sdar_moe", "vocab_size": 512,
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2,
        "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": None,
        "tie_word_embeddings": False, "attention_bias": False,
        "use_sliding_window": False, "block_length": 4,
        "mask_token_id": 500, "denoising_steps": 4,
        "confidence_threshold": 0.9, "param_dtype": "float32",
        "kv_cache_dtype": "float32"}
TINY_MIX = {
    "kind": "generate_sdar", "lanes": 3, "kv_capacity": 64,
    "prompt_buckets": [16], "warm_batch_rungs": [1],
    "loop": "closed", "clients": 5, "ramp_s": 0.3, "drain_s": 30.0,
    "prompt_len": {"dist": "loguniform", "lo": 9, "hi": 30},
    "output_len": {"dist": "loguniform", "lo": 8, "hi": 20},
    "pool": 8, "check": {"requests": 3, "pad_to": 256, "chunk": 8},
    # the tiny float32 program reads 5e-8 (rounding); fp8 3e-2 and the
    # causal block 1.5e-3 and more
    "limits": {"served_logit_err": 1e-4, "served_logit_err_q1": 1e-4}}


def context(seconds=1.5, trace=0, tmp_path=None, mix=TINY_MIX):
    return harness.Context({"name": CELL, "chips": 1}, TINY, mix,
                           2 ** 31 + 29, seconds, trace, time.perf_counter(),
                           trace_dir=str(tmp_path) if tmp_path else None)


# -- the counts --------------------------------------------------------
def test_flops_sdar_against_hand_counts():
    # projections 2 x 64 x 64 + 2 x 64 x 32, router 64 x 8, two experts
    # of 3 x 64 x 32, over two layers
    assert counts.attention_params(TINY) == 12288
    assert counts.expert_params(TINY) == 6144
    assert counts.layer_gemm_params(TINY) == 2 * (12288 + 512 + 2 * 6144)
    # positions 4-7 read 8 keys, 8-11 read 12
    assert counts.attended(TINY, 4, 8) == 4 * 8 + 4 * 12
    assert counts.attended(TINY, 0, 4) == 16
    assert counts.row_flops(TINY, 4, 8) == \
        2 * 8 * 50176 + 2 * 4 * 64 * 512 + 4 * 4 * 16 * 2 * 80
    assert counts.step_flops(TINY, 2, 12, 96) == \
        2 * 12 * 50176 + 2 * 2 * 4 * 64 * 512 + 4 * 4 * 16 * 2 * 96
    # every parameter: embedding and head 2 x 512 x 64, a layer's
    # attention, norms (64 + 64 + 16 + 16), router and 8 experts
    per_layer = 12288 + 160 + 512 + 8 * 6144
    assert counts.param_count(TINY) == 2 * 512 * 64 + 2 * per_layer + 64
    assert counts.dense_weight_bytes(TINY, 2) == \
        2 * (512 * 64 + 2 * (12288 + 160 + 512) + 64)
    assert counts.kv_bytes_per_position(TINY, 2) == 2 * 2 * 16 * 2
    assert counts.step_bytes(TINY, 100, 12, 5, 2, 2) == \
        counts.dense_weight_bytes(TINY, 2) + 5 * 6144 * 2 + 2 * 112 * 128


def test_the_published_stage_counts():
    """The cell's own file: 8.72 GB of bf16 leaves, and a pass of 48
    lanes (one folded) that touches every expert moves ≈ 8.7 GB."""
    cfg = harness.load_cell(CELL)[1]
    assert counts.param_count(cfg) * 2 == 8_722_111_488
    assert counts.layer_gemm_params(cfg) == 6 * (18_874_368 + 262_144
                                                 + 8 * 4_718_592)
    nbytes = counts.step_bytes(cfg, 48 * 1000, 47 * 4 + 8, 768)
    assert 8.6e9 < nbytes < 8.8e9


# -- the readers -------------------------------------------------------
def _passes():
    """Four passes of one block over two lanes (the first folded) and
    the steps round them: 12 + 8 + 8 + 8 positions for 8 tokens."""
    stats = [dict(positions=12, attended=4 * 104 + 4 * 108 + 4 * 208,
                  context_tokens=300, active=2, moe_experts_touched=10),
             dict(positions=8, attended=4 * 108 + 4 * 208,
                  context_tokens=304, active=2, moe_experts_touched=9)]
    spans, busy = [], []
    for i in range(4):
        lo = 0.1 * i
        spans.append(("gen/step", lo, lo + 0.09,
                      {"emitted": 8 if i == 3 else 0}))
        spans.append(("gen/decode", lo + 0.01, lo + 0.08,
                      stats[min(i, 1)]))
        busy.append((lo + 0.02, lo + 0.06))
    return spans, busy, stats


def test_positions_per_token_reads_the_regions_counts():
    spans, busy, _ = _passes()
    assert read("positions_per_token", made(spans, busy)) == 36 / 8
    spans = [s for s in spans if s[0] != "gen/step"]
    assert read("positions_per_token", made(spans, busy)) is None


def test_diffusion_step_roofline_against_its_least_time():
    spans, busy, stats = _passes()
    r = made(spans, busy)
    r.cfg = dict(TINY, param_dtype="bfloat16", kv_cache_dtype="bfloat16")
    r.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = sum(counts.roofline_seconds(
        counts.step_flops(r.cfg, s["active"], s["positions"], s["attended"]),
        counts.step_bytes(r.cfg, s["context_tokens"], s["positions"],
                          s["moe_experts_touched"]),
        197e12, 819e9)[0] for s in [stats[0]] + 3 * [stats[1]])
    assert read("diffusion_step_roofline", r) == pytest.approx(
        100 * least / 0.16)
    # a program whose regions do not count positions has nothing to read
    for s in spans:
        s[3].pop("attended", None)
    assert read("diffusion_step_roofline", made(spans, busy)) is None


# -- the traffic -------------------------------------------------------
def test_answers_end_on_a_blocks_end():
    mix = harness.load_cell(CELL)[2]
    for seed in (1, 2 ** 31 + 3):
        drawn = loadgen.lengths(mix, seed)
        got = generate_sdar.block_lengths(mix, seed, 4)
        assert [p for p, _ in got] == [p for p, _ in drawn]
        for (p, o), (_, want) in zip(got, drawn):
            assert (p + o) % 4 == 0 and 0 <= o - want < 4
        assert 128 <= min(p for p, _ in got) <= max(p for p, _ in got) \
            <= 2048
        assert 128 <= min(o for _, o in got) and \
            max(o for _, o in got) <= 515
        assert max(p + o for p, o in got) + 4 <= mix["kv_capacity"]
        assert 480 < np.median([p for p, _ in got]) < 560
        assert 240 < np.median([o for _, o in got]) < 280


def test_the_cells_files_agree_with_the_catalog_row():
    cell, cfg, mix, bench = harness.load_cell(CELL)
    assert cell["chips"] == 1 and mix["kind"] == "generate_sdar"
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    changed = {k for k, v in catalog.items() if cfg[k] != v}
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert changed == set(conf["reduced"]) == set(cfg["reduced"]) \
        == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 6 and cfg["published"] == {
        "num_hidden_layers": 48}
    assert cfg["mask_token_id"] < cfg["vocab_size"]
    assert set(mix["limits"]) == {"served_logit_err", "served_logit_err_q1"}
    assert {"fp8", "causal_block"} <= set(mix["controls"])
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == ["diffusion_step_roofline", "positions_per_token"]
    serve = {m["name"]: m for m in bench["end_to_end"]}["serve_tokens_per_s"]
    assert serve["workloads"][-1] == CELL


# -- the driver and its judge ------------------------------------------
def test_run_is_correct_and_reports_the_cells_metrics(monkeypatch):
    res = run_mod.run_cell(context(), BENCH, Device())
    assert res["correct"] is True and res["attempted"] > 10
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    monkeypatch.setattr(harness, "peaks_for", lambda kind: harness.load_json(
        harness.os.path.join(harness.HERE, "peaks.json"))["TPU v5 lite"])
    res = run_mod.run_cell(context(trace=1, tmp_path=harness.os.path.join(
        harness.HERE, ".trace", CELL)), BENCH, Device())
    assert res["correct"] is True
    want = {m["name"] for m in run_mod.metrics_of(
        BENCH, "per_layer", CELL, {"serve_tokens_per_s", "setup_s"})}
    assert {"diffusion_step_roofline", "positions_per_token",
            "serve_step_mfu", "prefill_call_ms", "sample_ms"} <= want
    assert set(res["metrics"]) == want
    assert 4.0 < res["metrics"]["positions_per_token"]["value"] < 6.0


def test_both_controls_come_out_not_correct():
    """``run.py --readings … --control fp8,causal_block``: fp8 matrix
    products, and a block's positions attending causally within it, each
    in the program's place on the same passes, come out not correct on
    every seed, by both numbers."""
    seeds = [5, 2 ** 31 + 6]
    args = argparse.Namespace(readings=",".join(map(str, seeds)),
                              control="fp8,causal_block", fault=None)
    got = run_mod.read_seeds(context(), args)["readings"]
    for seed in seeds:
        one = got[str(seed)]
        assert one["program"]["correct"] is True
        for control in ("control:fp8", "control:causal_block"):
            assert one[control]["correct"] is False
            assert failing(one[control]) == {"served_logit_err",
                                             "served_logit_err_q1"}


def test_passes_that_disagree_with_the_stream_are_found():
    final = [5, 6, 7, 8, 9, 10, 11, 12]
    ok = [(0, [5, 6, 500, 500]), (0, [5, 6, 7, 500]),
          (0, [5, 6, 7, 8, 500, 500, 500, 500])]
    assert generate_sdar.agrees(final, ok, 2, 500) == []
    bad = ok + [(4, [9, 99, 500, 500])]
    assert generate_sdar.agrees(final, bad, 2, 500) == [(3, 5)]
    # a prompt position is never masked
    assert generate_sdar.agrees(final, [(0, [500, 6, 7, 8])], 2, 500) == \
        [(0, 0)]


def test_a_program_without_block_diffusion_fails_at_the_drivers_import():
    """The parent commit under this PR's benchmark files: the driver's
    first statement after its import reads what only this change has."""
    import ast
    path = harness.os.path.join(harness.HERE, "drivers", "generate_sdar.py")
    body = ast.parse(open(path).read()).body
    first = next(i for i, n in enumerate(body)
                 if isinstance(n, (ast.Import, ast.ImportFrom)))
    assert body[first].module == "mxtpu.models.hybrid"
    probe = body[first + 1].value
    assert isinstance(probe, ast.Attribute) and probe.attr == "diffusion_spec"
