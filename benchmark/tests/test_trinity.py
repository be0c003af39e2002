"""The expert-share cell's benchmark files, on the CPU at a tiny size:
the counts against hand counts, the cell's files against the catalog
row, and the driver's judge with a sound run (traced: every metric of
the cell reads a value) and both controls (``correct`` has to be able
to fail).
"""
import argparse
import json
import time

import pytest

from benchmark import flops_trinity as counts
from benchmark import harness
from benchmark import run as run_mod

from test_correct import BENCH, Device, failing

CELL = "trinity-large-preview-agent-backlog"
TINY = {"name": "tiny_trinity", "model_type": "afmoe", "vocab_size": 97,
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 3,
        "layer_types": ["sliding_attention", "full_attention",
                        "sliding_attention"],
        "num_dense_layers": 1, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_experts": 4,
        "expert_share": {"of": 8, "first": 0}, "num_experts_per_tok": 2,
        "num_shared_experts": 1, "score_func": "sigmoid",
        "route_norm": True, "route_scale": 2.448, "n_group": 1,
        "topk_group": 1, "sliding_window": 8, "rope_theta": 10000,
        "rope_scaling": None, "rms_norm_eps": 1e-5, "mup_enabled": True,
        "tie_word_embeddings": False, "param_dtype": "float32",
        "kv_cache_dtype": "float32"}
TINY_MIX = {
    "kind": "generate_trinity", "lanes": 3, "kv_capacity": 48,
    "prompt_buckets": [4], "warm_batch_rungs": [1],
    "loop": "closed", "clients": 5, "ramp_s": 0.3, "drain_s": 30.0,
    # half the prompts past the window of 8, the longest past a wrap of
    # the ring of 12
    "prompt_len": {"dist": "loguniform", "lo": 5, "hi": 20},
    "output_len": {"dist": "loguniform", "lo": 3, "hi": 12},
    "pool": 8, "check": {"requests": 4, "pad_to": 64},
    # the tiny float32 program reads rounding (1e-7); fp8 1e-2 and the
    # biased weights 1e-3 and more
    "limits": {"served_logit_err": 1e-4, "served_logit_err_q1": 1e-4}}


def context(seconds=1.5, trace=0, tmp_path=None):
    return harness.Context({"name": CELL, "chips": 1}, TINY, TINY_MIX,
                           2 ** 31 + 37, seconds, trace, time.perf_counter(),
                           trace_dir=str(tmp_path) if tmp_path else None)


# -- the counts --------------------------------------------------------
def test_flops_trinity_against_hand_counts():
    # q, gate, o 3 x 64 x 64, k and v 2 x 64 x 32; a dense MLP 3 x 64 x
    # 96; a routed layer's router 64 x 8 and shared expert 3 x 64 x 32
    assert counts.attention_params(TINY) == 3 * 4096 + 2 * 2048
    assert counts.expert_params(TINY) == 6144
    assert counts.layer_counts(TINY) == (1, 2, 1, 2)
    assert counts.token_params(TINY, head=False) == \
        3 * 16384 + 18432 + 2 * (512 + 6144)
    assert counts.expected_held(TINY) == 2 * 2 * 4 / 8
    # every leaf but the embedding and the held experts, in bf16
    held = 2 * 4 * 6144
    assert counts.dense_weight_bytes(TINY) == \
        2 * (counts.param_count(TINY) - 97 * 64 - held)
    # two lanes at 5 and 11: full 16 positions, windows 5 + 8
    b = counts.decode_step_bytes(TINY, 16, 13, 3)
    assert b == counts.dense_weight_bytes(TINY) + 3 * 2 * 6144 \
        + (16 + 2 * 13) * 2 * 2 * 16 * 2
    assert counts.decode_step_flops(TINY, 2, 3, 16, 13) == \
        2 * 2 * counts.token_params(TINY) + 2 * 3 * 6144 \
        + 4 * 4 * 16 * (16 + 2 * 13)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert counts.experts_roofline_seconds(TINY, 10, 3, peaks) == \
        pytest.approx(max(2 * 10 * 6144 / 1e12, 3 * 2 * 6144 / 1e9))


def test_the_cells_arithmetic():
    """4,321.8 M parameters = 8.64 GB of bfloat16; the dense weights a
    decode step reads 1.24 GB; one held expert 56.6 MB."""
    _, cfg, _, _ = harness.load_cell(CELL)
    assert counts.param_count(cfg) == 4_321_903_872
    assert counts.dense_weight_bytes(cfg) == pytest.approx(1.2423e9,
                                                           rel=1e-4)
    assert counts.expert_bytes(cfg) == 2 * 3 * 3072 * 3072
    assert counts.layer_counts(cfg) == (1, 4, 1, 4)


def test_the_cells_files_agree_with_the_catalog_row():
    cell, cfg, mix, bench = harness.load_cell(CELL)
    assert cell["chips"] == 1 and mix["kind"] == "generate_trinity"
    row = next(json.loads(line) for line in open(
        "/".join([harness.ROOT, "benchmark", "tests", "data",
                  "trinity_catalog_row.json"])))
    catalog = row["config"]
    changed = {k for k, v in catalog.items() if cfg[k] != v}
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert conf["source"] == cfg["source"] == row["source_url"]
    assert changed == set(conf["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"}
    assert cfg["layer_types"] == catalog["layer_types"][5:10]
    assert cfg["num_experts"] * 8 == cfg["expert_share"]["of"] == 256
    assert cfg["vocab_size"] * 8 == cfg["vocab_share"]["of"] == 200192
    assert set(mix["limits"]) == {"served_logit_err", "served_logit_err_q1"}
    assert {"fp8", "bias_weights"} <= set(mix["controls"])
    mine = sorted(m["name"] for m in bench["per_layer"]
                  if m.get("workloads") == [CELL])
    assert mine == ["ep_decode_step_roofline", "ep_expert_roofline",
                    "ep_rows_per_expert"]
    serve = {m["name"]: m for m in bench["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in serve["workloads"]


# -- the driver and its judge ------------------------------------------
def test_run_is_correct_and_reports_the_cells_metrics(monkeypatch):
    res = run_mod.run_cell(context(), BENCH, Device())
    assert res["correct"] is True and res["attempted"] > 5
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    monkeypatch.setattr(harness, "peaks_for", lambda kind: harness.load_json(
        harness.os.path.join(harness.HERE, "peaks.json"))["TPU v5 lite"])
    res = run_mod.run_cell(context(trace=1, tmp_path=harness.os.path.join(
        harness.HERE, ".trace", CELL)), BENCH, Device())
    assert res["correct"] is True
    want = {m["name"] for m in run_mod.metrics_of(
        BENCH, "per_layer", CELL, {"serve_tokens_per_s", "setup_s"})}
    assert {"ep_decode_step_roofline", "ep_expert_roofline",
            "ep_rows_per_expert", "serve_step_mfu", "prefill_call_ms"} <= want
    assert set(res["metrics"]) == want
    assert res["metrics"]["ep_rows_per_expert"]["value"] >= 1.0


def test_both_controls_come_out_not_correct():
    """``run.py --readings … --control fp8,bias_weights``: fp8 matrix
    products, and combine weights taken from the biased scores, each in
    the program's place on the same decode steps, come out not correct
    on every seed at this tiny float32 size, where the program reads
    rounding alone (on the chip's bfloat16 products the biased weights
    move the logits less than the rounding does: PERF.md sec. 6)."""
    seeds = [5, 2 ** 31 + 6]
    args = argparse.Namespace(readings=",".join(map(str, seeds)),
                              control="fp8,bias_weights", fault=None)
    got = run_mod.read_seeds(context(), args)["readings"]
    for seed in seeds:
        one = got[str(seed)]
        assert one["program"]["correct"] is True
        for control in ("control:fp8", "control:bias_weights"):
            assert one[control]["correct"] is False
            assert failing(one[control])


def test_a_program_without_the_afmoe_family_fails_at_the_drivers_import():
    """The parent commit under this PR's benchmark files: the driver's
    first statement after its import reads what only this change has."""
    import ast
    path = harness.os.path.join(harness.HERE, "drivers",
                                "generate_trinity.py")
    body = ast.parse(open(path).read()).body
    first = next(i for i, n in enumerate(body)
                 if isinstance(n, (ast.Import, ast.ImportFrom)))
    assert body[first].module == "mxtpu.models.hybrid"
    probe = body[first + 1].value
    assert isinstance(probe, ast.Attribute) and probe.attr == "_from_afmoe"
