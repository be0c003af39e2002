"""``correct`` has to be able to fail.

The control — the reference put in the program's place one precision
down — comes out as not correct at a size a test run can hold, and a run
with the timed path broken underneath (the look for a chip skipped, the
rest of the run driven as the harness drives it) sees ``correct`` false
once for each fault a cell can have: a step that returns its state
unchanged, half of the batch left out, a token altered where it is
produced.
"""
import time

import numpy as np
import pytest

from conftest import TINY, TINY_GEN, TINY_TRAIN_MIX

from benchmark import harness
from benchmark.drivers import generate, train

TINY_GEN_MIX = {
    "kind": "generate", "lanes": 3, "kv_capacity": 32,
    "prompt_buckets": [8, 16], "warm_batch_rungs": [1, 2],
    "loop": "closed", "clients": 6, "ramp_s": 0.3, "drain_s": 20.0,
    "prompt_len": {"dist": "loguniform", "lo": 2, "hi": 14},
    "output_len": {"dist": "loguniform", "lo": 3, "hi": 12},
    "pool": 64, "check": {"requests": 64},
    # which numbers are held and which only printed is the cell's own
    # choice (workloads/*.json); the tiny float32 program reads 0 and its
    # bfloat16 control 2e-9 and more
    "limits": {"served_token_gap": None, "served_token_gap_mean": None,
               "served_token_gap_sq": 1e-10}}
BENCH = harness.load_json(harness.os.path.join(harness.ROOT,
                                               "BENCHMARK.json"))


class Device:
    platform, device_kind = "cpu", "TPU v5 lite"


def context(cell_name, cfg, mix, seconds=1.0, trace=0, tmp_path=None):
    cell = {"name": cell_name, "chips": 1}
    return harness.Context(cell, cfg, mix, 2 ** 31 + 21, seconds, trace,
                           time.perf_counter(),
                           trace_dir=str(tmp_path) if tmp_path else None)


def drive(cell_name, cfg, mix, tamper=None, **kw):
    from benchmark import run as run_mod
    return run_mod.run_cell(context(cell_name, cfg, mix, **kw), BENCH,
                            Device(), tamper)


def read_seeds(cell_name, cfg, mix, seeds, control=None, fault=None, **kw):
    """``run.py --readings``: what the chip calls that read a control or
    a fault at the cell's own size go through."""
    import argparse
    from benchmark import run as run_mod
    args = argparse.Namespace(readings=",".join(map(str, seeds)),
                              control=control, fault=fault)
    return run_mod.read_seeds(context(cell_name, cfg, mix, **kw),
                              args)["readings"]


def failing(one):
    return {k for k, c in one["compared"].items() if not c["ok"]}


# -- training ----------------------------------------------------------
def test_train_run_is_correct_and_reports_the_cells_metrics(tmp_path):
    res = drive("bert-large-mlm-s512", TINY, TINY_TRAIN_MIX)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    res = drive("bert-large-mlm-s512", TINY, TINY_TRAIN_MIX, trace=1,
                tmp_path=tmp_path)
    assert res["correct"] is True
    assert {"train_step_mfu", "train_host_call_ms",
            "device_idle_pct.train"} <= set(res["metrics"])
    # no Pallas call on the CPU: the kernel's reader finds nothing to
    # read and the metric is left out, never reported as 0
    assert "flash_attention_roofline" not in res["metrics"]
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def state_unchanged(step):
    import jax
    import jax.numpy as jnp
    copy = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True), tree)

    def call(x, y):
        # the step donates its state: keep copies, and put them back
        params = copy([p._data._data for p in step._params])
        state = copy(step._opt_state)
        loss = step(x, y)
        for p, v in zip(step._params, params):
            p._data._data = v
        step._opt_state = state
        return loss
    return call


def half_batch(step):
    from mxtpu import nd

    def call(x, y):
        h = x.shape[0] // 2
        return step(nd.array(x.asnumpy()[:h]), nd.array(y.asnumpy()[:h]))
    return call


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_train_fault_comes_out_not_correct(fault):
    res = drive("bert-large-mlm-s512", TINY, TINY_TRAIN_MIX, tamper=fault)
    assert res["correct"] is False
    bad = [k for k, c in res["compared"].items() if not c["ok"]]
    assert bad and "requests_failed" not in bad
    if fault is state_unchanged:
        # nothing moved: the first gradient and the change both read 1
        assert res["compared"]["grad_norm_gap"]["value"] == \
            pytest.approx(1.0)
        assert res["compared"]["change_norm_gap"]["value"] == \
            pytest.approx(1.0)


SEEDS = [2 ** 31 + 33, 2 ** 31 + 34, 7]


def test_train_control_comes_out_not_correct():
    """fp8 matrix products in the program's place, through the entry
    and the judge that read the control on the chip, on the numbers the
    cell holds (its workload file leaves the loss and the ``*_own`` gaps
    unlimited, and so does the tiny mix).  bf16 is what the
    configuration states; the tiny program runs float32 and is held to
    the tiny limits."""
    cell = harness.load_json(harness.os.path.join(
        harness.HERE, "workloads", "mlm-s512-b8.json"))
    assert {k for k, v in cell["limits"].items() if v is not None} == \
        {k for k, v in TINY_TRAIN_MIX["limits"].items() if v is not None} \
        == {"grad_norm_gap", "change_norm_gap"}
    got = read_seeds("bert-large-mlm-s512", TINY, TINY_TRAIN_MIX, SEEDS,
                     control="fp8")
    for seed in SEEDS:
        one = got[str(seed)]["control:fp8"]
        assert one["correct"] is False
        assert failing(one) and failing(one) <= {"grad_norm_gap",
                                                 "change_norm_gap"}
    got = read_seeds("bert-large-mlm-s512", TINY, TINY_TRAIN_MIX, SEEDS[:1])
    assert got[str(SEEDS[0])]["program"]["correct"] is True


def test_train_half_batch_in_the_reference_comes_out_not_correct():
    got = read_seeds("bert-large-mlm-s512", TINY, TINY_TRAIN_MIX, SEEDS[:1],
                     fault="half_batch")
    one = got[str(SEEDS[0])]["fault:half_batch"]
    assert one["correct"] is False
    assert failing(one) <= {"grad_norm_gap", "change_norm_gap"}


# -- serving -----------------------------------------------------------
def test_generate_run_is_correct(tmp_path):
    res = drive("bertgen-large-fusion-backlog", TINY_GEN, TINY_GEN_MIX)
    assert res["correct"] is True and res["attempted"] > 10
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    res = drive("bertgen-large-fusion-backlog", TINY_GEN, TINY_GEN_MIX,
                trace=1, tmp_path=tmp_path)
    assert res["correct"] is True
    assert {"serve_step_mfu", "decode_step_roofline", "decode_call_ms",
            "batcher_self_ms", "token_gap_p95_ms",
            "device_idle_pct.serve"} <= set(res["metrics"])


def test_generate_token_altered_comes_out_not_correct(monkeypatch):
    """The program's sampler puts the runner-up first at one position of
    every stream: the stream stays consistent, only the reference can
    tell."""
    from mxtpu.serving import generate as prog_generate
    real = prog_generate.sample_token

    def altered(logits, *, position, seed=0, top_k=1):
        if position % 5 == 0:
            row = np.asarray(logits, np.float64).reshape(-1)
            return int(np.argsort(row)[-2])
        return real(logits, position=position, seed=seed, top_k=top_k)

    monkeypatch.setattr(prog_generate, "sample_token", altered)
    res = drive("bertgen-large-fusion-backlog", TINY_GEN, TINY_GEN_MIX)
    assert res["correct"] is False
    assert res["compared"]["served_token_gap_sq"]["ok"] is False
    assert res["failed"] == 0


def test_generate_request_lost_comes_out_not_correct():
    def lossy(submit):
        def call(prompt, max_tokens, on_token):
            if len(prompt) % 4 == 0:
                raise RuntimeError("refused")
            return submit(prompt, max_tokens, on_token)
        return call
    res = drive("bertgen-large-fusion-backlog", TINY_GEN, TINY_GEN_MIX,
                tamper=lossy)
    assert res["correct"] is False and res["failed"] > 0


def test_generate_control_comes_out_not_correct():
    """bfloat16 in the program's place on the same prompts and tokens,
    through the entry and the judge that read the control on the chip:
    not correct by the number the cell holds, the mean of the squared
    gaps, while the program's own tokens are."""
    cell = harness.load_json(harness.os.path.join(
        harness.HERE, "workloads", "fusion-backlog.json"))
    assert {k for k, v in cell["limits"].items() if v is not None} == \
        {k for k, v in TINY_GEN_MIX["limits"].items() if v is not None} \
        == {"served_token_gap_sq"}
    seeds = [5, 2 ** 31 + 6, 2 ** 31 + 7]
    got = read_seeds("bertgen-large-fusion-backlog", TINY_GEN, TINY_GEN_MIX,
                     seeds, control="bfloat16")
    for seed in seeds:
        one = got[str(seed)]
        assert one["program"]["correct"] is True
        assert one["control:bfloat16"]["correct"] is False
        assert failing(one["control:bfloat16"]) == {"served_token_gap_sq"}
