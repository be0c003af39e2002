"""mxprec — dtype-flow analysis + committed precision ledgers
(ISSUE 10).

Covers: the hazard classifier on synthetic HLO; four seeded
perturbations that each trip EXACTLY one rule with the op and source
site named (bf16 accumulating reduce, sub-f32 dot, f64 creep, missing
fp32 master weight); the one-dtype-analyzer migration (`summarize`'s
dtype block == dtypeflow's, committed hlocheck contracts keep their
shape); the `python -m tools.mxprec` CLI exit-code/byte-determinism
contract; the `MXTPU_PREC_AUDIT` runtime knob; and the optimizer
multi-precision fix end to end (bf16 params track f32 training within
tolerance while staying bf16, eager and compiled).

Lowerings go through ``analysis.lowered_summary`` — the sanctioned
pre-optimization route — so mxlint's ``hlo-raw-assert`` rule stays
happy.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mxtpu import analysis, nd, parallel
from mxtpu.analysis import dtypeflow
from mxtpu.base import MXNetError
from mxtpu.gluon import nn
from mxtpu.parallel import restore_params, snapshot_params

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------
# synthetic module: a bf16 dot feeding a bf16 accumulating reduce —
# the two textbook AMP hazards — plus one dead upcast for the
# dtype-summary bookkeeping
# ---------------------------------------------------------------------
BF16_SYNTH = """HloModule bf16synth

%accum (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[] parameter(0)
  %y = bf16[] parameter(1)
  ROOT %z = bf16[] add(bf16[] %x, bf16[] %y)
}

ENTRY %main (p0: bf16[8,16], p1: bf16[16,4]) -> bf16[8] {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %p1 = bf16[16,4]{1,0} parameter(1)
  %d = bf16[8,4]{1,0} dot(bf16[8,16]{1,0} %p0, bf16[16,4]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cv = f32[8,4]{1,0} convert(bf16[8,4]{1,0} %d)
  %z = bf16[] constant(0)
  ROOT %r = bf16[8]{0} reduce(bf16[8,4]{1,0} %d, bf16[] %z), dimensions={1}, to_apply=%accum
}
"""

CLEAN_F32 = """HloModule clean

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %r = f32[4]{0} add(f32[4]{0} %p0, f32[4]{0} %p0)
}
"""


def _rules(hazards):
    return [h["rule"] for h in hazards]


# ------------------------------------------------- hazard classifier

def test_hazards_on_synthetic_bf16():
    hz = dtypeflow.hazard_findings(BF16_SYNTH)
    assert sorted(_rules(hz)) == ["bf16-accum-reduction",
                                  "matmul-preferred-type"]
    by_rule = {h["rule"]: h for h in hz}
    assert by_rule["bf16-accum-reduction"]["op"] == "reduce"
    assert by_rule["matmul-preferred-type"]["op"] == "dot"
    # every hazard formats to the one-line audit shape
    for h in hz:
        assert dtypeflow.format_hazard(h).startswith(f"[{h['rule']}]")


def test_clean_f32_has_no_hazards():
    assert dtypeflow.hazard_findings(CLEAN_F32) == []


def test_dtype_summary_counts():
    s = dtypeflow.dtype_summary(BF16_SYNTH)
    assert s["f64_ops"] == 0
    assert s["converts"] == {"bf16->f32": 1}
    assert s["upcasts"] == {"bf16->f32": 1}


def test_program_ledger_shape():
    led = dtypeflow.program_ledger(BF16_SYNTH)
    assert set(led) == {"flows", "float_ops", "hazards"}
    assert led["float_ops"]["bf16"] > 0


# ------------------------------------------------- ONE dtype analyzer

def test_summarize_dtype_block_delegates_to_dtypeflow():
    """hlocheck's `dtype` contract section and dtypeflow must be the
    same analyzer — byte-identical output on the same text."""
    assert analysis.summarize(BF16_SYNTH, {})["dtype"] == \
        dtypeflow.dtype_summary(BF16_SYNTH)


def test_committed_contracts_keep_dtype_shape():
    """The migration is compat: every committed hlocheck contract
    still carries the {converts, f64_ops, upcasts} dtype block."""
    cdir = os.path.join(_ROOT, "contracts")
    foreign = {"lockorder", "amp_policy", "quant_policy"}
    seen = 0
    for fn in sorted(os.listdir(cdir)):
        if not fn.endswith(".json") or fn[:-5] in foreign:
            continue
        with open(os.path.join(cdir, fn)) as f:
            contract = json.load(f)
        for prog, summ in contract["programs"].items():
            assert set(summ["dtype"]) == \
                {"converts", "f64_ops", "upcasts"}, (fn, prog)
            seen += 1
    assert seen >= 6


# --------------------------------------------- seeded perturbations
# each seeds ONE hazard into a real pre-opt lowering and asserts the
# classifier names exactly that rule, the op, and this file as site

def test_seeded_bf16_accum_reduction():
    import jax
    import jax.numpy as jnp

    def softmaxish(a):                       # hand-rolled bf16 sum
        e = jnp.exp(a)
        return jax.lax.reduce(e, jnp.bfloat16(0), jax.lax.add, (1,))

    led = analysis.lowered_summary(
        softmaxish, jnp.ones((4, 8), jnp.bfloat16))
    assert _rules(led["hazards"]) == ["bf16-accum-reduction"]
    h = led["hazards"][0]
    assert h["op"] == "reduce"
    assert "test_prec.py" in h["site"]


def test_seeded_sub_f32_matmul():
    import jax.numpy as jnp

    led = analysis.lowered_summary(
        lambda a, b: a @ b,
        jnp.ones((4, 8), jnp.bfloat16), jnp.ones((8, 2), jnp.bfloat16))
    assert _rules(led["hazards"]) == ["matmul-preferred-type"]
    h = led["hazards"][0]
    assert h["op"] == "dot"
    assert "test_prec.py" in h["site"]
    assert "preferred_element_type" in h["detail"]


def test_seeded_f64_creep():
    import jax.numpy as jnp
    from jax import enable_x64

    with enable_x64():
        led = analysis.lowered_summary(
            lambda a: (a.astype(jnp.float64) * 2.0).sum(),
            jnp.ones((4,), jnp.float32))
    # f64 flows through several ops, but ONLY the f64 rule fires
    assert set(_rules(led["hazards"])) == {"f64-creep"}
    assert any(h["op"] == "convert" and "test_prec.py" in h["site"]
               for h in led["hazards"])


def test_seeded_int8_accum_matmul():
    import jax
    import jax.numpy as jnp

    def q8_dot_no_accum(a, b):
        # tagged like the real pass, so ONLY the accumulation rule
        # fires — the missing preferred_element_type lets the s8xs8
        # product land back in s8
        with jax.named_scope("q8_seeded"):
            return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

    led = analysis.lowered_summary(
        q8_dot_no_accum,
        jnp.ones((4, 8), jnp.int8), jnp.ones((8, 2), jnp.int8))
    assert _rules(led["hazards"]) == ["int8-accum-matmul"]
    h = led["hazards"][0]
    assert h["op"] == "dot"
    assert "test_prec.py" in h["site"]
    assert "preferred_element_type=int32" in h["detail"]


def test_seeded_quant_missing_scale():
    import jax.numpy as jnp
    from jax import lax

    led = analysis.lowered_summary(
        lambda a, b: lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.int32),
        jnp.ones((4, 8), jnp.int8), jnp.ones((8, 2), jnp.int8))
    assert _rules(led["hazards"]) == ["quant-missing-scale"]
    h = led["hazards"][0]
    assert h["op"] == "dot"
    assert "test_prec.py" in h["site"]
    assert "q8_" in h["detail"]


def _bf16_step(x, y, oparams):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, flatten=False), nn.Dense(4, flatten=False))
    net.initialize(init="xavier")
    net(x)
    net.cast("bfloat16")
    return parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), "sgd", dict(oparams))


def test_seeded_missing_master_weight():
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 8).astype(np.float32))
    y = nd.array(rng.randn(4, 4).astype(np.float32))
    step = _bf16_step(x, y, {"learning_rate": 0.05,
                             "multi_precision": False})
    sigs = step.param_sigs(x, y)
    finds = dtypeflow.master_weight_findings(step.optimizer, sigs)
    # one finding per bf16 param, each naming the param as the site
    assert len(finds) == len(sigs) > 0
    assert {f["rule"] for f in finds} == {"master-weight"}
    assert {f["op"] for f in finds} == {"sgd"}
    assert sorted(f["site"] for f in finds) == \
        sorted(name for name, _, _ in sigs)
    # the default (multi_precision unset -> auto) carries the master
    step_on = _bf16_step(x, y, {"learning_rate": 0.05})
    assert dtypeflow.master_weight_findings(
        step_on.optimizer, step_on.param_sigs(x, y)) == []


# ----------------------------------------- optimizer multi-precision

def test_bf16_master_weight_parity():
    """bf16 params + fp32 masters track full-f32 sgd within bf16
    resolution (measured max rel err 2.3e-3 over 5 steps), params
    STAY bf16 across steps, and the optimizer state carries only
    f32 leaves (the masters)."""
    import jax

    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 16).astype(np.float32))
    y = nd.array(rng.randn(8, 4).astype(np.float32))

    def make():
        net = nn.HybridSequential()
        net.add(nn.Dense(16, flatten=False), nn.Dense(4, flatten=False))
        net.initialize(init="xavier")
        net(x)
        return net

    loss = lambda p, t: ((p - t) ** 2).mean()  # noqa: E731
    net_f = make()
    snap = snapshot_params(net_f)
    net_b = make()
    restore_params(net_b, snap)
    net_b.cast("bfloat16")

    step_f = parallel.build_train_step(net_f, loss, "sgd",
                                       {"learning_rate": 0.05})
    step_b = parallel.build_train_step(net_b, loss, "sgd",
                                       {"learning_rate": 0.05})
    lf = [float(step_f(x, y).asscalar()) for _ in range(5)]
    lb = [float(step_b(x, y).asscalar()) for _ in range(5)]
    np.testing.assert_allclose(lf, lb, rtol=0.02, atol=1e-3)
    assert lf[-1] < lf[0]  # both actually trained

    # weights never left bf16 (the pre-fix failure mode: the sgd rule
    # promoted them to f32 on step one and step two blew up)
    sigs = step_b.param_sigs(x, y)
    assert {dt for _, _, dt in sigs} == {"bfloat16"}
    # plain sgd has no base state, so every state leaf IS a master
    leaves = jax.tree_util.tree_leaves(step_b._opt_state)
    assert leaves and {str(l.dtype) for l in leaves} == {"float32"}
    assert dtypeflow.master_weight_findings(step_b.optimizer,
                                            sigs) == []


def test_eager_multi_precision_update():
    """The eager (gluon.Trainer) path: create_state_multi_precision
    hangs an f32 master off the state and update_multi_precision
    downcasts once per step."""
    from mxtpu import optimizer as optmod

    opt = optmod.SGD(learning_rate=0.1)
    w = nd.array(np.ones((4,), np.float32)).astype("bfloat16")
    g = nd.array(np.full((4,), 0.5, np.float32)).astype("bfloat16")
    state = opt.create_state_multi_precision(0, w)
    master = state[0]
    assert str(np.dtype(master.dtype)) == "float32"
    opt.update_multi_precision(0, w, g, state)
    assert "bfloat16" in str(np.dtype(w.dtype))
    got = w.asnumpy().astype(np.float32)
    # 1 - 0.1*0.5 = 0.95, rounded to the nearest bf16 (0.949219)
    np.testing.assert_allclose(got, np.full((4,), 0.949219), atol=1e-4)


# ------------------------------------------------------ runtime audit

class _FakeCompiled:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_prec_audit_knob(monkeypatch):
    for k in ("MXTPU_PREC_AUDIT", "MXNET_PREC_AUDIT",
              "MXTPU_HLO_AUDIT", "MXNET_HLO_AUDIT"):
        monkeypatch.delenv(k, raising=False)
    dirty = _FakeCompiled(BF16_SYNTH)
    assert analysis.maybe_audit(dirty, label="t", mem={}) is None
    monkeypatch.setenv("MXTPU_PREC_AUDIT", "1")
    with pytest.warns(RuntimeWarning, match="precision audit"):
        analysis.maybe_audit(dirty, label="t", mem={})
    monkeypatch.setenv("MXTPU_PREC_AUDIT", "2")
    with pytest.raises(MXNetError, match="MXTPU_PREC_AUDIT=2"):
        analysis.maybe_audit(dirty, label="t", mem={})
    # a clean program passes silently even in raise mode
    assert analysis.maybe_audit(_FakeCompiled(CLEAN_F32), label="t",
                                mem={}) is not None


# ---------------------------------------------------------------- CLI

def _mxprec(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.mxprec", *args],
        capture_output=True, text=True, cwd=_ROOT, timeout=240)


def test_cli_roundtrip_determinism_and_drift(tmp_path):
    """--update then --check is a fixed point; two --update runs are
    byte-identical; a corrupted ledger fails with the target named."""
    d = str(tmp_path)
    up1 = _mxprec("--update", "selftest", "--contracts-dir", d)
    assert up1.returncode == 0, up1.stdout + up1.stderr
    path = tmp_path / "prec" / "selftest.json"
    first = path.read_bytes()

    up2 = _mxprec("--update", "selftest", "--contracts-dir", d)
    assert up2.returncode == 0, up2.stdout + up2.stderr
    assert path.read_bytes() == first  # byte-deterministic

    ok = _mxprec("--check", "selftest", "--contracts-dir", d)
    assert ok.returncode == 0, ok.stdout + ok.stderr

    ledger = json.loads(first)
    prog = next(iter(ledger["programs"]))
    ledger["programs"][prog]["float_ops"]["f64"] = 7
    path.write_text(json.dumps(ledger, indent=1, sort_keys=True)
                    + "\n")
    bad = _mxprec("--check", "selftest", "--contracts-dir", d)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "selftest" in bad.stdout


def test_cli_usage_errors(tmp_path):
    unk = _mxprec("--check", "no_such_target")
    assert unk.returncode == 2
    assert "unknown target" in unk.stderr

    empty = _mxprec("--check", "--contracts-dir", str(tmp_path))
    assert empty.returncode == 2
    assert "no ledgers" in empty.stderr

    (tmp_path / "prec").mkdir()
    (tmp_path / "prec" / "ghost.json").write_text("{}\n")
    orphan = _mxprec("--check", "--contracts-dir", str(tmp_path))
    assert orphan.returncode == 2
    assert "ghost" in orphan.stderr


@pytest.mark.slow
def test_committed_prec_ledgers_check_clean():
    """THE acceptance check: the committed tree passes a full
    `python -m tools.mxprec --check` (ledgers + amp_policy + README
    table) with exit 0."""
    r = _mxprec("--check")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 violation(s)" in r.stdout


def test_amp_policy_is_machine_derived():
    """amp_policy.json carries the four op classes with per-target
    evidence plus the kernel custom-call metadata — the exact inputs
    the AMP PR consumes."""
    with open(os.path.join(_ROOT, "contracts",
                           "amp_policy.json")) as f:
        policy = json.load(f)
    for cls in ("allow", "deny", "fp32_force", "inherit"):
        assert policy[cls], cls
        for op, entry in policy[cls].items():
            assert entry["reason"]
            assert entry["evidence"]  # {target: float-op count}
    assert "dot" in policy["allow"]
    assert "exponential" in policy["deny"]
    assert "reduce" in policy["fp32_force"]
    assert set(policy["custom_calls"]) == \
        {"batch_norm", "flash_attention", "layer_norm", "kv_write",
         "ssm_update"}
    for name, meta in policy["custom_calls"].items():
        # the column store of the KV table only moves bits
        assert meta["accum_dtype"] == \
            ("none" if name == "kv_write" else "f32")


def test_quant_policy_is_machine_derived():
    """quant_policy.json carries the allow/deny classes with
    per-target evidence plus the calibration block — thresholds under
    both estimators, per-channel weight scales, and the int8
    contraction census the serving contract pins."""
    with open(os.path.join(_ROOT, "contracts",
                           "quant_policy.json")) as f:
        policy = json.load(f)
    assert policy["targets"] == ["resnet18", "serving_bert"]
    for cls in ("allow", "deny"):
        assert policy[cls], cls
        for op, entry in policy[cls].items():
            assert entry["reason"], op
    assert "dot" in policy["allow"]
    assert "convolution" in policy["allow"]
    for op, entry in policy["allow"].items():
        assert entry["evidence"], op  # {target: float-op count}
    assert "exponential" in policy["deny"]
    assert "rsqrt" in policy["deny"]
    calib = policy["calibration"]
    th = calib["activation_thresholds"]
    assert set(th) == {"entropy", "minmax"}
    assert set(th["entropy"]) == set(th["minmax"]) \
        == set(calib["weight_scales"])
    for key, scales in calib["weight_scales"].items():
        assert scales and all(s > 0 for s in scales), key
    for census in calib["int8_contractions"].values():
        assert census == {"s8xs8->s32": 9}
