"""mxmem — static memory-footprint analysis + committed HBM ledgers
(ISSUE 20).

Covers: the decomposition/attribution units on synthetic programs and
mem stats; the five hazard rules, each tripped by EXACTLY one seeded
perturbation with the buffer and site named (drop ``donate`` →
donation-missed; ``zero=0`` under a declared-ZeRO record →
zero-replication; grow the slot table past the declared
``kv_cache_spec`` → kv-overcommit; pad past the waste threshold →
padding-waste; shrink a device-class budget → budget-exceeded); the
ONE-memory-analyzer migration (committed hlocheck peak-bytes budgets
stay byte-compatible with the ledgers); the ``python -m tools.mxmem``
CLI exit-code/byte-determinism contract; the ``MXTPU_MEM_AUDIT``
runtime knob; and the committed-ledger acceptance proofs (bert_zero
opt-state ≤ planned shard geometry, generate_decode KV table ==
declared geometry + scratch slot).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mxtpu import analysis, nd, parallel
from mxtpu.analysis import memflow
from mxtpu.base import MXNetError
from mxtpu.gluon import nn

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a synthetic reduce-scatter program for collective-scratch
# attribution: 1024 f32 elems scattered to 128 per shard
RS_SYNTH = """HloModule rssynth

%sum (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %z = f32[] add(f32[] %x, f32[] %y)
}

ENTRY %main (p0: f32[1024]) -> f32[128] {
  %p0 = f32[1024]{0} parameter(0)
  ROOT %rs = f32[128]{0} reduce-scatter(f32[1024]{0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, to_apply=%sum
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


class _FakeMA:
    argument_size_in_bytes = 1000
    output_size_in_bytes = 100
    temp_size_in_bytes = 500
    alias_size_in_bytes = 40
    generated_code_size_in_bytes = 7


class _FakeCompiled:
    def __init__(self, text=CLEAN_F32, ma=None):
        self._text = text
        self._ma = ma if ma is not None else _FakeMA()

    def as_text(self):
        return self._text

    def memory_analysis(self):
        return self._ma


# ------------------------------------------------ attribution units

def test_mem_stats_hbm_peak_convention():
    """hbm_peak is temp + argument — the repo-wide convention every
    committed peak-bytes budget pins, now owned by memflow alone."""
    mem = memflow.mem_stats(_FakeCompiled())
    assert mem["hbm_peak"] == 1500
    assert mem["alias_size_in_bytes"] == 40
    # parallel._mem_stats is the same analyzer
    assert parallel._mem_stats(_FakeCompiled()) == mem
    # a backend that doesn't report yields None, not a crash
    class _NoMA:
        def memory_analysis(self):
            raise RuntimeError("unimplemented")
    assert memflow.mem_stats(_NoMA()) is None


def test_decompose_categories():
    mem = {"argument_size_in_bytes": 1000, "temp_size_in_bytes": 500,
           "output_size_in_bytes": 100, "alias_size_in_bytes": 40}
    d = memflow.decompose(mem, params_bytes=600, opt_state_bytes=300,
                          kv_table_bytes=0, collective_scratch=64)
    assert d["peak_hbm"] == 1500          # temp + argument, exactly
    assert d["params"] == 600
    assert d["opt_state"] == 300
    assert d["inputs_other"] == 100       # argument remainder
    assert d["activations_temps"] == 500
    assert d["collectives_scratch"] == 64
    assert d["donated_aliased"] == 40
    # over-attribution clamps the remainder at zero instead of going
    # negative (donated args leave the argument count)
    d2 = memflow.decompose(mem, params_bytes=2000)
    assert d2["inputs_other"] == 0
    assert memflow.decompose(None)["peak_hbm"] == 0


def test_collective_scratch_attribution():
    # 128 f32 elems materialized by the reduce-scatter result
    assert memflow.collective_scratch_bytes(RS_SYNTH) == 512
    assert memflow.collective_scratch_bytes(CLEAN_F32) == 0


def test_kv_expected_bytes_geometry():
    # (layers=2, kv=2, lanes=2, heads=2, L=32, head=32) f32 + 1
    # scratch slot: 2*2*3*2*32*32*4
    assert memflow.kv_expected_bytes((2, 2, 2, 2, 32, 32)) == 98304


def test_planned_shard_bytes_oracle():
    sigs = [((16, 16), "float32")] * 4
    planned = memflow.planned_shard_bytes(sigs, 8, 2)
    buckets = parallel.plan_zero_buckets(sigs, 8)
    assert planned == sum(2 * b["padded_bytes"] // 8 for b in buckets)


# --------------------------------------------- seeded perturbations
# each trips EXACTLY one rule, with the buffer and site named

def _donation_record(declared):
    return {"target": "t", "programs": {"step": {
        "mem": {"argument_size_in_bytes": 64,
                "temp_size_in_bytes": 0},
        "donation": {"declared": declared,
                     "donatable": {"0": {"label": "train_vals",
                                         "bytes": 48}}}}}}


def test_seeded_donation_missed():
    led = memflow.build_ledger(_donation_record(declared=[]))
    assert _rules(led["hazards"]) == ["donation-missed"]
    h = led["hazards"][0]
    assert h["op"] == "parameter"
    assert h["site"] == "step:arg0"
    assert "train_vals" in h["detail"]
    assert "donate_argnums" in h["detail"]
    # declaring the donation clears it
    assert memflow.build_ledger(
        _donation_record(declared=[0]))["hazards"] == []


def test_seeded_donation_missed_real_step():
    """Dropping TrainStep's donate_argnums=(0, 2) path surfaces both
    donatable buffers (train_vals + opt_state) under the ONE
    donation-missed rule."""
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 8).astype(np.float32))
    y = nd.array(rng.randn(4, 4).astype(np.float32))
    net = nn.HybridSequential()
    net.add(nn.Dense(8, flatten=False), nn.Dense(4, flatten=False))
    net.initialize(init="xavier")
    net(x)
    step = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), "sgd",
        {"learning_rate": 0.05}, donate=False)
    step(x, y)
    record = memflow.train_step_record(step, x, y, "nodonate")
    led = memflow.build_ledger(record)
    assert set(_rules(led["hazards"])) == {"donation-missed"}
    sites = sorted(h["site"] for h in led["hazards"])
    assert sites == ["train_step:arg0", "train_step:arg2"]
    # the default (donate=True) is clean
    step_on = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), "sgd",
        {"learning_rate": 0.05})
    step_on(x, y)
    led_on = memflow.build_ledger(
        memflow.train_step_record(step_on, x, y, "donate"))
    assert led_on["hazards"] == []


def _mesh(n=8):
    import jax
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]), ("dp",))


def test_seeded_zero_replication():
    """zero=0 forced under a record declared to shard: measured
    opt-state bytes exceed the plan_zero_buckets geometry and
    EXACTLY zero-replication fires, naming the opt-state buffer."""
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 16).astype(np.float32))
    y = nd.array(rng.randn(8, 4).astype(np.float32))
    net = nn.HybridSequential()
    net.add(nn.Dense(16, flatten=False), nn.Dense(4, flatten=False))
    net.initialize(init="xavier")
    net(x)
    step = parallel.build_train_step(
        net, lambda p, t: ((p - t) ** 2).mean(), "adam",
        {"learning_rate": 1e-3}, mesh=_mesh(), zero=0)
    assert not step.zero
    step(x, y)
    record = memflow.train_step_record(step, x, y, "zero_pert",
                                       zero_expected=True)
    led = memflow.build_ledger(record)
    assert _rules(led["hazards"]) == ["zero-replication"]
    h = led["hazards"][0]
    assert h["op"] == "opt-state"
    assert h["site"] == "zero_pert:opt_state"
    assert "replicated" in h["detail"]
    z = record["zero"]
    assert z["opt_state_bytes"] > z["planned_shard_bytes"]


def test_seeded_kv_overcommit():
    """A slot table grown past the declared kv_cache_spec geometry
    (+1 scratch slot) trips exactly kv-overcommit."""
    spec = (2, 2, 2, 2, 32, 32)
    ok = memflow.kv_expected_bytes(spec)
    record = {"target": "gen", "programs": {},
              "kv": {"spec": list(spec), "itemsize": 4,
                     "table_bytes": ok, "expected_bytes": ok}}
    assert memflow.build_ledger(record)["hazards"] == []
    # two extra lanes past the spec: 5 slots instead of 3
    grown = dict(record, kv=dict(record["kv"],
                                 table_bytes=ok // 3 * 5))
    led = memflow.build_ledger(grown)
    assert _rules(led["hazards"]) == ["kv-overcommit"]
    h = led["hazards"][0]
    assert h["op"] == "kv-table"
    assert h["site"] == "gen:kv_table"
    assert "kv_cache_spec" in h["detail"]


def test_seeded_padding_waste():
    record = {"target": "t", "programs": {},
              "padding": [
                  {"site": "zero_bucket0[(4, 16, 16):float32]",
                   "used_bytes": 1 << 20,
                   "padded_bytes": (1 << 20) + (1 << 19)}]}
    led = memflow.build_ledger(record)
    assert _rules(led["hazards"]) == ["padding-waste"]
    h = led["hazards"][0]
    assert h["op"] == "pad"
    assert "zero_bucket0" in h["site"]
    # under the 25% threshold (or under the absolute floor): clean
    small = {"target": "t", "programs": {},
             "padding": [{"site": "b", "used_bytes": 1 << 20,
                          "padded_bytes": (1 << 20) + (1 << 17)}]}
    assert memflow.build_ledger(small)["hazards"] == []
    tiny = {"target": "t", "programs": {},
            "padding": [{"site": "b", "used_bytes": 64,
                         "padded_bytes": 512}]}
    assert memflow.build_ledger(tiny)["hazards"] == []


def test_seeded_budget_exceeded():
    """Shrinking the target's device-class budget below its peak
    trips exactly budget-exceeded, naming the program."""
    record = {"target": "t", "programs": {"step": {
        "mem": {"argument_size_in_bytes": 1000,
                "temp_size_in_bytes": 500}}}}
    budgets = {"classes": {"nano": {"bytes": 1400}},
               "default_class": "nano", "targets": {}}
    led = memflow.build_ledger(record, budgets)
    assert _rules(led["hazards"]) == ["budget-exceeded"]
    h = led["hazards"][0]
    assert h["op"] == "program"
    assert h["site"] == "step"
    assert "1500" in h["detail"] and "nano" in h["detail"]
    # a roomy class is clean, and headroom is recorded
    budgets["classes"]["nano"]["bytes"] = 1 << 30
    led_ok = memflow.build_ledger(record, budgets)
    assert led_ok["hazards"] == []
    assert led_ok["budget_bytes"] == 1 << 30
    assert 0 < led_ok["headroom_frac"] < 1


# --------------------------------------------- committed acceptance

def _load_ledger(name):
    with open(os.path.join(_ROOT, "contracts", "mem",
                           f"{name}.json")) as f:
        return json.load(f)


def test_committed_bert_zero_proves_shard_geometry():
    """THE ZeRO acceptance proof: the committed ledger's measured
    per-device opt-state bytes are ≤ the plan_zero_buckets geometry
    (equality on this padding-free fixture), at exactly 1/8 of the
    replicated baseline's."""
    z = _load_ledger("bert_zero")["zero"]
    assert z["expected"] and z["sharded"]
    assert z["opt_state_bytes"] <= z["planned_shard_bytes"]
    r = _load_ledger("bert_replicated")["zero"]
    assert not r["expected"]
    assert z["opt_state_bytes"] * 8 == r["opt_state_bytes"]


def test_committed_generate_decode_proves_kv_geometry():
    """THE KV acceptance proof: the committed table bytes equal the
    declared kv_cache_spec geometry + 1 scratch slot, and the decode
    program donates the table."""
    led = _load_ledger("generate_decode")
    kv = led["kv"]
    assert kv["table_bytes"] == kv["expected_bytes"]
    assert kv["table_bytes"] == memflow.kv_expected_bytes(
        kv["spec"], kv["itemsize"])
    decode = led["programs"]["decode_step"]
    don = decode["donation"]
    assert don["declared"], "decode KV table must be donated"
    assert don["donatable"][str(don["declared"][0])]["label"] \
        == "kv_table"


def test_committed_ledgers_hazard_free_and_peak_compatible():
    """Every committed mem ledger is hazard-free, and where the
    hlocheck contract pins a peak-bytes budget for the same program
    the two analyzers agree byte-for-byte (the ONE-analyzer
    migration kept hbm_peak compatible)."""
    mdir = os.path.join(_ROOT, "contracts", "mem")
    names = sorted(fn[:-5] for fn in os.listdir(mdir)
                   if fn.endswith(".json") and fn != "budgets.json")
    assert len(names) >= 9
    checked = 0
    for name in names:
        led = _load_ledger(name)
        assert led["hazards"] == [], (name, led["hazards"])
        cpath = os.path.join(_ROOT, "contracts", f"{name}.json")
        if not os.path.exists(cpath):
            continue
        with open(cpath) as f:
            contract = json.load(f)
        for prog, summ in contract["programs"].items():
            pinned = (summ.get("budgets") or {}).get("peak_bytes")
            if pinned is None or prog not in led["programs"]:
                continue
            dec = led["programs"][prog]["decomposition"]
            assert dec["peak_hbm"] == pinned, (name, prog)
            checked += 1
    assert checked >= 6


def test_budgets_are_declarative():
    with open(os.path.join(_ROOT, "contracts", "mem",
                           "budgets.json")) as f:
        budgets = json.load(f)
    assert budgets["classes"]["hbm16"]["bytes"] == 16 * 1024 ** 3
    assert budgets["default_class"] in budgets["classes"]
    # every committed ledger resolves to a real class with headroom
    cls, limit = memflow.resolve_budget("anything", budgets)
    assert cls and limit


# ------------------------------------------------------ runtime audit

def test_mem_audit_knob(monkeypatch):
    for k in ("MXTPU_MEM_AUDIT", "MXNET_MEM_AUDIT",
              "MXTPU_MEM_BUDGET", "MXNET_MEM_BUDGET",
              "MXTPU_HLO_AUDIT", "MXTPU_PREC_AUDIT"):
        monkeypatch.delenv(k, raising=False)
    fat = _FakeCompiled()  # peak 1500 B
    # off: no parse, no findings
    assert analysis.maybe_audit(fat, label="t") is None
    # warn: peak over a 1-byte budget
    monkeypatch.setenv("MXTPU_MEM_AUDIT", "1")
    monkeypatch.setenv("MXTPU_MEM_BUDGET", "1")
    with pytest.warns(RuntimeWarning, match="memory audit"):
        analysis.maybe_audit(fat, label="t")
    # raise
    monkeypatch.setenv("MXTPU_MEM_AUDIT", "2")
    with pytest.raises(MXNetError, match="MXTPU_MEM_AUDIT=2"):
        analysis.maybe_audit(fat, label="t")
    # a program under budget passes silently even in raise mode
    monkeypatch.setenv("MXTPU_MEM_BUDGET", "1000000")
    assert analysis.maybe_audit(fat, label="t") is not None
    # the stamp records the mode for cache-reaudit decisions
    assert analysis.audit_stamp()["mem_audit"] == 2
    assert analysis.needs_reaudit({"hlo_audit": 0, "prec_audit": 0})


def test_mem_audit_findings_direct():
    from mxtpu import knobs
    assert memflow.mem_audit_findings(None, "x") == []
    assert memflow.mem_audit_findings({}, "x") == []
    # explicit budget override via the knob
    old = os.environ.get("MXTPU_MEM_BUDGET")
    os.environ["MXTPU_MEM_BUDGET"] = "100"
    try:
        out = memflow.mem_audit_findings({"hbm_peak": 1500}, "prog")
        assert len(out) == 1
        assert "1500" in out[0] and "prog" in out[0]
    finally:
        if old is None:
            os.environ.pop("MXTPU_MEM_BUDGET", None)
        else:
            os.environ["MXTPU_MEM_BUDGET"] = old


# ---------------------------------------------------------------- CLI

def _mxmem(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.mxmem", *args],
        capture_output=True, text=True, cwd=_ROOT, timeout=240)


def test_cli_roundtrip_determinism_and_drift(tmp_path):
    """--update then --check is a fixed point; two --update runs are
    byte-identical; budgets.json is bootstrapped once and never
    overwritten; a corrupted ledger fails with the target named."""
    d = str(tmp_path)
    up1 = _mxmem("--update", "selftest", "--contracts-dir", d)
    assert up1.returncode == 0, up1.stdout + up1.stderr
    path = tmp_path / "mem" / "selftest.json"
    first = path.read_bytes()
    bpath = tmp_path / "mem" / "budgets.json"
    assert bpath.exists()

    # budgets are hand-edited policy: --update must not rewrite them
    budgets = json.loads(bpath.read_text())
    budgets["classes"]["custom"] = {"bytes": 123456, "doc": "mine"}
    bpath.write_text(json.dumps(budgets, indent=1, sort_keys=True)
                     + "\n")
    edited = bpath.read_bytes()

    up2 = _mxmem("--update", "selftest", "--contracts-dir", d)
    assert up2.returncode == 0, up2.stdout + up2.stderr
    assert path.read_bytes() == first  # byte-deterministic
    assert bpath.read_bytes() == edited  # never regenerated

    ok = _mxmem("--check", "selftest", "--contracts-dir", d)
    assert ok.returncode == 0, ok.stdout + ok.stderr

    ledger = json.loads(first)
    ledger["programs"]["eigh_matmul"]["decomposition"]["peak_hbm"] += 8
    path.write_text(json.dumps(ledger, indent=1, sort_keys=True)
                    + "\n")
    bad = _mxmem("--check", "selftest", "--contracts-dir", d)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "selftest" in bad.stdout


def test_cli_usage_errors(tmp_path):
    unk = _mxmem("--check", "no_such_target")
    assert unk.returncode == 2
    assert "unknown target" in unk.stderr

    empty = _mxmem("--check", "--contracts-dir", str(tmp_path))
    assert empty.returncode == 2
    assert "no ledgers" in empty.stderr

    (tmp_path / "mem").mkdir()
    (tmp_path / "mem" / "ghost.json").write_text("{}\n")
    orphan = _mxmem("--check", "--contracts-dir", str(tmp_path))
    assert orphan.returncode == 2
    assert "ghost" in orphan.stderr


@pytest.mark.slow
def test_committed_mem_ledgers_check_clean():
    """THE acceptance check: the committed tree passes a full
    `python -m tools.mxmem --check` (ledgers + README table) with
    exit 0."""
    r = _mxmem("--check")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 violation(s)" in r.stdout
