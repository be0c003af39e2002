"""mxtpu.analysis + tools/hlocheck (ISSUE 6).

Covers: the HLO parser on synthetic text; every one of the five
contract rule families tripped by a perturbation that touches ONLY
that family; the lockfile round-trip (``--update`` then ``--check``
is a fixed point, a corrupted lockfile fails with the right rule,
an unknown target is a usage error); two-lowering stability of
summaries; the ``MXTPU_HLO_AUDIT`` runtime knob; and the
``program_summary`` wiring on serving's ``ModelRunner``
(``TrainStep``'s is pinned by tests/test_zero.py).

Compiled programs are reached through ``analysis.compiled_summary``
/ ``compiled_artifact`` only — mxlint's ``hlo-raw-assert`` rule keeps
raw ``.lower()``/``hlo_text()`` grepping out of tests/.
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mxtpu import analysis
from mxtpu.analysis import contracts as C
from mxtpu.base import MXNetError

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------
# synthetic module: one fusion hiding bracket ops, one custom call,
# two collectives, one f64 parameter + downcast, a dead convert whose
# line each perturbation below swaps for its own poison
# ---------------------------------------------------------------------
_CV_LINE = "  %cv = f32[4]{0} convert(f64[4]{0} %p1)"
_CT_LINE = ("  %ct = f32[16,8]{1,0} transpose(f32[8,16]{1,0} %cc), "
            "dimensions={1,0}")

SYNTH = f"""HloModule synth

%add (x: f32[], y: f32[]) -> f32[] {{
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %z = f32[] add(f32[] %x, f32[] %y)
}}

%wrapped_fusion (param_0: f32[8,16]) -> f32[8,16] {{
  %param_0 = f32[8,16]{{1,0}} parameter(0)
  %t = f32[16,8]{{1,0}} transpose(f32[8,16]{{1,0}} %param_0), dimensions={{1,0}}
  ROOT %c = f32[8,16]{{1,0}} copy(f32[16,8]{{1,0}} %t)
}}

ENTRY %main (p0: f32[8,16], p1: f64[4]) -> (f32[8,16], f32[2,16]) {{
  %p0 = f32[8,16]{{1,0}} parameter(0)
  %p1 = f64[4]{{0}} parameter(1)
{_CV_LINE}
  %fu = f32[8,16]{{1,0}} fusion(f32[8,16]{{1,0}} %p0), kind=kLoop, calls=%wrapped_fusion
  %ar = f32[8,16]{{1,0}} all-reduce(f32[8,16]{{1,0}} %fu), replica_groups={{}}, to_apply=%add
  %rs = f32[2,16]{{1,0}} reduce-scatter(f32[8,16]{{1,0}} %ar), replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, to_apply=%add
  %cc = f32[8,16]{{1,0}} custom-call(f32[8,16]{{1,0}} %fu), custom_call_target="my_kernel"
{_CT_LINE}
  ROOT %tup = (f32[8,16]{{1,0}}, f32[2,16]{{1,0}}) tuple(f32[16,8]{{1,0}} %ct, f32[2,16]{{1,0}} %rs)
}}
"""

CLEAN = """HloModule clean

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %r = f32[4]{0} add(f32[4]{0} %p0, f32[4]{0} %p0)
}
"""


def _summ(text):
    return analysis.summarize(text, {"hbm_peak": 4096})


def _rules(violations):
    return {v.rule for v in violations}


# ------------------------------------------------------------- parser

def test_parser_structure():
    prog = analysis.parse_hlo(SYNTH)
    assert set(prog.computations) == {"add", "wrapped_fusion", "main"}
    assert prog.entry_name == "main"
    assert prog.instruction_count() == 15
    assert prog.count_opcode("transpose") == 2
    main = prog.entry
    cc = main.by_name["cc"]
    assert cc.opcode == "custom-call" and cc.target == "my_kernel"
    fu = main.by_name["fu"]
    assert "wrapped_fusion" in fu.calls
    ar = main.by_name["ar"]
    assert ar.result_bytes() == 8 * 16 * 4
    assert ar.result_elems() == 128
    tup = main.by_name["tup"]
    assert tup.root
    assert tup.shapes == [("f32", (8, 16)), ("f32", (2, 16))]
    assert tup.result_bytes() == 512 + 128
    # consumers see through operand lists
    assert {i.name for i in main.consumers("cc")} == {"ct"}


def _scaled_rowsum(x):
    import jax
    import jax.numpy as jnp
    return jax.lax.reduce(x * 2.0, jnp.float32(0), jax.lax.add, (1,))


def test_parser_reads_the_preopt_dialect():
    """``lowered.as_text(dialect="hlo")`` prints bare names (no ``%``),
    no operand types and headers without a parameter list; operands,
    called computations and source sites must still resolve."""
    from mxtpu.analysis.dtypeflow import instr_site
    text = analysis.lowered_text(_scaled_rowsum,
                                 np.ones((4, 8), np.float32))
    assert "%" not in text and "stack_frame_id" not in text
    prog = analysis.parse_hlo(text)
    entry = prog.entry
    (red,) = [i for i in entry.instructions if i.opcode == "reduce"]
    assert len(red.operands) == 2
    assert all(o in entry.by_name for o in red.operands)
    assert entry.by_name[red.operands[0]].opcode == "multiply"
    (region,) = red.calls
    assert [i.opcode for i in prog.computations[region].instructions
            if i.root] == ["add"]
    op_name, site = instr_site(red)
    assert op_name.endswith("reduce")
    assert site.startswith("tests/test_analysis.py:")
    # literals name no operand
    assert all(not i.operands for i in prog.all_instructions()
               if i.opcode in ("parameter", "constant"))


def test_lowered_text_does_not_depend_on_the_call_site():
    """The dump's location tables hold every outer frame, the caller
    of ``lower()`` included; ``inline_source_sites`` keeps only each
    instruction's own site, so one program lowered from two lines is
    one text (what the kill-switch bit-identity tests compare)."""
    x = np.ones((4, 8), np.float32)
    a = analysis.lowered_text(_scaled_rowsum, x)
    b = analysis.lowered_text(_scaled_rowsum, x)
    assert a == b
    assert "FileLocations" not in a and 'source_file="' in a


def test_inline_source_sites_resolves_innermost_frame():
    from mxtpu.analysis.hlo import inline_source_sites
    text = """HloModule m

FileNames
1 "/a/outer.py"
2 "/a/inner.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=3 end_line=3 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=41 end_line=42 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

ENTRY main {
  a = f32[4]{0} parameter(0), metadata={op_name="a"}
  ROOT n = f32[4]{0} negate(a), metadata={op_name="neg" stack_frame_id=2}
}
"""
    out = inline_source_sites(text)
    assert 'metadata={op_name="neg" source_file="/a/inner.py" ' \
           'source_line=41}' in out
    assert "outer.py" not in out and "StackFrames" not in out
    # a frame id with no table row is dropped, not kept dangling
    assert "stack_frame_id" not in inline_source_sites(
        text.replace("stack_frame_id=2", "stack_frame_id=9"))


def test_summary_families():
    s = _summ(SYNTH)
    assert s["collectives"] == {
        "all-reduce": {"count": 1, "bytes": 512, "max_elems": 128},
        "reduce-scatter": {"count": 1, "bytes": 128, "max_elems": 32},
    }
    # feeds: transpose+copy hidden in the fusion; consumes: %ct
    assert s["custom_calls"] == {
        "my_kernel": {"count": 1, "bracketed": 3}}
    assert s["dtype"]["f64_ops"] == 1          # the %p1 parameter
    assert s["dtype"]["converts"] == {"f64->f32": 1}
    assert s["dtype"]["upcasts"] == {}         # downcast is not creep
    assert s["budgets"] == {"instruction_count": 15, "fusion_count": 1,
                            "peak_bytes": 4096}
    assert s["host_transfers"] == {"count": 0, "ops": {}}


def test_bracket_evidence_rows():
    rows = analysis.bracket_evidence(analysis.parse_hlo(SYNTH))
    assert len(rows) == 3
    feeds = [r for r in rows if r["side"] == "feeds"]
    assert {r["op"] for r in feeds} == {"transpose", "copy"}
    assert all(r["via"] == "fu" for r in feeds)
    (consume,) = [r for r in rows if r["side"] == "consumes"]
    assert consume["op"] == "transpose" and consume["via"] == ""
    table = analysis.format_evidence_table(rows)
    assert "my_kernel" in table and "feeds" in table


# -------------------------------------------------- contract families

def test_contract_fixed_point_on_identical_summary():
    s = _summ(SYNTH)
    v, n = C.check_contract(C.make_contract("synth", {"p": s}),
                            {"p": copy.deepcopy(s)})
    assert v == [] and n == []


_AG_LINE = ("  %ag = f32[8,16]{1,0} all-gather(f32[8,16]{1,0} %fu), "
            "replica_groups={{0,1,2,3}}, dimensions={0}\n  %cc =")
_PERTURBATIONS = [
    # each mutation must trip its family and ONLY its family
    ("collective-new",
     lambda t: t.replace("  %cc =", _AG_LINE), "collectives"),
    ("collective-vanished",
     lambda t: t.replace("reduce-scatter(", "add("), "collectives"),
    ("custom-call-vanished",
     lambda t: t.replace("custom-call(", "negate("),
     "custom-call-bracket"),
    ("bracket-growth",       # a new copy consuming the custom call
     lambda t: t.replace(
         _CV_LINE, "  %cv = f32[8,16]{1,0} copy(f32[8,16]{1,0} %cc)"),
     "custom-call-bracket"),
    ("dtype-upcast",         # f64 result + f32->f64 convert appear
     lambda t: t.replace(
         _CV_LINE,
         "  %cv = f64[8,16]{1,0} convert(f32[8,16]{1,0} %p0)"),
     "dtype-policy"),
    ("host-transfer",
     lambda t: t.replace(
         _CV_LINE, "  %cv = token[] outfeed(f32[8,16]{1,0} %p0)"),
     "host-transfer"),
    ("budget-blowout",       # +4/15 instructions > the 10% tolerance
     lambda t: t.replace("  ROOT %tup", "".join(
         f"  %d{i} = f32[8,16]{{1,0}} add(f32[8,16]{{1,0}} %p0, "
         f"f32[8,16]{{1,0}} %p0)\n" for i in range(4)) + "  ROOT %tup"),
     "budget"),
]


@pytest.mark.parametrize(
    "mut,rule", [(m, r) for _, m, r in _PERTURBATIONS],
    ids=[name for name, _, _ in _PERTURBATIONS])
def test_synth_perturbation_trips_exactly_one_family(mut, rule):
    contract = C.make_contract("synth", {"p": _summ(SYNTH)})
    v, _ = C.check_contract(contract, {"p": _summ(mut(SYNTH))})
    assert v, f"perturbation did not trip {rule}"
    assert _rules(v) == {rule}


def test_budget_improvement_is_a_notice_not_a_violation():
    _, bloat, _ = _PERTURBATIONS[-1]
    contract = C.make_contract("synth", {"p": _summ(bloat(SYNTH))})
    v, n = C.check_contract(contract, {"p": _summ(SYNTH)})
    assert v == []
    assert any("improved" in x for x in n)


def test_missing_and_extra_programs_are_contract_violations():
    s = _summ(SYNTH)
    contract = C.make_contract("synth", {"p": s})
    v, _ = C.check_contract(contract, {"p": s, "extra": s})
    assert _rules(v) == {"contract"}
    v, _ = C.check_contract(contract, {})
    assert _rules(v) == {"contract"}


# ------------------------------------------- compiled perturbations

_LOOSE = {"instruction_count": 10.0, "fusion_count": 10.0,
          "peak_bytes": 10.0}


def _eigh_base(a):
    import jax.numpy as jnp
    w, _ = jnp.linalg.eigh(a + a.T)
    return w.sum()


def _eigh_pert(a):
    # same eigh custom call, but a transposed operand and an
    # eigenvector consumer force extra layout ops at the boundary
    import jax.numpy as jnp
    w, v = jnp.linalg.eigh(jnp.transpose(a @ a))
    return (v * w).sum()


def _sym_input():
    return np.arange(64.0, dtype=np.float32).reshape(8, 8) / 64.0


def test_compiled_bracket_perturbation_trips():
    a = _sym_input()
    base = analysis.compiled_summary(_eigh_base, a)
    pert = analysis.compiled_summary(_eigh_pert, a)
    assert any("syevd" in t for t in base["custom_calls"])
    contract = C.make_contract("eigh", {"p": base}, tolerances=_LOOSE)
    v, _ = C.check_contract(contract, {"p": pert})
    assert _rules(v) == {"custom-call-bracket"}
    assert any("brackets" in x.message for x in v)


def test_compiled_dtype_perturbation_trips():
    from jax import enable_x64

    def f32_step(x):
        return (x * 2.0).sum()

    def f64_step(x):
        import jax.numpy as jnp
        return (x.astype(jnp.float64) * 2.0).sum()

    x = np.ones((8, 8), np.float32)
    base = analysis.compiled_summary(f32_step, x)
    assert base["dtype"]["f64_ops"] == 0
    with enable_x64(True):
        pert = analysis.compiled_summary(f64_step, x)
    assert pert["dtype"]["f64_ops"] > 0
    assert pert["dtype"]["upcasts"].get("f32->f64", 0) >= 1
    contract = C.make_contract("dt", {"p": base}, tolerances=_LOOSE)
    v, _ = C.check_contract(contract, {"p": pert})
    assert "dtype-policy" in _rules(v)
    assert not _rules(v) & {"collectives", "custom-call-bracket",
                            "host-transfer"}


def test_compiled_host_transfer_trips():
    import jax

    def host_step(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    obs = analysis.compiled_summary(host_step, np.ones(4, np.float32))
    assert obs["host_transfers"]["count"] >= 1
    assert any("callback" in op for op in obs["host_transfers"]["ops"])
    # zero out ONLY the stored transfer count: exactly that rule trips
    contract = C.make_contract("cb", {"p": copy.deepcopy(obs)})
    contract["programs"]["p"]["host_transfers"] = {"count": 0,
                                                   "ops": {}}
    v, _ = C.check_contract(contract, {"p": obs})
    assert _rules(v) == {"host-transfer"}


def test_two_lowering_stability():
    a = _sym_input()
    s1 = analysis.compiled_summary(_eigh_pert, a)
    s2 = analysis.compiled_summary(_eigh_pert, a)
    assert json.dumps(s1, sort_keys=True) == json.dumps(s2,
                                                        sort_keys=True)
    v, n = C.check_contract(C.make_contract("eigh", {"p": s1}),
                            {"p": s2})
    assert v == [] and n == []


# ---------------------------------------------------- runtime audit

class _FakeCompiled:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_maybe_audit_knob(monkeypatch):
    monkeypatch.delenv("MXTPU_HLO_AUDIT", raising=False)
    monkeypatch.delenv("MXNET_HLO_AUDIT", raising=False)
    dirty = _FakeCompiled(SYNTH)   # f64 param + bracketed custom call
    assert analysis.maybe_audit(dirty, label="t", mem={}) is None
    monkeypatch.setenv("MXTPU_HLO_AUDIT", "1")
    with pytest.warns(RuntimeWarning, match="HLO audit"):
        summ = analysis.maybe_audit(dirty, label="t", mem={})
    assert summ["custom_calls"]["my_kernel"]["bracketed"] == 3
    monkeypatch.setenv("MXTPU_HLO_AUDIT", "2")
    with pytest.raises(MXNetError, match="MXTPU_HLO_AUDIT=2"):
        analysis.maybe_audit(dirty, label="t", mem={})
    # a clean program passes silently even in raise mode
    assert analysis.maybe_audit(_FakeCompiled(CLEAN), label="t",
                                mem={}) is not None


def test_runner_program_summary_wiring(tmp_path):
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.gluon import nn
    from mxtpu.serving import ModelRunner
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize(init="xavier")
    net(nd.array(np.zeros((1, 5), np.float32)))
    sym_file, param_file = net.export(str(tmp_path / "m"))
    r = ModelRunner.from_export(sym_file, param_file,
                                input_specs={"data": (5,)},
                                max_batch_size=4)
    s = r.program_summary(r.bucket_for(1))
    assert s["budgets"]["instruction_count"] > 0
    assert s["host_transfers"]["count"] == 0
    text, _mem = r.program_artifact(r.bucket_for(1))
    assert isinstance(text, str) and "ENTRY" in text


# ------------------------------------------------------------- CLI

def _hlocheck(args):
    return subprocess.run(
        [sys.executable, "-m", "tools.hlocheck", *args],
        capture_output=True, text=True, cwd=_ROOT, timeout=240)


def test_cli_update_check_fixed_point(tmp_path):
    r = _hlocheck(["--update", "selftest",
                   "--contracts-dir", str(tmp_path)])
    assert r.returncode == 0, r.stdout + r.stderr
    path = tmp_path / "selftest.json"
    assert path.exists()
    r = _hlocheck(["--check", "selftest",
                   "--contracts-dir", str(tmp_path)])
    assert r.returncode == 0, r.stdout + r.stderr
    # corrupt exactly one pinned fact: the right family must be named
    data = json.loads(path.read_text())
    prog = next(iter(data["programs"]))
    cc = data["programs"][prog]["custom_calls"]
    cc[next(iter(cc))]["bracketed"] = 0
    path.write_text(json.dumps(data))
    r = _hlocheck(["--check", "selftest",
                   "--contracts-dir", str(tmp_path)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "custom-call-bracket" in r.stdout


def test_cli_unknown_target_is_usage_error(tmp_path):
    r = _hlocheck(["--check", "no-such-target",
                   "--contracts-dir", str(tmp_path)])
    assert r.returncode == 2


@pytest.mark.slow
def test_committed_contracts_check_clean():
    """The committed contracts/ lockfiles hold for this tree — the
    same gate ci_static and `bench.py --contracts` run."""
    r = _hlocheck(["--check"])
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
