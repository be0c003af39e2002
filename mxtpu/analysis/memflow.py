"""memflow — the ONE memory-footprint analyzer (ISSUE 20).

Exactly as :mod:`.dtypeflow` consolidated dtype facts behind one
analyzer, this module owns every byte-accounting fact in the tree:

* :func:`mem_stats` — XLA ``memory_analysis()`` as a plain dict with
  the repo-wide ``hbm_peak`` = temp + argument convention (moved here
  from ``mxtpu.parallel._mem_stats``, which now delegates);
* :func:`opt_state_leaf_bytes` — per-device optimizer-state bytes
  (ZeRO-sharded leaves count only the local shard);
* :func:`decompose` — peak HBM per device split into params /
  optimizer state / activations+temps / collectives scratch / KV
  table / donated-aliased / other-input bytes;
* the five hazard rules (mxprec finding shape — ``rule``/``op``/
  ``site``/``detail``): **donation-missed**, **zero-replication**
  (:func:`mxtpu.parallel.plan_zero_buckets` is the oracle),
  **kv-overcommit**, **padding-waste**, **budget-exceeded** (against
  the declarative per-device-class budgets in
  ``contracts/mem/budgets.json``);
* committed-ledger build/compare for ``contracts/mem/<target>.json``
  (``python -m tools.mxmem`` is the CLI; serialization matches the
  repo lockfile idiom, so ``--update`` -> ``--check`` is a
  byte-identical fixed point) and the README HBM table.

The runtime knob ``MXTPU_MEM_AUDIT`` (1 warn / 2 raise) applies
:func:`mem_audit_findings` — the budget check — to every program
``TrainStep`` / ``ModelRunner`` / ``GenerateRunner`` compiles, via
``analysis.maybe_audit`` beside the HLO/PREC audits.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from .hlo import HloProgram, parse_hlo
from .summary import COLLECTIVE_OPS

REPO_ROOT = Path(__file__).resolve().parents[2]

MEM_SUBDIR = "mem"
BUDGETS_NAME = "budgets"

MEM_BEGIN = "<!-- mxmem:hbm:begin -->"
MEM_END = "<!-- mxmem:hbm:end -->"

# padding-waste thresholds: a pad is a finding only when it wastes
# both a meaningful FRACTION of the buffer and a meaningful number of
# absolute bytes (tiny fixtures pad a few rows by design)
PAD_WASTE_FRAC = 0.25
PAD_WASTE_MIN_BYTES = 1 << 16

# optimizer kind -> f32 state leaves per parameter (adam: m+v; the
# momentum family: one velocity; plain sgd: none).  The oracle the
# zero-replication rule scales plan_zero_buckets geometry by.
STATE_LEAVES = {"adam": 2, "adamw": 2, "lamb": 2, "rmsprop": 2,
                "ftrl": 2, "adagrad": 1, "sgd": 1, "nag": 1}

_MIB = 1024.0 * 1024.0


# ----------------------------------------------------------------------
# mem stats (the hbm_peak convention — canonical here)
# ----------------------------------------------------------------------
def mem_stats(compiled) -> Optional[Dict[str, int]]:
    """``memory_analysis()`` of a compiled program as a plain dict
    (None when the backend doesn't report).  ``hbm_peak`` is
    temp + argument bytes — the resident high-water the program needs
    beyond its outputs.  Every committed peak-bytes budget in
    ``contracts/`` pins this exact convention."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out: Dict[str, int] = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    out["hbm_peak"] = (out.get("temp_size_in_bytes", 0) +
                       out.get("argument_size_in_bytes", 0))
    return out


def opt_state_leaf_bytes(opt_state) -> int:
    """Optimizer-state bytes resident PER DEVICE: replicated leaves
    count in full, sharded leaves only the local shard (the dp×
    saving ZeRO-1 exists for).  ``TrainStep.opt_state_bytes``
    delegates here."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            total += shards[0].data.nbytes
        else:
            total += int(getattr(leaf, "nbytes", 0))
    return total


def collective_scratch_bytes(program: Union[str, HloProgram]) -> int:
    """Bytes materialized by collective results in one program —
    the exchange buffers the compiled step keeps live during
    all-reduce / reduce-scatter / all-gather (async ``-start`` forms
    count once; their ``-done`` halves are skipped)."""
    if isinstance(program, str):
        program = parse_hlo(program)
    total = 0
    for comp in program.computations.values():
        for instr in comp.instructions:
            op = instr.opcode
            if op.endswith("-done") and op[:-5] in COLLECTIVE_OPS:
                continue
            kind = op[:-6] if op.endswith("-start") else op
            if kind in COLLECTIVE_OPS:
                total += instr.result_bytes()
    return total


# ----------------------------------------------------------------------
# decomposition
# ----------------------------------------------------------------------
def decompose(mem: Optional[Dict[str, int]], *,
              params_bytes: int = 0, opt_state_bytes: int = 0,
              kv_table_bytes: int = 0,
              collective_scratch: int = 0) -> Dict[str, int]:
    """Split one program's per-device footprint into the ledger
    categories.  ``params`` / ``opt_state`` / ``kv_table`` are
    semantic byte counts the caller attributes (they ride inside the
    argument buffers); ``inputs_other`` is the argument remainder
    (batches, frozen params, rng keys, hyperparameters);
    ``collectives_scratch`` is a report-only attribution WITHIN the
    temp bytes, not an additional term.  ``peak_hbm`` keeps the
    repo-wide temp + argument convention byte-for-byte."""
    mem = mem or {}
    arg = int(mem.get("argument_size_in_bytes", 0))
    temp = int(mem.get("temp_size_in_bytes", 0))
    attributed = params_bytes + opt_state_bytes + kv_table_bytes
    return {
        "params": int(params_bytes),
        "opt_state": int(opt_state_bytes),
        "kv_table": int(kv_table_bytes),
        "activations_temps": temp,
        "collectives_scratch": int(collective_scratch),
        "donated_aliased": int(mem.get("alias_size_in_bytes", 0)),
        "inputs_other": max(0, arg - attributed),
        "output": int(mem.get("output_size_in_bytes", 0)),
        "peak_hbm": temp + arg,
    }


# ----------------------------------------------------------------------
# hazard rules (mxprec finding shape: rule / op / site / detail)
# ----------------------------------------------------------------------
def _finding(rule: str, op: str, site: str, detail: str) -> Dict:
    return {"rule": rule, "op": op, "site": site, "detail": detail}


def donation_hazards(record: Dict) -> List[Dict]:
    """**donation-missed** — a donatable argument buffer (declared by
    the runner's geometry: the train-vals/opt-state pair, the serving
    input tuple, the decode KV slot table) is not in the program's
    donated set, so caller copy + callee output both stay resident
    and the footprint doubles for that buffer."""
    out: List[Dict] = []
    for prog in sorted(record.get("programs", {})):
        entry = record["programs"][prog]
        don = entry.get("donation")
        if not don:
            continue
        declared = {int(i) for i in don.get("declared", ())}
        for idx in sorted(don.get("donatable", {}),
                          key=lambda s: int(s)):
            if int(idx) in declared:
                continue
            info = don["donatable"][idx]
            out.append(_finding(
                "donation-missed", "parameter",
                f"{prog}:arg{idx}",
                f"{info.get('label', 'buffer')} "
                f"({int(info.get('bytes', 0))} B) is donatable but "
                f"not donated — pass donate_argnums so XLA aliases "
                f"it to the output instead of keeping both live"))
    return out


def zero_hazards(record: Dict) -> List[Dict]:
    """**zero-replication** — a ZeRO target whose measured per-device
    optimizer-state bytes exceed the ``plan_zero_buckets`` shard
    geometry: the states are (partially) replicated where the plan
    says they must be sharded.  Fires only on targets DECLARED to
    shard (``expected``): the replicated baselines carry the oracle
    for comparison without tripping it."""
    z = record.get("zero")
    if not z or not z.get("expected", True):
        return []
    actual = int(z.get("opt_state_bytes", 0))
    planned = int(z.get("planned_shard_bytes", 0))
    if actual <= planned:
        return []
    return [_finding(
        "zero-replication", "opt-state",
        f"{record.get('target', '?')}:opt_state",
        f"optimizer state holds {actual} B/device but the "
        f"plan_zero_buckets dp={z.get('dp')} shard geometry allows "
        f"{planned} B — states are replicated, not sharded "
        f"({z.get('states_per_param')} leaves/param)")]


def kv_hazards(record: Dict) -> List[Dict]:
    """**kv-overcommit** — the decode KV slot table holds more bytes
    than the declared ``kv_cache_spec`` geometry plus the one scratch
    slot prefill padding scatters into."""
    kv = record.get("kv")
    if not kv:
        return []
    actual = int(kv.get("table_bytes", 0))
    expected = int(kv.get("expected_bytes", 0))
    if actual <= expected:
        return []
    spec = tuple(kv.get("spec", ()))
    return [_finding(
        "kv-overcommit", "kv-table",
        f"{record.get('target', '?')}:kv_table",
        f"KV slot table holds {actual} B but kv_cache_spec "
        f"{spec} + 1 scratch slot allows {expected} B — lanes grew "
        f"past the declared cache geometry")]


def padding_hazards(record: Dict, *, frac: float = PAD_WASTE_FRAC,
                    min_bytes: int = PAD_WASTE_MIN_BYTES
                    ) -> List[Dict]:
    """**padding-waste** — a bucket pads more than ``frac`` of its
    payload away (and more than ``min_bytes`` absolute): the ladder /
    shard geometry is burning HBM on zeros."""
    out: List[Dict] = []
    for row in record.get("padding", ()):
        used = int(row.get("used_bytes", 0))
        padded = int(row.get("padded_bytes", 0))
        waste = padded - used
        if used <= 0 or waste <= 0:
            continue
        if waste / used > frac and waste >= min_bytes:
            out.append(_finding(
                "padding-waste", "pad", str(row.get("site", "?")),
                f"{waste} B of padding on {used} B of payload "
                f"({100.0 * waste / used:.1f}% > "
                f"{100.0 * frac:.0f}% threshold)"))
    return out


def budget_hazards(record: Dict,
                   budgets: Optional[Dict]) -> List[Dict]:
    """**budget-exceeded** — a program's peak HBM per device exceeds
    the target's declared device-class budget
    (``contracts/mem/budgets.json``)."""
    if not budgets:
        return []
    cls, limit = resolve_budget(record.get("target", ""), budgets)
    if limit is None:
        return []
    out: List[Dict] = []
    for prog in sorted(record.get("programs", {})):
        entry = record["programs"][prog]
        mem = entry.get("mem") or {}
        peak = int(mem.get(
            "hbm_peak",
            int(mem.get("temp_size_in_bytes", 0))
            + int(mem.get("argument_size_in_bytes", 0))))
        if peak > limit:
            out.append(_finding(
                "budget-exceeded", "program", f"{prog}",
                f"peak {peak} B exceeds the {cls} device-class "
                f"budget of {limit} B — this target no longer fits "
                f"its declared device"))
    return out


def hazard_findings_mem(record: Dict,
                        budgets: Optional[Dict] = None) -> List[Dict]:
    """All memory hazards of one target record, sorted for
    byte-deterministic ledgers (same ordering contract as
    ``dtypeflow.hazard_findings``)."""
    out = (donation_hazards(record) + zero_hazards(record)
           + kv_hazards(record) + padding_hazards(record)
           + budget_hazards(record, budgets))
    return sorted(out, key=lambda h: (h["rule"], h["op"], h["site"],
                                      h["detail"]))


# ----------------------------------------------------------------------
# budgets (declarative, hand-edited — --update never rewrites an
# existing file, only bootstraps a missing one)
# ----------------------------------------------------------------------
DEFAULT_BUDGETS = {
    "comment": "Declarative per-device-class HBM budgets for "
               "`python -m tools.mxmem` (hand-edited; --update only "
               "bootstraps this file when missing).  The mem ledgers "
               "check every target's peak HBM/device against its "
               "class — the gate ROADMAP item 2's tensor-parallel "
               "dp x tp meshes will extend.",
    "classes": {
        "hbm16": {"bytes": 16 * 1024 ** 3,
                  "doc": "16 GiB HBM per device (v2/v3-era chip)"},
        "hbm32": {"bytes": 32 * 1024 ** 3,
                  "doc": "32 GiB HBM per device"},
        "host-ci": {"bytes": 2 * 1024 ** 3,
                    "doc": "2 GiB — the CPU-backend CI fixture "
                           "class every tiny contract target must "
                           "fit with room to spare"},
    },
    "default_class": "hbm16",
    "targets": {},
}


def mem_dir(directory: Path) -> Path:
    return Path(directory) / MEM_SUBDIR


def ledger_path(name: str, directory: Path) -> Path:
    return mem_dir(directory) / f"{name}.json"


def budgets_path(directory: Path) -> Path:
    return mem_dir(directory) / f"{BUDGETS_NAME}.json"


def load_budgets(directory: Path) -> Optional[Dict]:
    p = budgets_path(directory)
    if not p.exists():
        return None
    return json.loads(p.read_text())


def resolve_budget(target: str, budgets: Optional[Dict]
                   ) -> Tuple[Optional[str], Optional[int]]:
    """(device_class, byte limit) for one target; (None, None) when
    no budgets are declared."""
    if not budgets:
        return None, None
    cls = budgets.get("targets", {}).get(
        target, budgets.get("default_class"))
    info = budgets.get("classes", {}).get(cls)
    if info is None:
        return cls, None
    return cls, int(info.get("bytes", 0))


def _dump(obj) -> str:
    return json.dumps(obj, indent=1, sort_keys=True) + "\n"


def save_ledger(ledger: Dict, directory: Path) -> Path:
    path = ledger_path(ledger["target"], directory)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(_dump(ledger))
    return path


def load_ledger(name: str, directory: Path) -> Dict:
    return json.loads(ledger_path(name, directory).read_text())


def save_budgets(budgets: Dict, directory: Path) -> Path:
    path = budgets_path(directory)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(_dump(budgets))
    return path


def committed_ledgers(directory: Path) -> Dict[str, Dict]:
    d = mem_dir(directory)
    if not d.is_dir():
        return {}
    return {p.stem: json.loads(p.read_text())
            for p in sorted(d.glob("*.json"))
            if p.stem != BUDGETS_NAME}


def compare_ledgers(committed: Dict, fresh: Dict) -> List[str]:
    """Drift between a committed mem ledger and a fresh build — empty
    when byte-identical under the lockfile serialization."""
    from tools.mxprec.core import _diff
    if _dump(committed) == _dump(fresh):
        return []
    out: List[str] = []
    _diff(committed, fresh, "", out)
    return out or ["ledger drifted (serialization-level difference)"]


# ----------------------------------------------------------------------
# target records -> ledgers
# ----------------------------------------------------------------------
def build_ledger(record: Dict,
                 budgets: Optional[Dict] = None) -> Dict:
    """One target record (``tools/hlocheck/targets.py`` MEM_TARGETS
    builds these) into the committed ``contracts/mem/<target>.json``
    shape: per-program decomposition, the semantic sections (zero /
    kv / padding / donation), the resolved device-class budget, and
    the hazard findings — every value an int or a string, so two
    builds of the same tree are byte-identical."""
    target = record["target"]
    cls, limit = resolve_budget(target, budgets)
    programs: Dict[str, Dict] = {}
    peak = 0
    for prog in sorted(record.get("programs", {})):
        entry = record["programs"][prog]
        mem = entry.get("mem") or {}
        dec = decompose(
            mem,
            params_bytes=entry.get("params_bytes",
                                   record.get("params_bytes", 0)),
            opt_state_bytes=entry.get(
                "opt_state_bytes", record.get("opt_state_bytes") or 0),
            kv_table_bytes=entry.get("kv_table_bytes", 0),
            collective_scratch=entry.get("collective_scratch", 0))
        peak = max(peak, dec["peak_hbm"])
        row: Dict[str, Any] = {"decomposition": dec}
        if entry.get("donation"):
            row["donation"] = {
                "declared": sorted(int(i) for i in
                                   entry["donation"]["declared"]),
                "donatable": {
                    str(k): {"label": v.get("label", "buffer"),
                             "bytes": int(v.get("bytes", 0))}
                    for k, v in sorted(
                        entry["donation"]["donatable"].items(),
                        key=lambda kv: int(kv[0]))}}
        programs[prog] = row
    ledger: Dict[str, Any] = {
        "comment": "mxmem memory ledger -- regenerate with "
                   f"`python -m tools.mxmem --update {target}`",
        "target": target,
        "programs": programs,
        "peak_hbm": peak,
        "hazards": hazard_findings_mem(record, budgets),
    }
    if cls is not None:
        ledger["device_class"] = cls
        if limit:
            ledger["budget_bytes"] = limit
            ledger["headroom_frac"] = round(
                (limit - peak) / limit, 6)
    for key in ("zero", "kv"):
        if record.get(key):
            ledger[key] = {k: (list(v) if isinstance(v, tuple) else v)
                           for k, v in sorted(record[key].items())}
    if record.get("padding"):
        ledger["padding"] = [
            {"site": str(r["site"]),
             "used_bytes": int(r["used_bytes"]),
             "padded_bytes": int(r["padded_bytes"])}
            for r in record["padding"]]
    return ledger


# ----------------------------------------------------------------------
# record builders — the sanctioned views TrainStep / ModelRunner /
# GenerateRunner ``memory_summary()`` delegate to
# ----------------------------------------------------------------------
def _sig_bytes(shape: Sequence[int], dtype: str) -> int:
    import numpy as np
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def train_step_record(step, x, y, target: str = "train_step",
                      zero_expected: Optional[bool] = None) -> Dict:
    """Memory record of one ``TrainStep`` batch signature: ONE
    compile, then decomposition inputs (trainable-param bytes from
    ``param_sigs``, per-device optimizer-state bytes, collective
    scratch from the compiled text), the donation declaration
    (train-vals + opt-state are the donatable pair, ``donate=(0, 2)``
    when on), and — under ZeRO — the ``plan_zero_buckets`` oracle and
    its padding table."""
    compiled = step._compiled_for(x, y)
    mem = mem_stats(compiled) or {}
    scratch = collective_scratch_bytes(compiled.as_text())
    sigs = step.param_sigs(x, y)
    params_bytes = sum(_sig_bytes(shape, dt) for _, shape, dt in sigs)
    opt_bytes = step.opt_state_bytes()
    donation = {
        "declared": [0, 2] if step.donate else [],
        "donatable": {
            "0": {"label": "train_vals", "bytes": params_bytes},
            "2": {"label": "opt_state", "bytes": opt_bytes}}}
    record: Dict[str, Any] = {
        "target": target,
        "programs": {"train_step": {
            "mem": mem, "collective_scratch": scratch,
            "donation": donation}},
        "params_bytes": params_bytes,
        "opt_state_bytes": opt_bytes,
    }
    zero_dp = _zero_dp(step)
    if zero_dp:
        if zero_expected is None:
            # without a target-level declaration, a step claiming
            # ZeRO (``zero``) must deliver its plan; a deliberately
            # replicated step carries the oracle informationally
            zero_expected = bool(step.zero)
        record.update(zero_oracle(step, zero_dp,
                                  expected=zero_expected))
    return record


def _zero_dp(step) -> int:
    """dp width of a ZeRO-evaluated step (0 = not a zero target)."""
    if step.mesh is None or step.dp_axis not in step.mesh.shape:
        return 0
    dp = int(step.mesh.shape[step.dp_axis])
    return dp if dp > 1 else 0


def planned_shard_bytes(sigs: Sequence[Tuple], dp: int,
                        states_per_param: int = 2) -> int:
    """Planned per-device optimizer-state bytes for ``(shape,
    dtype)`` signatures sharded dp-wide: the ``plan_zero_buckets``
    geometry × the optimizer's f32 state-leaf count — THE
    zero-replication oracle (bench.py's dp8 projection uses it
    too)."""
    from mxtpu.parallel import plan_zero_buckets
    buckets = plan_zero_buckets(list(sigs), dp)
    return int(sum(states_per_param * b["padded_bytes"] // dp
                   for b in buckets))


def zero_oracle(step, dp: int,
                states_per_param: Optional[int] = None,
                expected: bool = True) -> Dict:
    """The zero-replication oracle for one step: planned per-device
    shard bytes from ``plan_zero_buckets`` geometry × the optimizer's
    state-leaf count, plus the per-bucket padding table.  Optimizer
    states are f32 regardless of the param storage dtype (the fp32-
    master rule mxprec enforces), so the plan is computed on f32
    signatures — and under AMP the sharded master copy counts as one
    more state leaf.  A step that SHOULD shard (``zero=0`` forced
    under a dp>1 mesh on a declared-ZeRO target) fails the rule
    exactly because its measured bytes exceed this plan."""
    from mxtpu.parallel import plan_zero_buckets
    kind = type(step.optimizer).__name__.lower()
    if states_per_param is None:
        states_per_param = STATE_LEAVES.get(kind, 2)
        if step.amp:
            states_per_param += 1  # the sharded fp32 master
    sigs = [(shape, "float32") for _, shape, _ in step.param_sigs()]
    buckets = plan_zero_buckets(sigs, dp)
    planned = planned_shard_bytes(sigs, dp, states_per_param)
    return {
        "zero": {"dp": dp, "optimizer": kind,
                 "states_per_param": int(states_per_param),
                 "planned_shard_bytes": int(planned),
                 "opt_state_bytes": int(step.opt_state_bytes()),
                 "sharded": bool(step.zero),
                 "expected": bool(expected)},
        "padding": [
            {"site": f"zero_bucket{j}"
                     f"[{b['stacked_shape']}:{b['dtype']}]",
             "used_bytes": b["param_bytes"],
             "padded_bytes": b["padded_bytes"]}
            for j, b in enumerate(buckets)],
    }


def runner_record(runner, target: str = "serving",
                  buckets: Optional[Sequence] = None) -> Dict:
    """Memory record of a ``ModelRunner`` bucket ladder: per-bucket
    decomposition (weights ride as the param-vals operand; the padded
    input tuple is the donatable arg 0)."""
    weight_bytes = runner.weight_bytes()
    programs: Dict[str, Dict] = {}
    for bucket in (buckets if buckets is not None
                   else runner.buckets()):
        batch, seq = bucket
        text, mem = runner.program_artifact(bucket)
        mem = mem or {}
        inputs = max(0, int(mem.get("argument_size_in_bytes", 0))
                     - weight_bytes)
        programs[f"bucket_b{batch}_s{seq}"] = {
            "mem": mem,
            "collective_scratch": collective_scratch_bytes(text),
            "donation": {
                "declared": [0] if runner._donate else [],
                "donatable": {"0": {"label": "input_batch",
                                    "bytes": inputs}}}}
    return {"target": target, "programs": programs,
            "params_bytes": weight_bytes}


def generate_record(runner, target: str = "generate",
                    buckets: Optional[Sequence] = None) -> Dict:
    """Memory record of a ``GenerateRunner``: per-rung prefill + the
    decode step.  The KV slot table is both the dominant argument
    buffer (attributed per program) and the donatable operand (last
    data arg of every entry); the kv section pins table bytes ==
    declared ``kv_cache_spec`` geometry + 1 scratch slot — the
    equality the kv-overcommit rule guards."""
    import jax.numpy as jnp
    import numpy as np
    weight_bytes = runner.weight_bytes()
    # every state table the runner declares (one float32 KV table for
    # a 6-tuple kv_spec; a state spec names several, each in its own
    # dtype): allocated bytes against the declared lanes + 1 scratch
    allocated = runner.state_bytes()
    table_bytes = sum(allocated.values())
    tables = []
    for t in runner.state_spec:
        shape = t.shape[:t.lane_axis] + (t.shape[t.lane_axis] + 1,) \
            + t.shape[t.lane_axis + 1:]
        tables.append({"name": t.name, "spec": list(t.shape),
                       "lane_axis": t.lane_axis, "dtype": t.dtype,
                       "table_bytes": allocated[t.name],
                       "expected_bytes": int(np.prod(
                           shape, dtype=np.int64))
                       * jnp.dtype(t.dtype).itemsize})
    spec = tuple(runner.kv_spec)
    itemsize = next(jnp.dtype(t.dtype).itemsize
                    for t in runner.state_spec if t.name == "kv")
    expected = sum(t["expected_bytes"] for t in tables)
    programs: Dict[str, Dict] = {}
    for bucket in (buckets if buckets is not None
                   else runner.buckets()):
        kind, shp = bucket
        name = "decode_step" if kind == "decode" \
            else f"prefill_b{shp[0]}_s{shp[1]}"
        text, mem = runner.program_artifact(bucket)
        mem = mem or {}
        # the state is the LAST data operand of every entry, as the
        # runner donates it: behind the rows, which hold a ``length``
        # where the program takes one
        kv_argnum = len(runner._structs(bucket)) - 1
        programs[name] = {
            "mem": mem,
            "collective_scratch": collective_scratch_bytes(text),
            "kv_table_bytes": table_bytes,
            "donation": {
                "declared": [kv_argnum] if runner._donate else [],
                "donatable": {str(kv_argnum): {
                    "label": "kv_table", "bytes": table_bytes}}}}
    return {
        "target": target, "programs": programs,
        "params_bytes": weight_bytes,
        "kv": {"spec": list(spec), "itemsize": itemsize,
               "slots": int(runner._kv_shape[2]),
               "table_bytes": table_bytes,
               "expected_bytes": expected, "tables": tables},
    }


def kv_expected_bytes(kv_spec: Sequence[int],
                      itemsize: int = 4) -> int:
    """Bytes the declared ``kv_cache_spec`` geometry allows the slot
    table: the spec's lane count plus ONE scratch slot."""
    spec = tuple(int(d) for d in kv_spec)
    shape = spec[:2] + (spec[2] + 1,) + spec[3:]
    n = 1
    for d in shape:
        n *= d
    return n * int(itemsize)


def summary_view(record: Dict,
                 budgets: Optional[Dict] = None) -> Dict:
    """The ``memory_summary()`` dict the runners expose: per-program
    decomposition + hazards — the sanctioned alternative to raw
    ``compiled.memory_analysis()`` grepping (mxlint's ``mem-hygiene``
    rule)."""
    led = build_ledger(record, budgets)
    out = {"target": led["target"],
           "programs": {p: v["decomposition"]
                        for p, v in led["programs"].items()},
           "peak_hbm": led["peak_hbm"],
           "hazards": led["hazards"]}
    for key in ("zero", "kv", "device_class", "budget_bytes"):
        if key in led:
            out[key] = led[key]
    return out


# ----------------------------------------------------------------------
# runtime audit (MXTPU_MEM_AUDIT via analysis.maybe_audit)
# ----------------------------------------------------------------------
def mem_audit_findings(mem: Optional[Dict[str, int]],
                       label: str = "") -> List[str]:
    """The contract-free memory audit for freshly compiled programs:
    peak HBM per device against the default device-class budget
    (``MXTPU_MEM_BUDGET`` overrides the byte limit for tests /
    constrained deploys; 0 = use ``contracts/mem/budgets.json``'s
    default class).  Ledger checks live in ``python -m
    tools.mxmem``."""
    if not mem:
        return []
    from mxtpu import knobs
    limit = int(knobs.get("MXTPU_MEM_BUDGET"))
    cls = "MXTPU_MEM_BUDGET"
    if not limit:
        budgets = load_budgets(REPO_ROOT / "contracts")
        if not budgets:
            return []
        cls, limit = resolve_budget("", budgets)
        if not limit:
            return []
    peak = int(mem.get("hbm_peak", 0))
    where = f" in {label}" if label else ""
    if peak > limit:
        return [f"peak HBM {peak} B{where} exceeds the {cls} budget "
                f"of {limit} B"]
    return []


# ----------------------------------------------------------------------
# README table (committed ledgers -> markdown between markers)
# ----------------------------------------------------------------------
def _mib(n: int) -> str:
    return f"{n / _MIB:.2f}"


def _ledger_row(name: str, led: Dict) -> str:
    params = opt = act = kv = 0
    for prog in led.get("programs", {}).values():
        d = prog.get("decomposition", {})
        params = max(params, d.get("params", 0))
        opt = max(opt, d.get("opt_state", 0))
        act = max(act, d.get("activations_temps", 0))
        kv = max(kv, d.get("kv_table", 0))
    peak = led.get("peak_hbm", 0)
    cls = led.get("device_class", "—")
    hazards = len(led.get("hazards", []))
    return (f"| {name} | {len(led.get('programs', {}))} "
            f"| {_mib(params)} | {_mib(opt)} | {_mib(act)} "
            f"| {_mib(kv)} | {_mib(peak)} | {cls} | {hazards} |")


def render_mem_table(ledgers: Dict[str, Dict]) -> str:
    lines = [MEM_BEGIN,
             "| target | programs | params | opt state | activ+temps"
             " | KV table | peak HBM | class | hazards |",
             "|---|---|---|---|---|---|---|---|---|"]
    for name in sorted(ledgers):
        lines.append(_ledger_row(name, ledgers[name]))
    lines.append("")
    lines.append(f"*MiB per device (max over each target's programs);"
                 f" committed in `contracts/mem/`, regenerate with "
                 f"`python -m tools.mxmem --fix-readme`.*")
    lines.append(MEM_END)
    return "\n".join(lines)


def readme_drift(root: Path, ledgers: Dict[str, Dict]) -> List[str]:
    readme = root / "README.md"
    if not readme.exists():
        return ["README.md missing"]
    text = readme.read_text()
    if MEM_BEGIN not in text or MEM_END not in text:
        return ["README.md lacks the mxmem:hbm markers — run "
                "`python -m tools.mxmem --fix-readme`"]
    current = text.split(MEM_BEGIN, 1)[1].split(MEM_END, 1)[0]
    want = render_mem_table(ledgers) \
        .split(MEM_BEGIN, 1)[1].split(MEM_END, 1)[0]
    if current.strip() != want.strip():
        return ["README memory table is stale — run "
                "`python -m tools.mxmem --fix-readme`"]
    return []


def fix_readme(root: Path, ledgers: Dict[str, Dict]) -> bool:
    readme = root / "README.md"
    text = readme.read_text()
    if MEM_BEGIN not in text or MEM_END not in text:
        raise SystemExit(
            f"README.md lacks the markers {MEM_BEGIN!r} … "
            f"{MEM_END!r}; add them where the table should live")
    head = text.split(MEM_BEGIN, 1)[0]
    tail = text.split(MEM_END, 1)[1]
    new = head + render_mem_table(ledgers) + tail
    if new != text:
        readme.write_text(new)
        return True
    return False
