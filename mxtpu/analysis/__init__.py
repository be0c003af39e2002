"""mxtpu.analysis — static analysis over lowered/compiled XLA
programs (ISSUE 6).

Three layers:

* :mod:`.hlo` — the structural HLO-text parser (the ONE in the tree);
* :mod:`.summary` — deterministic program summaries across the five
  rule families (collectives, custom-call brackets, dtype policy,
  budgets, host transfers) plus the report-only bracket evidence
  table;
* :mod:`.contracts` — committed lockfiles under ``contracts/`` and
  the check that compares a fresh summary against them
  (``python -m tools.hlocheck`` is the CLI).

Tests inspect compiled programs through :func:`compiled_summary` /
:func:`compiled_evidence` rather than grepping ``hlo_text()``
directly — mxlint's ``hlo-raw-assert`` rule enforces this.

The runtime audit (:func:`maybe_audit`, knob ``MXTPU_HLO_AUDIT``)
applies the contract-free hygiene subset — no host transfers, no f64
creep, no bracketed custom calls — to every program ``TrainStep`` and
serving's ``ModelRunner`` compile: ``1`` warns, ``2`` raises, unset
costs nothing.

:mod:`.memflow` (ISSUE 20) is the memory sibling: the ONE ``hbm_peak``
analyzer, per-device HBM decomposition, the five memory hazard rules,
and the committed ledgers under ``contracts/mem/`` (``python -m
tools.mxmem`` is the CLI; knob ``MXTPU_MEM_AUDIT``).
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional, Tuple

from .hlo import HloProgram, inline_source_sites, parse_hlo
from .summary import (BRACKET_OPS, COLLECTIVE_OPS, HOST_TRANSFER_OPS,
                      audit_findings, bracket_evidence,
                      format_evidence_table, summarize)
from . import dtypeflow
from .dtypeflow import (cast_flows, dtype_summary, format_hazard,
                        hazard_findings, master_weight_findings,
                        program_ledger)
from . import memflow
from .memflow import (collective_scratch_bytes, decompose,
                      hazard_findings_mem, mem_audit_findings,
                      mem_stats)
from .contracts import (CONTRACTS_DIR, DEFAULT_TOLERANCES, Violation,
                        check_contract, contract_path, load_contract,
                        make_contract, save_contract)

__all__ = [
    "HloProgram", "parse_hlo", "summarize", "bracket_evidence",
    "format_evidence_table", "audit_findings", "Violation",
    "check_contract", "make_contract", "save_contract",
    "load_contract", "contract_path", "CONTRACTS_DIR",
    "DEFAULT_TOLERANCES", "COLLECTIVE_OPS", "BRACKET_OPS",
    "HOST_TRANSFER_OPS", "mem_stats", "compiled_artifact",
    "executable_artifact",
    "compiled_summary", "compiled_evidence", "maybe_audit",
    "audit_mode", "dtypeflow", "dtype_summary", "cast_flows",
    "hazard_findings", "format_hazard", "master_weight_findings",
    "program_ledger", "lowered_text", "lowered_summary",
    "prec_audit_mode", "audit_stamp", "needs_reaudit",
    "memflow", "decompose", "collective_scratch_bytes",
    "hazard_findings_mem", "mem_audit_findings", "mem_audit_mode",
]


def compiled_artifact(fn, *args, **jit_kwargs
                      ) -> Tuple[str, Optional[Dict[str, int]]]:
    """``(hlo_text, mem_stats)`` of ``fn`` lowered and compiled on
    the current backend — the sanctioned route for tests that need a
    compiled program (keeps raw ``.lower()``/``.hlo_text()`` calls
    out of ``tests/``)."""
    import jax
    return executable_artifact(
        jax.jit(fn, **jit_kwargs).lower(*args).compile())


def executable_artifact(compiled) -> Tuple[str, Optional[Dict[str, int]]]:
    """``(hlo_text, mem_stats)`` of a program already compiled (a
    runner's entry, built by the runner's own route)."""
    return compiled.as_text(), mem_stats(compiled)


def lowered_text(fn, *args, **jit_kwargs) -> str:
    """PRE-optimization HLO text of ``fn`` lowered (not compiled),
    with per-instruction ``metadata={op_name= source_file=
    source_line=}`` — mxprec's substrate.  The pre-opt dump keeps the
    program as written (a bf16 ``dot`` without
    ``preferred_element_type`` is still a bf16 dot, not the f32 op +
    round-trip converts backend float normalization rewrites it
    into), which is the level an AMP policy must reason at."""
    import jax
    lowered = jax.jit(fn, **jit_kwargs).lower(*args)
    return inline_source_sites(
        lowered.as_text(dialect="hlo", debug_info=True))


def lowered_summary(fn, *args, **jit_kwargs) -> Dict:
    """``program_ledger`` of the PRE-optimization lowering of ``fn``
    — the sanctioned route for tests that need dtype-flow facts about
    a program as written."""
    return program_ledger(lowered_text(fn, *args, **jit_kwargs))


def compiled_summary(fn, *args, **jit_kwargs) -> Dict:
    """Contract-shaped summary of ``fn`` compiled on the current
    backend."""
    text, mem = compiled_artifact(fn, *args, **jit_kwargs)
    return summarize(text, mem)


def compiled_evidence(fn, *args, **jit_kwargs) -> List[Dict[str, str]]:
    """Custom-call bracket evidence rows for ``fn`` compiled on the
    current backend."""
    text, _ = compiled_artifact(fn, *args, **jit_kwargs)
    return bracket_evidence(parse_hlo(text))


# ----------------------------------------------------------------------
# runtime audit (MXTPU_HLO_AUDIT)
# ----------------------------------------------------------------------
def _knob_mode(name: str) -> int:
    from mxtpu import knobs
    v = str(knobs.get(name)).strip().lower()
    if v in ("", "0", "false", "off"):
        return 0
    return 2 if v == "2" else 1


def audit_mode() -> int:
    """0 off (default), 1 warn, 2 raise."""
    return _knob_mode("MXTPU_HLO_AUDIT")


def prec_audit_mode() -> int:
    """``MXTPU_PREC_AUDIT``: 0 off (default), 1 warn, 2 raise."""
    return _knob_mode("MXTPU_PREC_AUDIT")


def mem_audit_mode() -> int:
    """``MXTPU_MEM_AUDIT``: 0 off (default), 1 warn, 2 raise."""
    return _knob_mode("MXTPU_MEM_AUDIT")


def audit_stamp() -> Dict[str, int]:
    """This process's audit modes as the persistent-cache entry meta
    (``mxtpu.cache``): the knobs are per-process, so a disk entry
    records how strictly its WRITER audited and a reader with
    stricter modes re-audits the reloaded program instead of trusting
    the writer's (possibly absent) cold-birth audit."""
    return {"hlo_audit": audit_mode(), "prec_audit": prec_audit_mode(),
            "mem_audit": mem_audit_mode()}


def needs_reaudit(meta: Dict) -> bool:
    """True when this process audits more strictly than the writer of
    a cache entry stamped with ``meta`` did (missing/legacy stamps
    count as unaudited)."""
    def _m(v) -> int:
        return v if isinstance(v, int) else 0
    return (audit_mode() > _m(meta.get("hlo_audit"))
            or prec_audit_mode() > _m(meta.get("prec_audit"))
            or mem_audit_mode() > _m(meta.get("mem_audit")))


def maybe_audit(compiled, label: str = "",
                mem: Optional[Dict[str, int]] = None
                ) -> Optional[Dict]:
    """Audit one freshly compiled program if ``MXTPU_HLO_AUDIT`` /
    ``MXTPU_PREC_AUDIT`` ask for it; returns the summary (or None when
    both audits are off).  Called at compile sites only — compiles are
    rare and expensive, so reading the knobs here keeps the off path
    at zero overhead.

    The precision audit classifies dtypeflow hazards over the same
    compiled text; post-optimization dumps lack source metadata and
    normalize some sub-f32 math, so it catches the surviving forms
    (f64 creep, narrowing-accumulator reduce regions, sub-f32 dots) —
    the full pre-opt analysis lives in ``python -m tools.mxprec``.

    The memory audit (``MXTPU_MEM_AUDIT``) checks the program's peak
    HBM per device against the device-class budget
    (``MXTPU_MEM_BUDGET`` override, else contracts/mem/budgets.json)
    — the ledger-level decomposition lives in ``python -m
    tools.mxmem``."""
    mode = audit_mode()
    pmode = prec_audit_mode()
    mmode = mem_audit_mode()
    if not mode and not pmode and not mmode:
        return None
    if mem is None:
        mem = mem_stats(compiled)
    program = parse_hlo(compiled.as_text())
    summ = summarize(program, mem)
    if mode:
        findings = audit_findings(summ, label)
        if findings:
            msg = "HLO audit: " + "; ".join(findings)
            if mode >= 2:
                from mxtpu.base import MXNetError
                raise MXNetError(msg + " (MXTPU_HLO_AUDIT=2)")
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
    if pmode:
        where = f" in {label}" if label else ""
        hazards = hazard_findings(program)
        if hazards:
            msg = (f"precision audit{where}: "
                   + "; ".join(format_hazard(h) for h in hazards))
            if pmode >= 2:
                from mxtpu.base import MXNetError
                raise MXNetError(msg + " (MXTPU_PREC_AUDIT=2)")
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
    if mmode:
        mfindings = mem_audit_findings(mem, label)
        if mfindings:
            msg = "memory audit: " + "; ".join(mfindings)
            if mmode >= 2:
                from mxtpu.base import MXNetError
                raise MXNetError(msg + " (MXTPU_MEM_AUDIT=2)")
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
    return summ
