"""Pallas TPU kernels — the cuDNN-fusion tier of the reference
(``src/operator/nn/cudnn/``†), rebuilt as hand-written TPU kernels for
the ops XLA's automatic fusion doesn't nail (SURVEY.md §7 M6).

Dispatch policy: kernels engage on the TPU backend (or when
``MXTPU_PALLAS=interpret`` forces interpreter mode for CPU testing);
every kernel has a pure-lax reference implementation used as fallback
and as the parity oracle in tests.
"""
from __future__ import annotations

import jax

from .. import knobs

__all__ = ["layer_norm", "flash_attention", "pallas_enabled",
           "precision_metadata", "layout_metadata"]


_KERNELS = ("flash_attention", "layer_norm", "batch_norm", "kv_write",
            "ssm_update")


def layout_metadata():
    """``{kernel_name: LAYOUT}`` for every Pallas kernel — the
    declared operand-layout contract (which physical layouts each
    custom call binds without relayout copies, and the knob that
    picks a variant).  The layout half of the AMP/MFU work: transpose
    brackets around custom calls are invisible to cost_analysis, so
    the contract is stated where dispatch lives and audited by
    test/hlocheck instead of rediscovered per regression."""
    import importlib
    return {
        name: dict(importlib.import_module(
            f"{__name__}.{name}").LAYOUT)
        for name in _KERNELS
    }


def precision_metadata():
    """``{kernel_name: PRECISION}`` for every Pallas kernel that
    declares its accumulation discipline — evidence for mxprec's
    ``contracts/amp_policy.json`` ``custom_calls`` section (custom
    calls are opaque to the HLO dtype-flow scan)."""
    # the kernel entry points shadow their module names in this
    # namespace (``flash_attention`` is the function), so resolve the
    # modules explicitly
    import importlib
    return {
        name: dict(importlib.import_module(
            f"{__name__}.{name}").PRECISION)
        for name in _KERNELS
    }


def pallas_enabled() -> bool:
    """True when the Pallas path should be used."""
    flag = knobs.get("MXTPU_PALLAS")
    if flag in ("0", "off", "false"):
        return False
    if flag == "interpret":
        return True
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas interpreter mode exactly when the backend is not a TPU:
    on the chip a kernel is always handed to the TPU compiler."""
    return jax.default_backend() != "tpu"


from .layer_norm import layer_norm, layer_norm_reference  # noqa: E402
from .flash_attention import (flash_attention,  # noqa: E402
                              attention_reference)
