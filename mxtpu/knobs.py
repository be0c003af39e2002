"""Central registry of every ``MXTPU_*`` environment knob (ISSUE 5).

One declaration per knob — name, type, default, one-line doc — and one
accessor, :func:`get`, that every call site in ``mxtpu/``, ``tools/``
and ``bench.py`` goes through.  The registry is the single source of
truth three consumers share:

* runtime reads (:func:`get` — live ``os.environ`` lookup, typed,
  with the reference's ``MXNET_*`` spelling accepted as a fallback
  exactly like ``base.get_env`` always did);
* the README knob table (:func:`readme_table` generates it;
  ``python -m tools.mxlint --fix-readme`` writes it between the
  ``<!-- mxlint:knob-table -->`` markers, and the lint's
  ``knob-readme-drift`` check fails when it goes stale);
* ``tools/mxlint``'s ``knob-unregistered`` / ``knob-raw-env`` rules —
  reading an ``MXTPU_*`` name that is not declared here, or reading
  one through raw ``os.environ`` instead of :func:`get`, is a lint
  violation.

This module must stay importable WITHOUT jax and WITHOUT the mxtpu
package (tools/mxlint loads it by file path so linting never pays a
jax import); keep it free of framework imports.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, NamedTuple, Optional

try:  # normal package import
    from .base import MXNetError as _Err
except ImportError:  # standalone import by path (tools/mxlint)
    _Err = RuntimeError  # type: ignore[assignment,misc]

__all__ = ["Knob", "register", "get", "registered", "readme_table"]

_TRUTHY = {"1", "true", "yes", "on"}
_FALSY = {"0", "false", "no", "off", ""}


class Knob(NamedTuple):
    name: str
    default: Any
    kind: str          # "bool" | "int" | "float" | "str"
    doc: str
    group: str         # README table grouping


_REGISTRY: Dict[str, Knob] = {}
_MISSING = object()


def register(name: str, default: Any, kind: str = "str", doc: str = "",
             group: str = "misc") -> Knob:
    if kind not in ("bool", "int", "float", "str"):
        raise _Err(f"knob {name}: unknown kind {kind!r}")
    if not name.startswith("MXTPU_"):
        raise _Err(f"knob {name!r} must be MXTPU_-prefixed")
    if name in _REGISTRY:
        raise _Err(f"knob {name} registered twice")
    knob = Knob(name, default, kind, doc, group)
    _REGISTRY[name] = knob
    return knob


def _coerce(knob: Knob, raw: str) -> Any:
    if knob.kind == "bool":
        low = raw.strip().lower()
        if low in _TRUTHY:
            return True
        if low in _FALSY:
            return False
        raise _Err(f"invalid boolean value {knob.name}={raw!r}")
    if knob.kind == "int":
        return int(raw)
    if knob.kind == "float":
        return float(raw)
    return raw


def get(name: str, default: Any = _MISSING) -> Any:
    """Typed live read of a registered knob.  The environment always
    wins; otherwise ``default`` (when given) overrides the registered
    default.  ``MXNET_<suffix>`` is consulted as a fallback spelling so
    reference-era scripts keep working."""
    knob = _REGISTRY.get(name)
    if knob is None:
        raise _Err(
            f"unregistered knob {name!r} — declare it in mxtpu/knobs.py "
            f"(tools/mxlint enforces this)")
    raw = os.environ.get(name)
    if raw is None:
        raw = os.environ.get("MXNET_" + name[len("MXTPU_"):])
    if raw is None:
        return knob.default if default is _MISSING else default
    return _coerce(knob, raw)


def registered() -> Dict[str, Knob]:
    return dict(_REGISTRY)


# ----------------------------------------------------------------------
# The registry.  Every MXTPU_* name read anywhere in the tree (and the
# coordination names tools/launch.py exports to workers) is declared
# here; keep defaults in sync with the consuming module's docs.
# NOTE: first argument must stay a string literal — tools/mxlint
# cross-references these declarations.
# ----------------------------------------------------------------------

# -- performance kill switches (each =0 restores the pre-optimization
#    behaviour exactly; README "Performance kill switches & knobs") ----
register("MXTPU_FUSED_LN_EPILOGUE", True, "bool",
         "Fused bias+dropout+add+LayerNorm Pallas epilogue; `0` "
         "reverts to the unfused lax composite.", "kill-switch")
register("MXTPU_FUSED_BN", False, "bool",
         "Opt-in one-HBM-pass Pallas BatchNorm(Add)Relu kernel; the "
         "default composite keeps XLA-fused epilogues (BASELINE.md "
         "\"Fused-BN verdict\").", "kill-switch")
register("MXTPU_FLASH_BWD", "auto", "str",
         "Flash-attention backward: `auto` (length-based pick), "
         "`pallas` (blockwise kernel), `ref` (recompute composite).",
         "kill-switch")
register("MXTPU_PALLAS", "auto", "str",
         "Pallas kernel dispatch: `auto` (on TPU), `interpret` "
         "(interpreter mode for CPU testing), `0` (disable).",
         "kill-switch")
register("MXTPU_EXECUTOR_JIT", True, "bool",
         "Symbolic Executor compiles the bound graph under a "
         "shape-keyed jax.jit; `0` falls back to eager per-op "
         "interpretation.", "kill-switch")
register("MXTPU_AMP", "", "str",
         "Policy-driven bf16 autocast (mxtpu.amp, consumes "
         "contracts/amp_policy.json): `0` is the kill switch — forces "
         "AMP off everywhere and the trained/served programs are "
         "bit-identical to pre-AMP; `1` force-enables it for every "
         "TrainStep/ModelRunner; unset defers to the per-call "
         "`amp=` argument.", "kill-switch")
register("MXTPU_AMP_LOSS_SCALE", 65536.0, "float",
         "Initial dynamic loss scale for AMP training (power of two; "
         "grows x2 per stable window, halves on non-finite grads).  "
         "`0` disables loss scaling entirely (pure autocast, no "
         "skipped-step logic).", "kill-switch")
register("MXTPU_AMP_SCALE_WINDOW", 2000, "int",
         "Consecutive finite-grad steps before the AMP loss scale "
         "doubles (the grow window; backoff on a non-finite step is "
         "immediate).", "kill-switch")
register("MXTPU_QUANT", "", "str",
         "Policy-driven INT8 post-training quantization (mxtpu.quant, "
         "consumes contracts/quant_policy.json): `0` is the kill "
         "switch — forces quantization off everywhere and the served "
         "programs are bit-identical to the unquantized path; `1` "
         "force-enables it for every ModelRunner; unset defers to the "
         "per-call `quant=` argument.", "kill-switch")
register("MXTPU_QUANT_CALIB", "entropy", "str",
         "Calibration collector for mxtpu.quant activation "
         "thresholds: `entropy` (KL-minimizing threshold, the "
         "reference's TensorRT-style search) or `minmax` (abs-max).",
         "kill-switch")
register("MXTPU_QUANT_CALIB_BATCHES", 10, "int",
         "Maximum representative batches a ModelRunner.calibrate() "
         "pass consumes when the caller does not say otherwise.",
         "kill-switch")

# -- guards (this PR) --------------------------------------------------
register("MXTPU_GUARDS", "", "str",
         "Runtime guard rails (mxtpu.guards): `1` warn on recompile "
         "churn and pin TrainStep/ModelRunner dispatch transfer-clean "
         "via jax.transfer_guard; `2` raise instead of warn; "
         "unset/`0` = off with zero overhead.", "guards")
register("MXTPU_GUARDS_CHURN_LIMIT", 10, "int",
         "Compiles tolerated per guarded jit entry before the "
         "recompile-churn guard fires (ModelRunner adds its bucket-"
         "ladder size).", "guards")
register("MXTPU_RACE", False, "bool",
         "Rerun the test suite under the mxrace lockset sanitizer "
         "(mxtpu/analysis/lockset.py): threading.Lock/RLock are "
         "traced and the serving/obs classes are instrumented per "
         "their `# guarded-by:` annotations — empty candidate "
         "locksets, guarded-by violations, and runtime lock-order "
         "inversions fail the test with the access sites named.  "
         "Test-time only (`MXTPU_RACE=1 pytest tests/`); unset = "
         "zero overhead, the sanitizer is never imported.  The "
         "static half lives in `python -m tools.mxrace`.", "guards")

register("MXTPU_HLO_AUDIT", "", "str",
         "Static HLO audit (mxtpu.analysis) of every program "
         "TrainStep / serving ModelRunner compiles: `1` warn when "
         "the compiled step contains host transfers, f64 creep, or "
         "custom calls bracketed by transpose/copy; `2` raise; "
         "unset/`0` = off with zero overhead.  Contract checks "
         "against committed lockfiles live in `python -m "
         "tools.hlocheck`.", "guards")

register("MXTPU_PREC_AUDIT", "", "str",
         "Precision audit (mxtpu.analysis.dtypeflow) of every program "
         "TrainStep / serving ModelRunner compiles: `1` warn when the "
         "compiled step contains bf16 accumulating reductions, "
         "matmuls missing preferred_element_type=f32, or f64 creep; "
         "`2` raise; unset/`0` = off with zero overhead.  Ledger "
         "checks against contracts/prec/ live in `python -m "
         "tools.mxprec`.", "guards")

register("MXTPU_MEM_AUDIT", "", "str",
         "Memory audit (mxtpu.analysis.memflow) of every program "
         "TrainStep / serving ModelRunner / GenerateRunner compiles: "
         "`1` warn when the program's peak HBM per device (temp + "
         "argument bytes) exceeds the device-class budget; `2` "
         "raise; unset/`0` = off with zero overhead.  Ledger checks "
         "against contracts/mem/ live in `python -m tools.mxmem`.",
         "guards")

register("MXTPU_MEM_BUDGET", 0, "int",
         "Per-device HBM byte budget the MXTPU_MEM_AUDIT runtime "
         "check enforces.  `0` (default) = use the default device "
         "class from contracts/mem/budgets.json; any other value "
         "overrides the limit in bytes (tests and constrained "
         "deploys).", "guards")

# -- observability (mxtpu.obs) -----------------------------------------
register("MXTPU_OBS", True, "bool",
         "Unified observability layer (mxtpu.obs): metrics registry, "
         "per-request trace ids, flight recorders.  `0` = off: the "
         "factories hand back shared no-op instruments, so hot paths "
         "pay nothing (asserted by `obs.self_check()` at bench "
         "import).", "obs")
register("MXTPU_OBS_FLIGHT_CAPACITY", 256, "int",
         "Flight-recorder ring size — structured events kept per "
         "worker (oldest evicted first).", "obs")
register("MXTPU_OBS_DUMP_ON_ERROR", "", "str",
         "Extra flight-recorder postmortems: unset = dump only on "
         "worker death; `1` also dumps every recorder when a fleet "
         "request fails terminally; a directory path additionally "
         "writes each postmortem there as JSON.", "obs")
register("MXTPU_OBS_SAMPLE_PERIOD_US", 1000000, "int",
         "Time-series sampler period (obs.sampler): how often "
         "maybe_sample() snapshots the metrics registry into the "
         "bounded per-series rings that back windowed rates, "
         "p50/p95/p99 and SLO burn windows.", "obs")
register("MXTPU_OBS_HTTP_PORT", -1, "int",
         "Debug HTTP server (obs.debug_server): /metrics /varz "
         "/healthz /statusz /tracez on loopback.  -1 = never serve "
         "(default); 0 = ephemeral port (tests read it back from "
         "server.port); >0 = fixed port.", "obs")
register("MXTPU_SLO_CLASSES", "", "str",
         "Declarative latency SLOs, comma-separated "
         "`name:endpoint:target_ms:objective[:percentile]` (e.g. "
         "`interactive:fleet:50:0.95`), parsed by "
         "obs.parse_slo_classes into LatencySLO objects next to the "
         "built-in availability SLO.", "obs")

# -- numerics / engine -------------------------------------------------
register("MXTPU_ENGINE_TYPE", "ThreadedEnginePerDevice", "str",
         "`NaiveEngine` forces synchronous execution for debugging "
         "(reference MXNET_ENGINE_TYPE).", "engine")
register("MXTPU_ENGINE_SYNC", False, "bool",
         "`1` forces a blocking wait after every engine op (pairs "
         "with MXTPU_ENGINE_TYPE=NaiveEngine).", "engine")
register("MXTPU_EXEC_BULK_EXEC_TRAIN", True, "bool",
         "Allow bulked (scanned) multi-step training execution.",
         "engine")
register("MXTPU_DEFAULT_DTYPE", "float32", "str",
         "Default NDArray dtype.", "engine")
register("MXTPU_BN_VMEM_CAP_MB", 120, "int",
         "Scoped-VMEM budget for the Pallas BN kernel's channel-block "
         "selection.", "engine")
register("MXTPU_BN_LAYOUT", "auto", "str",
         "Fused-BN kernel operand layout: `auto` picks channels-minor "
         "(C on lanes, one (rows, C) block) when the whole stage fits "
         "the VMEM cap, else channels-major; `cm`/`major` force a "
         "variant.", "engine")
register("MXTPU_KVSTORE_BIGARRAY_BOUND", 1048576, "int",
         "Arrays >= this many elements use the big-array kvstore "
         "path.", "engine")
register("MXTPU_SAVE_FORMAT", "", "str",
         "Checkpoint container: `legacy` (reference dmlc stream) or "
         "`mxtpu` (MXTPU01 npz); unset picks by file extension.",
         "engine")
register("MXTPU_PROFILER_AUTOSTART", False, "bool",
         "Start the chrome-trace profiler at import.", "engine")

# -- serving -----------------------------------------------------------
register("MXTPU_SERVING_MAX_BATCH", 32, "int",
         "ModelRunner bucket-ladder cap (pow2 rungs up to this).",
         "serving")
register("MXTPU_SERVING_MAX_DELAY_US", 2000.0, "float",
         "DynamicBatcher assembly window in microseconds.", "serving")
register("MXTPU_SERVING_MAX_QUEUE", 0, "int",
         "Bound on queued requests before ServerBusy shedding "
         "(0/unset = 8x max batch).", "serving")
register("MXTPU_SERVING_DONATE", True, "bool",
         "Donate padded input buffers to the serving executable on "
         "accelerator backends.", "serving")
register("MXTPU_GEN_MAX_LANES", 8, "int",
         "KV-cache lanes per GenerateRunner: the continuous-batching "
         "decode width (one in-flight generation per lane).",
         "serving")
register("MXTPU_GEN_MAX_TOKENS", 64, "int",
         "Default per-request generation cap when submit passes no "
         "max_tokens.", "serving")
register("MXTPU_GEN_STREAM", True, "bool",
         "Stream tokens through the incremental result channel as "
         "they decode (off = deliver only the final sequence).",
         "serving")

# -- serving fleet (router / health / retry) ---------------------------
register("MXTPU_FLEET_LIVENESS_S", 2.0, "float",
         "Liveness deadline on a dispatched batch: in-flight past "
         "this is SUSPECT, past 2x is a hang (DEAD).", "fleet")
register("MXTPU_FLEET_DEAD_AFTER", 3, "int",
         "Consecutive canary failures on a SUSPECT worker before it "
         "is declared DEAD.", "fleet")
register("MXTPU_FLEET_CANARY_INTERVAL_S", 5.0, "float",
         "Seconds between canary inferences per worker (0 disables "
         "active health checks).", "fleet")
register("MXTPU_FLEET_CANARY_TIMEOUT_S", 1.0, "float",
         "Deadline on each canary inference.", "fleet")
register("MXTPU_FLEET_RETRY_MAX", 3, "int",
         "Router-level re-dispatch cap per request (retriable "
         "failures only).", "fleet")
register("MXTPU_FLEET_BACKOFF_BASE_US", 1000, "int",
         "Retry backoff base: min(cap, base * 2^(n-1)) + jitter.",
         "fleet")
register("MXTPU_FLEET_BACKOFF_CAP_US", 64000, "int",
         "Retry backoff cap in microseconds.", "fleet")
register("MXTPU_FLEET_JITTER", 0.2, "float",
         "Backoff jitter fraction (deterministic seeded RNG).",
         "fleet")
register("MXTPU_FLEET_HEDGE_AFTER_US", 0, "int",
         "Hedge a still-in-flight request onto a second worker after "
         "this many microseconds (0 disables hedging).", "fleet")
register("MXTPU_FLEET_MAX_PENDING", 1024, "int",
         "Bound on the router's parked-retry buffer before "
         "ServerBusy shedding.", "fleet")
register("MXTPU_FLEET_TICK_S", 0.005, "float",
         "Router ticker period in threaded mode.", "fleet")

# -- fleet control plane (autoscaler / admission / priority) -----------
register("MXTPU_FLEET_AUTOSCALE_MIN", 1, "int",
         "Autoscaler floor: never drain below this many healthy "
         "workers.", "controlplane")
register("MXTPU_FLEET_AUTOSCALE_MAX", 4, "int",
         "Autoscaler ceiling on live (non-dead) workers.",
         "controlplane")
register("MXTPU_FLEET_AUTOSCALE_UP_DEPTH", 4.0, "float",
         "Scale-up band: mean outstanding requests per healthy worker "
         "(router backlog included) above this counts as an overload "
         "tick.", "controlplane")
register("MXTPU_FLEET_AUTOSCALE_DOWN_DEPTH", 0.5, "float",
         "Scale-down band: mean outstanding per healthy worker below "
         "this (with an empty router backlog) counts as an underload "
         "tick.", "controlplane")
register("MXTPU_FLEET_AUTOSCALE_UP_ETA_US", 0.0, "float",
         "Additional scale-up trigger: predicted queue ETA "
         "(ServingStats.queue_eta_us) above this many microseconds "
         "counts as overload (0 disables the ETA signal).",
         "controlplane")
register("MXTPU_FLEET_AUTOSCALE_BURN", False, "bool",
         "Let an attached SLO engine's firing burn-rate alerts count "
         "as autoscaler overload ticks (scale up while the error "
         "budget is burning even if queue depth looks fine).  Off by "
         "default: scaling behaviour is bit-identical to the "
         "pre-SLO autoscaler unless explicitly enabled.",
         "controlplane")
register("MXTPU_FLEET_AUTOSCALE_BREACH_TICKS", 3, "int",
         "Hysteresis: consecutive over/under-band evaluations before "
         "the autoscaler acts (bands reset each action).",
         "controlplane")
register("MXTPU_FLEET_AUTOSCALE_COOLDOWN_S", 5.0, "float",
         "Minimum seconds between autoscaler actions (either "
         "direction).", "controlplane")
register("MXTPU_FLEET_ADMISSION", False, "bool",
         "Predictive admission control: shed a deadline-carrying "
         "request at submit with ServerBusy (+retry_after_us) when "
         "the class-aware queue ETA says it cannot finish in time.",
         "controlplane")
register("MXTPU_FLEET_ADMISSION_MARGIN", 1.0, "float",
         "Admission safety factor: shed when margin x predicted ETA "
         "exceeds the deadline budget (>1 sheds earlier, <1 gambles).",
         "controlplane")
register("MXTPU_FLEET_CLASSES", "", "str",
         "Priority/fairness classes as `name:weight[:quota],...` "
         "(e.g. `gold:8,bulk:1:64`): weight sets the weighted-round-"
         "robin dispatch share, quota bounds in-system requests per "
         "class.  Unset = one `default` class.", "controlplane")

# -- persistent compile cache (mxtpu/cache.py) -------------------------
register("MXTPU_CACHE", True, "bool",
         "Master switch for the persistent AOT executable cache: "
         "`0` = always compile, never touch disk.  The disk layer is "
         "also inert while MXTPU_CACHE_DIR is unset.", "cache")
register("MXTPU_CACHE_DIR", "", "str",
         "Root directory of the on-disk compiled-executable cache "
         "(crash-safe writes, checksum-verified loads).  ModelRunner "
         "buckets and AOT TrainStep programs load-or-compile through "
         "it; unset disables persistence.", "cache")
register("MXTPU_CACHE_SALT", "", "str",
         "Extra cache-key component: bump it to invalidate every "
         "cached executable (rollout epoch, config generation).",
         "cache")

# -- bench / tools -----------------------------------------------------
register("MXTPU_BENCH_MODEL", "all", "str",
         "bench.py workload selector (lenet|resnet50|bert|transformer|"
         "moe_ffn|ssd|bert_zero|serving_bert|... or `all`).", "bench")
register("MXTPU_BENCH_BATCH", 256, "int",
         "bench.py ResNet-50 global batch size.", "bench")
register("MXTPU_BENCH_DTYPE", "bfloat16", "str",
         "bench.py compute dtype (empty = model default).", "bench")
register("MXTPU_BENCH_WALL_BUDGET", 780.0, "float",
         "bench.py global wall-clock budget in seconds; over-budget "
         "rows are recorded as skipped.", "bench")
register("MXTPU_BENCH_ROW_BUDGET", 90.0, "float",
         "bench.py conservative per-row wall estimate used by the "
         "budget gate.", "bench")
register("MXTPU_PROFILE_BERT_MODEL", "large", "str",
         "tools/profile_bert.py model tier (tiny|base|large).",
         "bench")
register("MXTPU_PROBE_CONV", True, "bool",
         "tools/probe_bn_fusion.py: `0` skips the in-context conv "
         "probe.", "bench")

# -- distributed launch (written by tools/launch.py for workers) -------
register("MXTPU_COORDINATOR", "", "str",
         "Coordinator address exported to launched worker processes.",
         "launch")
register("MXTPU_NUM_PROCESSES", 1, "int",
         "World size exported to launched worker processes.", "launch")
register("MXTPU_PROCESS_ID", 0, "int",
         "Process rank exported to launched worker processes.",
         "launch")

# -- test harness ------------------------------------------------------
register("MXTPU_TEST_PLATFORM", "cpu", "str",
         "Test platform: `cpu` (virtual 8-device mesh) or `tpu`.",
         "test")
register("MXTPU_TEST_SEED", 42, "int",
         "Deterministic per-test seed (reference MXNET_TEST_SEED).",
         "test")
register("MXTPU_TEST_SLOW", False, "bool",
         "Enable heavy model-zoo test variants.", "test")


# ----------------------------------------------------------------------
# README generation
# ----------------------------------------------------------------------
_GROUP_TITLES = [
    ("kill-switch", "Performance kill switches"),
    ("guards", "Runtime guards"),
    ("obs", "Observability"),
    ("engine", "Engine / numerics"),
    ("serving", "Serving"),
    ("fleet", "Serving fleet"),
    ("controlplane", "Fleet control plane"),
    ("cache", "Persistent compile cache"),
    ("bench", "Bench & profiling tools"),
    ("launch", "Distributed launch"),
    ("test", "Test harness"),
]

TABLE_BEGIN = "<!-- mxlint:knob-table:begin (generated by " \
    "`python -m tools.mxlint --fix-readme`; do not edit by hand) -->"
TABLE_END = "<!-- mxlint:knob-table:end -->"


def _fmt_default(knob: Knob) -> str:
    if knob.kind == "bool":
        return "on" if knob.default else "off"
    if knob.default == "":
        return "unset"
    return f"`{knob.default}`"


def readme_table() -> str:
    """The README knob table, generated from the registry (checked
    for drift by tools/mxlint's knob-readme-drift rule)."""
    out: List[str] = [TABLE_BEGIN, ""]
    for group, title in _GROUP_TITLES:
        knobs = [k for k in _REGISTRY.values() if k.group == group]
        if not knobs:
            continue
        out.append(f"**{title}**")
        out.append("")
        out.append("| knob | type | default | effect |")
        out.append("|---|---|---|---|")
        for k in sorted(knobs, key=lambda k: k.name):
            doc = " ".join(k.doc.split())
            out.append(f"| `{k.name}` | {k.kind} | {_fmt_default(k)} "
                       f"| {doc} |")
        out.append("")
    out.append(f"({len(_REGISTRY)} knobs registered in "
               f"`mxtpu/knobs.py`.)")
    out.append("")
    out.append(TABLE_END)
    return "\n".join(out)
