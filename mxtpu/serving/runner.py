# mxlint: hot-path
"""ModelRunner — AOT-compiled bucketed inference executors sharing one
weight upload (ISSUE 4 tentpole item 1).

The TPU-native analog of the reference's C predict API over per-bucket
shared-weight executors (``MXPredReshape``† / ``BucketingModule``†,
SURVEY.md §3): a deployed model (``Module.save_checkpoint`` / gluon
``export`` artifacts, parsed through the same ``c_predict`` binding
path) is compiled ONCE PER SHAPE BUCKET — a powers-of-two batch ladder
crossed with optional sequence-length buckets for token models — into
XLA executables via ``jax.jit(..).lower(..).compile()``.  Weights are
uploaded to the device once and the SAME committed buffers feed every
bucket executable (the ``MXPredReshape`` zero-copy contract, asserted
by test); input buffers are donated on accelerator backends so the
padded batch staging buffer is recycled into the executable's
workspace.

Why buckets instead of dynamic shapes: XLA compiles static shapes.  A
pow2 batch ladder caps the number of programs at log2(max_batch) per
sequence bucket while bounding padding waste at <2x in the worst case
and ~1.3x expected under uniform fill — the same trade the reference's
``BucketingModule`` made for variable-length RNNs.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import MXNetError
from .. import guards
from .. import knobs
from .. import obs
from .. import profiler
from .batcher import InferenceRequest

__all__ = ["ModelRunner", "batch_ladder"]


def batch_ladder(max_batch_size: int) -> Tuple[int, ...]:
    """Powers-of-two ladder 1,2,4,… capped at ``max_batch_size`` (the
    cap itself is always a rung so full batches never pad)."""
    if max_batch_size < 1:
        raise MXNetError("max_batch_size must be >= 1")
    rungs = []
    b = 1
    while b < max_batch_size:
        rungs.append(b)
        b *= 2
    rungs.append(max_batch_size)
    return tuple(rungs)


class ModelRunner:
    """Load-once, compile-per-bucket, run-many inference engine.

    Parameters
    ----------
    symbol : mxtpu.symbol.Symbol
        The inference graph (deployment artifact).
    params : dict name -> numpy/NDArray
        Trained weights (``arg:``/``aux:`` prefixes already stripped).
    input_specs : dict name -> per-example shape tuple
        Shapes EXCLUDE the batch axis.  A ``None`` entry marks the
        variable (sequence) axis of a token model and requires
        ``seq_buckets``; e.g. ``{"data": (None,)}`` for token ids.
    input_dtypes : dict name -> dtype, optional (default float32)
    seq_buckets : ascending ints, optional
        Sequence-length rungs for every ``None`` axis.
    max_batch_size : int, optional (env MXTPU_SERVING_MAX_BATCH, 32)
    device : jax device, optional — one runner binds ONE device; build
        one runner per replica for data-parallel serving and let
        ``InferenceServer`` round-robin across them.
    pad_value : scalar used for sequence padding (default 0).
    cache : "auto" | None | mxtpu.cache.ExecutableCache
        The persistent executable cache (ISSUE 13).  "auto" (default)
        uses the knob-configured process cache (inert unless
        ``MXTPU_CACHE_DIR`` is set); None opts this runner out; an
        explicit :class:`~mxtpu.cache.ExecutableCache` pins one (fleet
        tests share a tmpdir cache this way).  Every bucket compile
        becomes load-or-compile: a verified disk hit skips tracing AND
        compilation, a miss compiles and serializes for the next
        process.
    """

    def __init__(self, symbol, params: Dict[str, Any],
                 input_specs: Dict[str, Tuple],
                 input_dtypes: Optional[Dict[str, Any]] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 max_batch_size: Optional[int] = None,
                 device=None, pad_value: float = 0,
                 donate: Optional[bool] = None, cache: Any = "auto",
                 amp=None, quant=None):
        import jax

        # policy-driven AMP (mxtpu.amp): weights upload bf16 (half the
        # serving HBM), re-enter the graph in f32, and only the
        # policy's allow-listed contractions compute in bf16.
        # MXTPU_AMP=0 kills it; off-path programs are bit-identical.
        from .. import amp as _amp_mod
        self._amp = _amp_mod.resolve(amp)
        # policy-driven INT8 quantization (mxtpu.quant): after a
        # calibrate() pass records activation thresholds, every
        # bucket compiles with the policy's allow-listed contractions
        # as s8xs8 GEMMs accumulating in i32.  MXTPU_QUANT=0 kills
        # it; off-path programs are bit-identical.
        from .. import quant as _quant_mod
        self._quant = _quant_mod.resolve(quant)
        self._quant_scales: Optional[Dict[str, float]] = None
        self._symbol = symbol
        self._input_names = list(input_specs)
        self._input_specs = {k: tuple(v) for k, v in input_specs.items()}
        self._input_dtypes = {
            k: np.dtype((input_dtypes or {}).get(k, np.float32))
            for k in input_specs}
        # Serving knobs (mxtpu/knobs.py, README "Serving"): the env
        # defaults feed every runner that does not pass explicit
        # values, so a deployment can be retuned without code changes.
        self.max_batch_size = int(
            max_batch_size if max_batch_size is not None
            else knobs.get("MXTPU_SERVING_MAX_BATCH"))
        self.batch_buckets = batch_ladder(self.max_batch_size)
        self.seq_buckets = tuple(sorted(int(s) for s in seq_buckets)) \
            if seq_buckets else None
        has_var = any(None in spec for spec in self._input_specs.values())
        if has_var and not self.seq_buckets:
            raise MXNetError(
                "serving: input_specs contain a variable (None) axis — "
                "pass seq_buckets")
        self._pad_value = pad_value
        self._device = device if device is not None else jax.devices()[0]
        if donate is None:
            donate = knobs.get("MXTPU_SERVING_DONATE")
        # _donate records the INTENT (what mxmem's donation-missed
        # rule audits); the CPU backend, where XLA drops donation,
        # is gated at the jit site in _entry so compiled programs
        # stay byte-identical there.
        self._donate = bool(donate)  # mxlint: disable=host-sync

        # -- one weight upload, shared by every bucket executable ------
        known = set(symbol.list_inputs())
        self._param_names = tuple(
            n for n in params if n in known and n not in input_specs)
        missing = known - set(self._param_names) - set(input_specs)
        if missing:
            raise MXNetError(
                f"serving: graph inputs {sorted(missing)} have neither "
                f"a param nor an input_spec")
        if self._amp:
            # bf16 weight storage: aux-named params (BN running
            # stats) stay f32 — their EMA magnitudes need the
            # mantissa; everything else halves its upload + HBM
            import jax.numpy as jnp
            from ..symbol import _is_aux_name

            def _stage(n):
                v = self._as_np(params[n])
                if v.dtype == np.float32 and not _is_aux_name(n):
                    v = v.astype(jnp.bfloat16)
                return jax.device_put(v, self._device)

            self._param_vals = tuple(_stage(n)
                                     for n in self._param_names)
        else:
            self._param_vals = tuple(
                jax.device_put(self._as_np(params[n]), self._device)
                for n in self._param_names)
        # lowering must pin THIS replica's device, or every runner
        # would compile (and expect buffers) on jax.devices()[0]
        self._sharding = jax.sharding.SingleDeviceSharding(self._device)
        self._param_structs = tuple(
            jax.ShapeDtypeStruct(v.shape, v.dtype,
                                 sharding=self._sharding)
            for v in self._param_vals)

        # _Endpoint worker threads race through _entry()/warmup() when
        # a server front-loads compiles while requests stream in; the
        # compile cache and its timing ledger are lock-protected so a
        # bucket is compiled exactly once.
        self._lock = threading.Lock()
        self._entries: Dict[Tuple, Any] = {}  # guarded-by: _lock
        self.compile_seconds: Dict[Tuple, float] = {}  # guarded-by: _lock
        self._guards = guards.enabled()
        # One compile per ladder rung is the design; anything past the
        # ladder (+ slack for explicit extra warmup buckets) is churn.
        self._entry_label = f"ModelRunner[{type(symbol).__name__}]"
        self._churn = guards.ChurnDetector(
            self._entry_label, limit=len(self.buckets()) + 4)
        # mxtpu.obs wiring (cached bool; no-op singletons when off):
        # compile events feed the registry AND the "compile" flight
        # recorder so a postmortem shows every cache miss with timing.
        self._obs = obs.enabled()
        self._region = obs.region_writer(self._obs)
        self._m_compile = obs.counter(
            "mxtpu_serving_compile_total",
            "Bucket executables actually compiled by XLA (cold "
            "builds only — disk-cache hits count in "
            "mxtpu_compile_cache_hit_total instead).",
            labels=("entry",)).labels(entry=self._entry_label)
        # source=cold|disk makes the cold-vs-warm split machine-
        # readable (ISSUE 13 satellite): "cold" paid XLA, "disk"
        # paid a verified deserialize off the persistent cache.
        _h = obs.histogram(
            "mxtpu_serving_compile_seconds",
            "Per-bucket entry build wall time (source=cold: XLA "
            "compile; source=disk: verified load from the persistent "
            "cache).", labels=("entry", "source"))
        self._m_compile_s = {
            src: _h.labels(entry=self._entry_label, source=src)
            for src in ("cold", "disk")}
        # the disk-hit counter next to ChurnDetector's
        # mxtpu_compile_cache_miss_total: of the in-process misses,
        # how many the persistent cache absorbed.
        self._m_cache_hit = obs.counter(
            "mxtpu_compile_cache_hit_total",
            "In-process compile-cache misses served from the "
            "persistent disk cache instead of XLA.",
            labels=("entry",)).labels(entry=self._entry_label)

        # ISSUE 13: the persistent executable cache + this runner's
        # model fingerprint (what was compiled: graph, input/param
        # signatures, donation — weights are runtime inputs, so one
        # entry serves every checkpoint of the same architecture).
        from .. import cache as cache_mod
        self._cache = cache_mod.default_cache() if cache == "auto" \
            else cache
        self._fingerprint = ""
        if self._cache is not None:
            self._fingerprint = self._model_fingerprint()

    @staticmethod
    def _as_np(v):
        # mxlint: sync-point — host-side param ingest, pre-upload
        return v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)

    # -- deployment-artifact constructors -------------------------------
    @classmethod
    def from_export(cls, symbol_file: str, params_file: str, **kwargs
                    ) -> "ModelRunner":
        """Load gluon ``HybridBlock.export`` / ``Module.save_checkpoint``
        artifacts (``-symbol.json`` + ``-NNNN.params``), parsing the
        params blob through the c_predict binding path."""
        from .. import symbol as sym_mod
        from ..c_predict import _params_from_bytes
        with open(symbol_file) as f:
            symbol = sym_mod.load_json(f.read())
        with open(params_file, "rb") as f:
            params = _params_from_bytes(f.read())
        return cls(symbol, params, **kwargs)

    @classmethod
    def from_checkpoint(cls, prefix: str, epoch: int, **kwargs
                        ) -> "ModelRunner":
        """``prefix-symbol.json`` + ``prefix-{epoch:04d}.params``."""
        return cls.from_export(f"{prefix}-symbol.json",
                               f"{prefix}-{epoch:04d}.params", **kwargs)

    # -- buckets ---------------------------------------------------------
    def bucket_for(self, n: int, seq_len: Optional[int] = None) -> Tuple:
        """Smallest (batch_bucket, seq_bucket) ladder rung covering a
        batch of ``n`` examples of length ``seq_len``."""
        if n < 1:
            raise MXNetError("serving: empty batch")
        if n > self.max_batch_size:
            raise MXNetError(
                f"serving: batch {n} exceeds max_batch_size "
                f"{self.max_batch_size}")
        b = next(r for r in self.batch_buckets if r >= n)
        if self.seq_buckets is None:
            return (b, None)
        if seq_len is None:
            raise MXNetError("serving: token model needs seq_len")
        if seq_len > self.seq_buckets[-1]:
            raise MXNetError(
                f"serving: seq_len {seq_len} exceeds largest bucket "
                f"{self.seq_buckets[-1]}")
        s = next(r for r in self.seq_buckets if r >= seq_len)
        return (b, s)

    def seq_bucket_for(self, seq_len: Optional[int]) -> Optional[int]:
        """The batcher's grouping key: requests sharing a seq bucket
        may batch together; batch-size bucketing happens at dispatch."""
        if self.seq_buckets is None:
            return None
        return self.bucket_for(1, seq_len)[1]

    def buckets(self) -> List[Tuple]:
        """The full ladder (what ``warmup()`` compiles)."""
        seqs = self.seq_buckets or (None,)
        return [(b, s) for s in seqs for b in self.batch_buckets]

    def _concrete_shape(self, name: str, batch: int,
                        seq: Optional[int]) -> Tuple[int, ...]:
        return (batch,) + tuple(seq if d is None else int(d)
                                for d in self._input_specs[name])

    # -- persistent cache keys (ISSUE 13) --------------------------------
    def _model_fingerprint(self) -> str:
        """sha256 over everything that shapes the compiled program
        EXCEPT the bucket: graph json, input specs/dtypes, param
        signatures, donation, pad semantics.  Weight VALUES are
        excluded on purpose — they are runtime arguments, so the same
        entry warms every checkpoint of this architecture."""
        import hashlib
        import json as _json
        # canonicalize gensym'd op-node names ("broadcast_mul7" — a
        # process-global counter) so two independently constructed
        # copies of the same graph fingerprint identically; edges and
        # heads are index-based, so op names are cosmetic.  Input
        # ("null") nodes keep their real names — they ARE semantics.
        graph = _json.loads(self._symbol.tojson())
        for i, node in enumerate(graph.get("nodes", ())):
            if node.get("op") not in (None, "null"):
                node["name"] = f"_op{i}"
        fp = {
            "symbol": graph,
            "inputs": {n: [list(self._input_specs[n]),
                           str(self._input_dtypes[n])]
                       for n in self._input_names},
            "params": [[n, list(v.shape), str(v.dtype)]
                       for n, v in zip(self._param_names,
                                       self._param_vals)],
            "donate": self._donate, "pad_value": self._pad_value,
        }
        if self._amp:
            # key only when ON: every pre-AMP cache entry (and the
            # MXTPU_AMP=0 path) keeps its fingerprint unchanged
            fp["amp"] = True
        if self._quant:
            # the calibrated thresholds are trace-baked constants, so
            # they ARE part of what was compiled — recalibration must
            # miss.  Keyed only when ON (same rule as amp).
            fp["quant"] = sorted(
                (self._quant_scales or {}).items()) or True
        blob = _json.dumps(fp, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:24]

    def _cache_key(self, bucket: Tuple):
        """The persistent-cache key of one bucket executable: model
        fingerprint x concrete bucket shape x single-device topology
        (+ the environment components ExecutableCache.key adds — jax
        version, backend, contract hash, salt)."""
        batch, seq = bucket
        shapes = {n: list(self._concrete_shape(n, batch, seq))
                  for n in self._input_names}
        extra = {}
        if self._quant:
            # explicit `quant` key component on top of the fingerprint
            # scales: a quantized executable can NEVER be loaded by an
            # unquantized runner, or vice versa (tests/test_cache.py)
            extra["quant"] = "int8"
        return self._cache.key(
            model=self._fingerprint, shape=str(sorted(shapes.items())),
            mesh="1dev", devices=(self._device,), **extra)

    def cached_buckets(self) -> List[Tuple]:
        """The subset of this runner's ladder present in the
        persistent cache right now (existence probe only; loads are
        verified later) — what the fleet consults before deciding a
        donor-less replacement can warm from disk."""
        if self._cache is None:
            return []
        return [b for b in self.buckets()
                if self._cache.contains(self._cache_key(b))]

    def warm_from_disk(self) -> Dict[Tuple, float]:
        """Warm every ladder bucket the persistent cache holds (a
        poisoned/stale entry quarantines and recompiles inside
        ``_entry`` — still off the data path).  Returns per-bucket
        build seconds; empty dict when there is no cache or no
        entries."""
        hits = self.cached_buckets()
        if not hits:
            return {}
        return self.warmup(hits)

    # -- INT8 calibration (mxtpu.quant, ISSUE 18) -------------------------
    def calibrate(self, batches: Sequence[Dict[str, Any]],
                  mode: Optional[str] = None,
                  num_batches: Optional[int] = None,
                  collector=None) -> Dict[str, float]:
        """Post-training calibration: run representative ``batches``
        (dicts of batched host arrays, one per input) EAGERLY through
        the deployed graph, observing every candidate contraction's
        activations with the chosen collector (``mode``: minmax |
        entropy; default the MXTPU_QUANT_CALIB knob).  The resulting
        per-tensor |x| thresholds arm the quantized trace path of
        every subsequent bucket compile, and re-fingerprint the
        persistent-cache identity (thresholds are trace-baked
        constants).  Deterministic given fixed batches — byte-equal
        threshold tables across runs.  Must run before warmup()."""
        import jax.numpy as jnp
        from .. import autograd
        from .. import quant as _quant_mod
        from ..ndarray.ndarray import NDArray
        from ..symbol import _eval_symbol
        if not self._quant:
            raise MXNetError(
                "serving: calibrate() on a non-quantized runner — "
                "pass quant=True (or MXTPU_QUANT=1), and note "
                "MXTPU_QUANT=0 overrides both")
        with self._lock:
            if self._entries:
                raise MXNetError(
                    "serving: calibrate() after buckets compiled — "
                    "calibration changes every program; calibrate "
                    "before warmup()")
        if num_batches is None:
            _, num_batches = _quant_mod.calib_config()
        if collector is None:
            collector = _quant_mod.make_collector(mode)
        # params enter in f32 exactly as _pure_fn re-enters them, so
        # the observed activations match the traced graph's
        param_nd = {
            n: NDArray(v.astype(jnp.float32)
                       if (jnp.issubdtype(v.dtype, jnp.floating)
                           and v.dtype != jnp.float32) else v,
                       None, _placed=True)
            for n, v in zip(self._param_names, self._param_vals)}
        prev_rec = autograd.set_recording(False)
        prev_train = autograd.set_training(False)
        try:
            for i, batch in enumerate(batches):
                if i >= num_batches:
                    break
                bindings = dict(param_nd)
                for n in self._input_names:
                    # mxlint: sync-point — host batch staging, offline
                    arr = np.asarray(batch[n], self._input_dtypes[n])
                    bindings[n] = NDArray(arr, None)
                with _quant_mod.calibrating(collector):
                    _eval_symbol(self._symbol, bindings)
        finally:
            autograd.set_training(prev_train)
            autograd.set_recording(prev_rec)
        self._quant_scales = collector.thresholds()  # mxrace: disable=unguarded-attr (pre-serving setup: calibrate raises once any bucket compiled, so no concurrent reader exists yet and the table is immutable afterwards)
        if not self._quant_scales:
            raise MXNetError(
                "serving: calibration observed no quantizable "
                "contraction — the graph has no FullyConnected/"
                "Convolution on f32 inputs")
        if self._cache is not None:
            self._fingerprint = self._model_fingerprint()  # mxrace: disable=unguarded-attr (same setup phase: re-fingerprint before any compile/serve thread can read it)
        return dict(self._quant_scales)

    def quant_scales(self) -> Optional[Dict[str, float]]:
        """The calibrated activation-threshold table (None before
        :meth:`calibrate`)."""
        return dict(self._quant_scales) \
            if self._quant_scales is not None else None

    # -- AOT compile ------------------------------------------------------
    def _pure_fn(self):
        """Pure (traceable) interpretation of the symbol: (input_vals,
        param_vals) -> tuple of raw outputs, inference mode (no
        recording, training=False — dropout is identity)."""
        import contextlib
        import jax.numpy as jnp
        from .. import amp as _amp_mod
        from .. import autograd
        from .. import quant as _quant_mod
        from ..ndarray.ndarray import NDArray
        from ..symbol import _eval_symbol
        sym = self._symbol
        in_names = tuple(self._input_names)
        p_names = self._param_names
        amp_on = self._amp
        quant_on = self._quant
        if quant_on and self._quant_scales is None:
            raise MXNetError(
                "serving: quantized runner has no calibrated scales — "
                "run calibrate(batches) before compiling buckets")
        quant_scales = self._quant_scales

        def fn(input_vals, param_vals):
            if amp_on:
                # AMP entry upcast (the TrainStep rule): bf16 weights
                # re-enter the graph in f32 so only the policy's
                # allow-listed contractions — cast back down inside
                # the autocast scope — ever compute in bf16; XLA
                # folds the convert pair at the weight→dot edges
                param_vals = tuple(
                    v.astype(jnp.float32)
                    if (jnp.issubdtype(v.dtype, jnp.floating)
                        and v.dtype != jnp.float32)
                    else v for v in param_vals)
            bindings = {}
            for n, v in zip(in_names, input_vals):
                bindings[n] = NDArray(v, None, _placed=True)
            for n, v in zip(p_names, param_vals):
                bindings[n] = NDArray(v, None, _placed=True)
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(False)
            # scope nesting: quant outermost — a contraction with a
            # recorded scale becomes an int8 GEMM; anything it leaves
            # on the float path still gets amp's bf16 cast when both
            # passes are on
            scope = contextlib.ExitStack()
            if quant_on:
                scope.enter_context(_quant_mod.quantize(quant_scales))
            if amp_on:
                scope.enter_context(_amp_mod.autocast())
            try:
                with scope:
                    outs = _eval_symbol(sym, bindings)
            finally:
                autograd.set_training(prev_train)
                autograd.set_recording(prev_rec)
            return tuple(o.data for o in outs)

        return fn

    def _entry(self, bucket: Tuple):
        """Compile (once) and return the bucket's XLA executable.
        Holding ``_lock`` across the compile trades warmup parallelism
        for the exactly-once contract: two worker threads hitting the
        same cold bucket would otherwise both pay the compile and one
        executable would be silently dropped."""
        with self._lock:
            entry = self._entries.get(bucket)
            if entry is not None:
                return entry
            import jax
            if self._guards:
                self._churn.note_compile(bucket)
            batch, seq = bucket
            in_structs = tuple(
                jax.ShapeDtypeStruct(self._concrete_shape(n, batch, seq),
                                     self._input_dtypes[n],
                                     sharding=self._sharding)
                for n in self._input_names)
            t0 = time.perf_counter()
            # ISSUE 13: load-or-compile through the persistent cache.
            # A verified disk hit skips tracing AND compilation; any
            # corrupt/truncated/stale entry quarantines inside
            # load() and we fall through to the cold path.
            from mxtpu import analysis
            compiled, source, ckey, cmeta = None, "cold", None, {}
            with self._region(obs.SPAN_COMPILE, entry=self._entry_label,
                              kind="serve", bucket=str(bucket)) as rg:
                if self._cache is not None:
                    ckey = self._cache_key(bucket)
                    compiled, cmeta = self._cache.load(ckey, with_meta=True)  # mxlint: sync-point — disk, pre-serving
                    if compiled is not None:
                        source = "disk"
                if compiled is None:
                    # donation applied only where XLA honors it; on
                    # cpu it is a silent no-op, so skipping it keeps
                    # that backend's programs byte-identical
                    apply_donate = (self._donate and
                                    jax.default_backend() != "cpu")
                    jitted = jax.jit(
                        self._pure_fn(),
                        donate_argnums=(0,) if apply_donate else ())
                    compiled = jitted.lower(
                        in_structs, self._param_structs).compile()
                    # MXTPU_HLO_AUDIT: static hygiene pass over every
                    # bucket executable as it is born (warmup()
                    # therefore audits the whole ladder) — no host
                    # transfers, no f64 creep, no layout-bracketed
                    # custom calls.  Audit BEFORE the store so a
                    # program that fails a raising audit never
                    # reaches disk.
                    analysis.maybe_audit(compiled,
                                         label=f"ModelRunner{bucket}")
                    if ckey is not None:
                        # serialize for the next process, stamped
                        # with this process's audit modes; failures
                        # degrade to a flight-recorder event inside
                        # store()
                        self._cache.store(ckey, compiled,
                                          meta=analysis.audit_stamp())
                elif analysis.needs_reaudit(cmeta):
                    # the audit knobs are per-process: the writer
                    # audited less strictly than this process asks
                    # for (or not at all), so the reloaded program is
                    # audited here
                    analysis.maybe_audit(compiled,
                                         label=f"ModelRunner{bucket}")
                rg.set(source=source)
            self.compile_seconds[bucket] = time.perf_counter() - t0
            entry = {"compiled": compiled, "in_structs": in_structs}
            self._entries[bucket] = entry
            if self._obs:
                if source == "cold":
                    # actual XLA compiles only — disk hits are entry
                    # builds but not compiles (dashboards read this
                    # as compile volume)
                    self._m_compile.inc()
                else:
                    self._m_cache_hit.inc()
                self._m_compile_s[source].observe(
                    self.compile_seconds[bucket])
                obs.flight("compile").record(
                    "compile_miss", entry=self._entry_label,
                    bucket=str(bucket), source=source,
                    seconds=round(self.compile_seconds[bucket], 4))
            return entry

    def warmup(self, buckets: Optional[Sequence[Tuple]] = None
               ) -> Dict[Tuple, float]:
        """Pre-compile the ladder (or a subset) so no production request
        pays a compile; returns per-bucket compile seconds."""
        with guards.no_implicit_transfers(self._guards):
            for bucket in (buckets if buckets is not None
                           else self.buckets()):
                self._entry(tuple(bucket))
        with self._lock:
            return dict(self.compile_seconds)

    # -- execution --------------------------------------------------------
    def _pad_stack(self, rows: List[Dict[str, np.ndarray]],
                   bucket: Tuple) -> Tuple:
        """Per-example input dicts -> padded device-ready arrays of the
        bucket's shape.  Batch padding repeats row 0 (keeps values in
        the embedding/index domain — zeros could be out-of-vocab for
        some models, row 0 never is); sequence padding uses
        ``pad_value``."""
        import jax
        batch, seq = bucket
        vals = []
        for name in self._input_names:
            shape = self._concrete_shape(name, batch, seq)
            dt = self._input_dtypes[name]
            buf = np.empty(shape, dt)
            for i, row in enumerate(rows):
                # mxlint: sync-point — staging host rows, not device data
                ex = np.asarray(row[name], dt)
                if ex.shape != shape[1:]:
                    # sequence-pad every None axis up to the bucket
                    pads, slices = [], []
                    for d, (want, got) in enumerate(
                            zip(shape[1:], ex.shape)):
                        if got > want:
                            raise MXNetError(
                                f"serving: input {name!r} axis {d} size "
                                f"{got} exceeds bucket {want}")
                        pads.append((0, want - got))
                        slices.append(slice(0, got))
                    ex = np.pad(ex, pads, constant_values=self._pad_value)
                buf[i] = ex
            if len(rows) < batch:
                buf[len(rows):] = buf[0]
            vals.append(jax.device_put(buf, self._device))
        return tuple(vals)

    def run_raw(self, input_vals: Tuple, bucket: Tuple) -> Tuple:
        """One executable dispatch on pre-padded device arrays — the
        back-to-back path bench.py measures batcher overhead against."""
        entry = self._entry(bucket)
        if self._guards:
            self._churn.note_call()
        with guards.no_implicit_transfers(self._guards):
            return entry["compiled"](input_vals, self._param_vals)

    def infer(self, inputs: Dict[str, np.ndarray],
              seq_len: Optional[int] = None) -> List[np.ndarray]:
        """Synchronous batched inference: ``inputs`` carry a leading
        batch axis; pads to the covering bucket, runs, slices back.
        Returns host numpy arrays (one per graph output)."""
        names = self._input_names
        # mxlint: sync-point — inputs are caller-supplied host arrays
        n = int(np.asarray(inputs[names[0]]).shape[0])
        if seq_len is None and self.seq_buckets is not None:
            seq_len = int(np.asarray(inputs[names[0]]).shape[1])  # mxlint: sync-point
        bucket = self.bucket_for(n, seq_len)
        rows = [{name: np.asarray(inputs[name])[i] for name in names}  # mxlint: sync-point
                for i in range(n)]
        vals = self._pad_stack(rows, bucket)
        outs = self.run_raw(vals, bucket)
        # mxlint: sync-point — the one deliberate D2H: materialize outputs
        return [np.asarray(o)[:n] for o in outs]

    def run_requests(self, requests: List[InferenceRequest],
                     now: Optional[float] = None,
                     mutate=None) -> Tuple:
        """Server path: execute one assembled same-group batch and
        scatter each request its OWN output rows (sequence axis trimmed
        back to the request's true length).  Returns (bucket, outputs)
        for stats.  ``mutate`` (host outputs -> host outputs) is the
        fault-injection seam — mxtpu.serving.faults corrupts results
        here so canary-based detection is exercised deterministically;
        production callers leave it None."""
        n = len(requests)
        seq = requests[0].group if self.seq_buckets is not None else None
        bucket = self.bucket_for(n, seq)
        # obs phase spans (pad/scatter, execute) — gated BEFORE any
        # timing/args work so the profiler-off path is one bool read
        active = profiler.is_active()
        tids = [r.trace_id for r in requests
                if r.trace_id is not None] if active else []
        t0 = profiler._now_us() if active else 0.0
        vals = self._pad_stack([r.payload for r in requests], bucket)
        if active:
            t1 = profiler._now_us()
            obs.span(obs.SPAN_PAD_SCATTER, t0, t1 - t0, cat="serving",
                     trace_ids=tids, bucket=str(bucket), batch=n)
        outs = self.run_raw(vals, bucket)
        # mxlint: sync-point — deliberate D2H before scattering rows
        host = [np.asarray(o) for o in outs]
        if active:
            obs.span(obs.SPAN_RUN, t1, profiler._now_us() - t1,
                     cat="serving", trace_ids=tids,
                     bucket=str(bucket), batch=n)
        if mutate is not None:
            host = mutate(host)
        done_t = time.monotonic() if now is None else now
        for i, r in enumerate(requests):
            row_outs = []
            for o in host:
                row = o[i]
                # un-pad the sequence axis (axis 0 of the per-example
                # view) when this output still carries the bucket length
                if (seq is not None and r.seq_len is not None
                        and row.ndim >= 1 and row.shape[0] == seq
                        and r.seq_len < seq):
                    row = row[:r.seq_len]
                row_outs.append(row)
            r._complete(row_outs, done_t)
        return bucket, host

    # -- introspection ----------------------------------------------------
    def program_artifact(self, bucket: Tuple):
        """``(hlo_text, mem_stats)`` of one bucket's compiled
        executable (compiling it if cold) — what tools/hlocheck
        summarizes into the serving contract."""
        from mxtpu import analysis
        compiled = self._entry(tuple(bucket))["compiled"]
        return compiled.as_text(), analysis.mem_stats(compiled)

    def program_summary(self, bucket: Tuple):
        """Contract-shaped static summary (``mxtpu.analysis``) of one
        bucket's compiled executable."""
        from mxtpu import analysis
        text, mem = self.program_artifact(bucket)
        return analysis.summarize(text, mem)

    def memory_summary(self, buckets: Optional[Sequence[Tuple]] = None):
        """The sanctioned memory view (``mxtpu.analysis.memflow``) of
        this runner's bucket ladder (largest bucket by default):
        per-program HBM decomposition with weights attributed, plus
        any memory hazard findings — what tests and operators read
        instead of raw ``memory_analysis()`` grepping (mxlint
        ``mem-hygiene``)."""
        from mxtpu.analysis import memflow
        if buckets is None:
            buckets = [self.buckets()[-1]]
        record = memflow.runner_record(self, buckets=buckets)
        budgets = memflow.load_budgets(
            memflow.REPO_ROOT / "contracts")
        return memflow.summary_view(record, budgets)

    def lowered_program_text(self, bucket: Tuple) -> str:
        """PRE-optimization HLO (with source metadata) of one
        bucket's program — lowers only, never compiles, so mxprec can
        ledger a cold ladder without paying warmup."""
        import jax
        from mxtpu import analysis
        batch, seq = tuple(bucket)
        in_structs = tuple(
            jax.ShapeDtypeStruct(
                self._concrete_shape(n, batch, seq),
                self._input_dtypes[n], sharding=self._sharding)
            for n in self._input_names)
        return analysis.lowered_text(self._pure_fn(), in_structs,
                                     self._param_structs)

    def num_compiled(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- fleet handoff (ISSUE 7: preemption-safe draining) ---------------
    def ladder_metadata(self) -> Dict[str, Any]:
        """What a draining worker hands its replacement: the ladder
        shape plus WHICH buckets were actually compiled (traffic-driven
        subset) and what each cost — so the replacement warms exactly
        the donor's working set instead of the full cross product."""
        with self._lock:
            compiled = sorted(self._entries)
            secs = dict(self.compile_seconds)
        return {"max_batch_size": self.max_batch_size,
                "seq_buckets": list(self.seq_buckets)
                if self.seq_buckets is not None else None,
                "compiled_buckets": [list(b) for b in compiled],
                "compile_seconds": {str(k): v for k, v in secs.items()},
                "weight_bytes": self.weight_bytes()}

    def warm_from(self, metadata: Dict[str, Any]) -> Dict[Tuple, float]:
        """Warm this (replacement) runner from a donor's
        :meth:`ladder_metadata` — compiles the donor's bucket set,
        restricted to buckets this runner's own ladder actually has
        (a replacement with a different ladder warms the
        intersection)."""
        own = set(self.buckets())
        donor = [tuple(b) for b in metadata.get("compiled_buckets", [])]
        return self.warmup([b for b in donor if b in own])

    def weight_buffers(self) -> Tuple:
        """The committed device arrays every bucket executable reads —
        tests assert these stay the SAME buffers across buckets (the
        MXPredReshape zero-copy contract)."""
        return self._param_vals

    def weight_bytes(self) -> int:
        return int(sum(v.nbytes for v in self._param_vals))
