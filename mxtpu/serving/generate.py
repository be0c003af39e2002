# mxlint: hot-path
"""mxtpu.serving.generate — KV-cache incremental decode with
continuous batching, token streaming, and replay-on-steal (ISSUE 19
tentpole).

Three pieces:

- :class:`GenerateRunner` AOT-compiles a *prefill* executable per
  (batch-rung x prompt-bucket) and ONE incremental *decode-step*
  executable over a preallocated bucket-paged KV cache.  The cache is
  a slot table: each in-flight request owns a cache *lane* (axis 2 of
  the one ``(num_layers, 2, slots, heads, L, head_dim)`` array).  A
  model with more than one kind of per-lane state declares a *state
  spec* instead (:class:`StateTable`: name, shape with its lane axis,
  dtype — keys and values by position beside recurrent state of fixed
  size); every table is allocated, donated, threaded and handed back
  together, and such a graph also takes each row's number of valid new
  tokens.  Either way a prefill call hands back what a decode call
  does: one row of logits a lane, left on the device
  (:class:`DeviceLogits`).  The
  graph threads the whole table through its layers: layer i's
  ``kv_cache_write`` (a decode step's one token a lane: one Pallas
  kernel that stores the column, ``mxtpu.kernels.kv_write``, where the
  device keeps the table with ``L`` minor; otherwise a loop over the
  lanes, each turn one ``lax.dynamic_update_slice`` on the table) puts
  each lane's new rows at its OWN step index of planes
  ``(i, 0)`` and ``(i, 1)``, ``kv_cache_read`` hands those planes to
  ``cached_attention``, which masks scores to each lane's valid
  prefix, so stale cache beyond a lane's frontier is unreachable and
  lane reuse needs no zeroing.  Nothing cuts the table apart or stacks
  it anew, so the donated table is updated in place: the table a
  program returns is the buffer it was given.  Both
  executables load-or-compile through the persistent disk cache
  (ISSUE 13) under generation-specific keys, so a rollout's first
  token on a warmed worker is never a compile.

- :class:`GenerateRequest` is the streaming future: tokens fire
  through ``on_token`` as they are sampled, ``result()`` returns the
  full stream, and ``partial_state()`` describes generation progress
  so a worker death mid-decode hands the fleet layer everything a
  replay needs (prompt + already-streamed tokens + the ORIGINAL
  submit clock — ``WorkerLost.partial``).

- :class:`GenerateBatcher` is the continuous (in-flight) batching
  policy, pure and clock-injected like :class:`DynamicBatcher`: each
  ``step(now)`` admits queued requests into freed lanes (join at a
  step boundary — grouped by prompt bucket, prefilled, first token
  sampled), runs ONE decode step over all lanes, samples/streams one
  token per active lane, and evicts finished (EOS / max_tokens /
  capacity) and deadline-expired requests.  Deterministic in sync
  mode — fake-clock tests drive it step by step.

Sampling is replay-deterministic: a greedy lane takes its row's first
maximum, found on the device after a prefill as after a decode step;
a top-k draw is made on the host, seeded by ``(seed,
absolute_position)`` — the same token ids come out across runs AND
across a mid-stream worker steal, because a replayed request resumes
at the same absolute positions.
"""
from __future__ import annotations

import math
import threading
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import numpy as np

from ..base import MXNetError
from .. import guards
from .. import knobs
from .. import obs
from .. import profiler
from .batcher import (InferenceRequest, RequestTimeout, ServerBusy,
                      WorkerLost, _lost_for)
from .runner import batch_ladder

__all__ = ["GenerateRequest", "GenerateRunner", "GenerateBatcher",
           "StateTable", "DeviceLogits", "sample_token"]


class StateTable(NamedTuple):
    """One per-lane state table of an incremental graph: ``shape`` with
    the lane count at ``lane_axis``, held on the device as ``dtype``."""
    name: str
    shape: Tuple[int, ...]
    lane_axis: int
    dtype: str


def sample_token(logits, *, position: int, seed: int = 0,
                 top_k: int = 1) -> int:
    """Replay-deterministic host-side sampling of ONE token.

    ``top_k <= 1`` is greedy argmax.  Otherwise the top-k logits are
    softmaxed and drawn with a generator seeded by ``(seed,
    absolute_position)`` — a pure function of (logits, seed,
    position), so a replayed generation that re-reaches the same
    position samples the SAME token regardless of which worker (or
    which run) computes it."""
    if top_k is None or top_k <= 1:
        # the first maximum of the row as it came: a float64 copy of a
        # hundred thousand logits would find the same one — and so does
        # the device, for a row it kept (``DeviceLogits``)
        first = getattr(logits, "first_maximum", None)
        if first is not None:
            return int(first)
        return int(np.argmax(np.asarray(logits).reshape(-1)))  # mxlint: sync-point — logits are already host rows here
    # mxlint: sync-point — logits are already host rows here
    row = np.asarray(logits, np.float64).reshape(-1)  # mxlint: disable=dtype-hygiene (f64 host sampling on purpose: platform-identical softmax/ties)
    k = min(int(top_k), row.shape[0])
    idx = np.argpartition(row, -k)[-k:]
    # stable descending order: ties break by token id, not partition
    # order, so the distribution is identical on every platform
    idx = idx[np.lexsort((idx, -row[idx]))]
    sub = row[idx] - row[idx].max()
    p = np.exp(sub)
    p /= p.sum()
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF,
                                 int(position) & 0x7FFFFFFF])
    return int(idx[rng.choice(k, p=p)])


class DeviceLogits:
    """A call's logits ``(rows, 1, V)`` as ``prefill`` and ``decode``
    hand them back — a prefill's row is its prompt's last valid
    position, a decode step's its slot's one new position: left on the
    device, beside each row's first maximum, found there and brought
    over — 4 bytes a row where a row is 400 KB at a vocabulary of
    100,352.  Indexed as the host array would be, ``logits[row, 0]`` is
    a row that ``sample_token`` draws greedily from without touching
    its numbers; whoever wants the numbers (``np.asarray`` of the whole
    or of a row: a top-k draw, a test) brings all of them over, once."""

    class Row:
        __slots__ = ("first_maximum", "_of", "_slot")

        def __init__(self, of, slot):
            self._of, self._slot = of, slot
            self.first_maximum = int(of.first_maximum[slot])

        def __array__(self, dtype=None, copy=None):
            row = self._of.host()[self._slot, 0]
            return row if dtype is None else row.astype(dtype)

    def __init__(self, rows, first_maximum: np.ndarray):
        self.rows, self.first_maximum = rows, first_maximum
        self._host = None

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.rows.shape)

    def host(self) -> np.ndarray:
        """The numbers, brought over at the first call and kept."""
        if self._host is None:
            # mxlint: sync-point — deliberate D2H, on demand: someone samples from the numbers
            self._host = np.asarray(self.rows)
        return self._host

    def __array__(self, dtype=None, copy=None):
        rows = self.host()
        return rows if dtype is None else rows.astype(dtype)

    def __getitem__(self, at):
        slot, _ = at
        return self.Row(self, slot)


class GenerateRequest(InferenceRequest):
    """Streaming generation future.

    ``prompt`` is the token-id list to condition on; ``prefix`` is the
    already-streamed continuation a REPLAY resumes from (empty for a
    fresh request) — the worker prefills ``prompt + prefix`` and the
    first freshly sampled token has stream index ``len(prefix)``.
    ``on_token(token, index)`` fires per emitted token (the streaming
    channel); ``result()`` returns the full stream
    ``prefix + new tokens``.  ``finish_reason`` is "eos" or "length"
    once complete."""

    __slots__ = ("prompt", "max_tokens", "eos_id", "top_k", "seed",
                 "prefix", "on_token", "tokens", "finish_reason")

    def __init__(self, prompt: Sequence[int], *,
                 max_tokens: int, eos_id: Optional[int] = None,
                 top_k: int = 1, seed: int = 0,
                 prefix: Sequence[int] = (),
                 on_token: Optional[Callable[[int, int], None]] = None,
                 group: Any = None, t_submit: float = 0.0,
                 deadline: Optional[float] = None,
                 trace_id: Optional[str] = None):
        prompt = [int(t) for t in prompt]
        super().__init__(prompt, group=group, seq_len=len(prompt),
                         t_submit=t_submit, deadline=deadline,
                         trace_id=trace_id)
        self.prompt = prompt
        self.max_tokens = int(max_tokens)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.prefix = [int(t) for t in prefix]
        self.on_token = on_token
        # tokens emitted by THIS attempt, appended by the (single)
        # stepping thread under the batcher's _cond; readers see them
        # through partial_state() / result() after completion.
        # mxrace: disable=unguarded-attr (single-writer stepping thread)
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None  # mxrace: disable=unguarded-attr (single-writer stepping thread)

    @property
    def emitted(self) -> int:
        """Total stream length so far (replayed prefix included)."""
        return len(self.prefix) + len(self.tokens)

    def partial_state(self) -> Dict[str, Any]:
        """What a replay needs (rides ``WorkerLost.partial`` when the
        worker holding this request dies): the prompt, EVERY token
        streamed so far (prefix + this attempt), and the ORIGINAL
        submit clock + deadline — a replay resumes the stream and
        inherits the first attempt's deadline accounting, it never
        double-bills."""
        return {"prompt": list(self.prompt),
                "tokens": list(self.prefix) + list(self.tokens),
                "t_submit": self.t_submit,
                "deadline": self.deadline}


class GenerateRunner:
    """AOT-compiled prefill + decode-step executables over a slot-table
    KV cache (one device).

    Parameters
    ----------
    symbol : mxtpu.symbol.Symbol
        An incremental export (``HybridBlock.export`` of a model
        called in incremental mode).  With one KV table: inputs
        ``(tokens, step, cache)``, outputs ``(logits, new_cache)``,
        ``logits`` over every position ``(B, S, V)`` (the prefill
        program keeps each row's last valid one), the cache laid out
        ``(num_layers, 2, B, heads, L, head_dim)`` — exactly what
        ``TransformerModel.kv_cache_spec`` /
        ``BERTModel.kv_cache_spec`` describe.  With a state spec:
        inputs ``(tokens, step, length, *tables)``, outputs ``(logits,
        *tables)``; ``length`` (B,) is each row's number of valid new
        tokens, and ``logits`` is ``(B, 1, V)``, one row a lane at its
        last valid position.
    params : dict name -> numpy/NDArray
        Trained weights (uploaded once, shared by every executable).
    kv_spec : tuple
        Either ``net.kv_cache_spec(max_lanes, max_len)`` — axis 2 is
        the lane count, axis 4 the cache capacity L, held in float32
        — or a state spec: a sequence of :class:`StateTable` ``(name,
        shape, lane_axis, dtype)`` such as
        ``HybridDecoderModel.state_spec`` declares, one of them a 6-D
        table named ``"kv"`` whose axis 4 is the capacity: how many
        positions a lane may reach, and the one bound the batcher
        evicts on.  It is that table's alone: another table holds what
        its layers need (a sliding-window layer's ring ``kv_win`` keeps
        window + one chunk of columns, recurrent state no positions at
        all).  The runner
        allocates ONE extra scratch slot in every table (prefill batch
        padding scatters there; its contents are garbage by
        construction and never read), so the device tables have
        ``max_lanes + 1`` slots.  ``new_cache()`` hands out one array
        for the 6-tuple and a tuple of arrays, in the spec's order and
        each in its own dtype, for a state spec; ``prefill``/``decode``
        take and return whichever it is.
    prompt_buckets : ascending ints
        Prompt-length rungs; prefill compiles per (batch-rung x
        prompt-bucket).  Prompts (plus replay prefixes) longer than
        the largest bucket prefill in bucket-width chunks.
    max_prefill_batch : int, optional
        The widest prefill rung there is (default: ``max_lanes``).  A
        prefill holds its rows' gathered lanes beside the tables, so
        where lanes are large the upper rungs of the ladder need more
        memory than the device has: the ladder then ends at this rung
        and the batcher admits at most so many requests a step.
    counters : dict, optional
        What a call of the graph counts beside its logits
        (``HybridDecoderModel.counter_spec``): ``{"device": names,
        "per_token": {name: multiple}}``.  A graph with ``device``
        counters has one more output after its tables, an int32 vector
        with an entry a name; the numbers come over in the fetch that
        brings the token ids.  A ``per_token`` count is the call's
        valid tokens times its multiple, known on the host.  Each is a
        fact of the call's region and adds to the counter
        ``mxtpu_<name>_total``.
    quant_scales : dict, optional — calibrated activation thresholds
        (from a :class:`ModelRunner` ``calibrate()`` over the same
        architecture) arming the int8 trace path; required when
        ``quant`` resolves on.  Quantized executables key SEPARATELY
        in the persistent cache (``quant=int8`` key component).
    """

    def __init__(self, symbol, params: Dict[str, Any],
                 kv_spec: Sequence[int], *,
                 prompt_buckets: Sequence[int],
                 input_names: Optional[Sequence[str]] = None,
                 device=None, donate: Optional[bool] = None,
                 max_prefill_batch: Optional[int] = None,
                 cache: Any = "auto", amp=None, quant=None,
                 quant_scales: Optional[Dict[str, float]] = None,
                 counters: Optional[Dict[str, Any]] = None):
        import jax

        from .. import amp as _amp_mod
        from .. import quant as _quant_mod
        self._amp = _amp_mod.resolve(amp)
        self._quant = _quant_mod.resolve(quant)
        self._quant_scales = dict(quant_scales) if quant_scales else None
        self._symbol = symbol
        counters = counters or {}
        self._device_counters = tuple(counters.get("device", ()))
        self._token_counters = dict(counters.get("per_token", {}))
        # one KV table as a 6-tuple of ints, or a declared state spec
        self._one_table = not isinstance(kv_spec[0], (tuple, list))
        if self._one_table:
            self.state_spec = (StateTable(
                "kv", tuple(int(d) for d in kv_spec), 2, "float32"),)
        else:
            self.state_spec = tuple(
                StateTable(str(n), tuple(int(d) for d in shape),
                           int(axis), jax.numpy.dtype(dt).name)
                for n, shape, axis, dt in kv_spec)
        kv = [t.shape for t in self.state_spec if t.name == "kv"]
        # the declared cache geometry mxmem audits
        self.kv_spec = kv[0] if kv else ()
        if len(self.kv_spec) != 6 or self.kv_spec[1] != 2:
            raise MXNetError(
                "generate: kv_spec must be (num_layers, 2, lanes, "
                "heads, L, head_dim) — use net.kv_cache_spec() — or a "
                "state spec with such a table named 'kv'")
        self.max_lanes = self.kv_spec[2]
        if self.max_lanes < 1:
            raise MXNetError("generate: kv_spec lane count must be >= 1")
        if any(t.shape[t.lane_axis] != self.max_lanes
               for t in self.state_spec):
            raise MXNetError(
                f"generate: every state table must hold "
                f"{self.max_lanes} lanes at its lane axis")
        n_in = 2 + len(self.state_spec) + (0 if self._one_table else 1)
        if input_names is None:
            input_names = tuple(f"data{i}" for i in range(n_in))
        if len(input_names) != n_in:
            raise MXNetError(
                "generate: input_names must be the (tokens, step, "
                "cache) triple of the incremental export, or (tokens, "
                "step, length, *tables) for a state spec")
        self._input_names = tuple(input_names)
        # one scratch slot past the lanes: prefill batch-padding rows
        # scatter there (duplicate scratch writes are garbage by
        # design — the scratch lane is never sampled from)
        self._slots = self.max_lanes + 1
        self.scratch_slot = self.max_lanes
        self._table_shapes = tuple(
            t.shape[:t.lane_axis] + (self._slots,)
            + t.shape[t.lane_axis + 1:] for t in self.state_spec)
        self._kv_shape = self._table_shapes[
            [t.name for t in self.state_spec].index("kv")]
        self.max_len = self.kv_spec[4]
        # what a prefill row gathers: one lane of every table
        self._lane_bytes = sum(self.state_bytes().values()) // self._slots
        self.prompt_buckets = tuple(sorted(int(s)
                                           for s in prompt_buckets))
        if not self.prompt_buckets:
            raise MXNetError("generate: prompt_buckets must be "
                             "non-empty")
        if self.prompt_buckets[-1] > self.max_len:
            raise MXNetError(
                f"generate: largest prompt bucket "
                f"{self.prompt_buckets[-1]} exceeds KV capacity "
                f"{self.max_len}")
        self.batch_buckets = batch_ladder(
            self.max_lanes if max_prefill_batch is None
            else max(1, min(int(max_prefill_batch), self.max_lanes)))
        self._device = device if device is not None else jax.devices()[0]
        if donate is None:
            donate = knobs.get("MXTPU_SERVING_DONATE")
        # _donate records the INTENT (what mxmem's donation-missed
        # rule audits); the CPU backend, where XLA drops donation,
        # is gated at the jit site in _entry so compiled programs
        # stay byte-identical there.
        self._donate = bool(donate)  # mxlint: disable=host-sync

        # -- one weight upload shared by prefill AND decode ------------
        known = set(symbol.list_inputs())
        for n in self._input_names:
            if n not in known:
                raise MXNetError(
                    f"generate: graph has no input {n!r} — pass the "
                    f"incremental export's input_names")
        self._param_names = tuple(
            n for n in params
            if n in known and n not in self._input_names)
        missing = known - set(self._param_names) \
            - set(self._input_names)
        if missing:
            raise MXNetError(
                f"generate: graph inputs {sorted(missing)} have "
                f"neither a param nor an input name")
        if self._amp:
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
        self._sharding = jax.sharding.SingleDeviceSharding(self._device)
        self._param_structs = tuple(
            jax.ShapeDtypeStruct(v.shape, v.dtype,
                                 sharding=self._sharding)
            for v in self._param_vals)

        self._lock = threading.Lock()
        self._entries: Dict[Tuple, Any] = {}  # guarded-by: _lock
        self.compile_seconds: Dict[Tuple, float] = {}  # guarded-by: _lock
        # source per built entry ("cold" paid XLA, "disk" loaded off
        # the persistent cache) — what the zero-cold-compile-on-a-
        # warmed-worker acceptance test asserts on.
        self._compile_sources: Dict[Tuple, str] = {}  # guarded-by: _lock
        self._guards = guards.enabled()
        self._entry_label = f"GenerateRunner[{type(symbol).__name__}]"
        self._churn = guards.ChurnDetector(
            self._entry_label, limit=len(self.buckets()) + 4)
        self._obs = obs.enabled()
        self._region = obs.region_writer(self._obs)
        self._m_compile = obs.counter(
            "mxtpu_serving_compile_total",
            "Bucket executables actually compiled by XLA (cold "
            "builds only — disk-cache hits count in "
            "mxtpu_compile_cache_hit_total instead).",
            labels=("entry",)).labels(entry=self._entry_label)
        _h = obs.histogram(
            "mxtpu_serving_compile_seconds",
            "Per-bucket entry build wall time (source=cold: XLA "
            "compile; source=disk: verified load from the persistent "
            "cache).", labels=("entry", "source"))
        self._m_compile_s = {
            src: _h.labels(entry=self._entry_label, source=src)
            for src in ("cold", "disk")}
        self._m_cache_hit = obs.counter(
            "mxtpu_compile_cache_hit_total",
            "In-process compile-cache misses served from the "
            "persistent disk cache instead of XLA.",
            labels=("entry",)).labels(entry=self._entry_label)
        self._m_temp_bytes = obs.gauge(
            "mxtpu_gen_program_temp_bytes",
            "Temporary bytes the compiled generation program needs "
            "beside its arguments and outputs (a rebuilt KV table "
            "shows here as a table's worth).",
            labels=("kind", "bucket"))
        self._m_state_bytes = obs.gauge(
            "mxtpu_gen_state_bytes",
            "Bytes the device holds for each per-lane state table as "
            "new_cache() allocated it (scratch slot and the device's "
            "tile padding included).",
            labels=("table",))
        self._m_resets = obs.counter(
            "mxtpu_gen_state_reset_total",
            "Prefill rows that started a lane from zero state "
            "(step 0): admissions and replays, not later chunks.")
        self._m_counted = {
            name: obs.counter(f"mxtpu_{name}_total",
                              f"{name}, summed over the generation "
                              f"programs' calls (GenerateRunner's "
                              f"counters).")
            for name in self._device_counters
            + tuple(self._token_counters)}

        from .. import cache as cache_mod
        self._cache = cache_mod.default_cache() if cache == "auto" \
            else cache
        self._fingerprint = ""
        if self._cache is not None:
            self._fingerprint = self._model_fingerprint()

    def _takes_length(self, kind: str) -> bool:
        """Whether the program of ``kind`` takes each row's number of
        valid tokens: a graph with a state spec is told it in both
        kinds; a one-table graph never is, and its prefill program
        alone takes it, to keep each row's last valid position."""
        return kind == "prefill" or not self._one_table

    def _rows(self, kind: str, tokens, step, length):
        """The row inputs the program of ``kind`` takes."""
        return (tokens, step, length) if self._takes_length(kind) \
            else (tokens, step)

    @staticmethod
    def _as_np(v):
        # mxlint: sync-point — host-side param ingest, pre-upload
        return v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)

    @classmethod
    def from_export(cls, symbol_file: str, params_file: str,
                    kv_spec: Sequence[int], **kwargs
                    ) -> "GenerateRunner":
        """Load the incremental export's ``-symbol.json`` +
        ``-NNNN.params`` artifacts through the c_predict binding
        path."""
        from .. import symbol as sym_mod
        from ..c_predict import _params_from_bytes
        with open(symbol_file) as f:
            symbol = sym_mod.load_json(f.read())
        with open(params_file, "rb") as f:
            params = _params_from_bytes(f.read())
        return cls(symbol, params, kv_spec, **kwargs)

    # -- buckets ---------------------------------------------------------
    def prompt_bucket_for(self, need: int) -> int:
        """Smallest prompt bucket covering ``need`` tokens — capped at
        the largest bucket (longer prefills chunk at that width)."""
        if need < 1:
            raise MXNetError("generate: empty prompt")
        for s in self.prompt_buckets:
            if s >= need:
                return s
        return self.prompt_buckets[-1]

    def batch_rung_for(self, n: int) -> int:
        if n < 1 or n > self.batch_buckets[-1]:
            raise MXNetError(
                f"generate: prefill batch {n} outside "
                f"1..{self.batch_buckets[-1]}")
        return next(r for r in self.batch_buckets if r >= n)

    def buckets(self) -> List[Tuple]:
        """Full executable ladder: every (prefill, (batch, prompt))
        rung plus THE decode step — what ``warmup()`` compiles."""
        out: List[Tuple] = [("prefill", (b, s))
                            for s in self.prompt_buckets
                            for b in self.batch_buckets]
        out.append(("decode", (self._slots,)))
        return out

    # -- persistent cache keys (ISSUE 13) --------------------------------
    def _model_fingerprint(self) -> str:
        """sha256 over everything that shapes the compiled programs
        except the bucket: graph json (op names canonicalized), input
        names, KV layout, donation, amp/quant arming.  Weight VALUES
        are runtime arguments — one entry warms every checkpoint of
        the architecture."""
        import hashlib
        import json as _json
        graph = _json.loads(self._symbol.tojson())
        for i, node in enumerate(graph.get("nodes", ())):
            if node.get("op") not in (None, "null"):
                node["name"] = f"_op{i}"
        fp = {
            "symbol": graph,
            "gen_inputs": list(self._input_names),
            "kv_shape": list(self._kv_shape),
            "params": [[n, list(v.shape), str(v.dtype)]
                       for n, v in zip(self._param_names,
                                       self._param_vals)],
            "donate": self._donate,
        }
        if not self._one_table:
            fp["state"] = [[t.name, list(shape), t.lane_axis, t.dtype]
                           for t, shape in zip(self.state_spec,
                                               self._table_shapes)]
        if self._amp:
            fp["amp"] = True
        if self._quant:
            fp["quant"] = sorted(
                (self._quant_scales or {}).items()) or True
        blob = _json.dumps(fp, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:24]

    def _cache_key(self, bucket: Tuple):
        """Persistent-cache key of one generation executable:
        fingerprint x ``gen:<kind>:<shape>`` x device — the ``gen:``
        prefix keys decode-step programs apart from any batch-path
        entry of the same graph, and ``quant=int8`` keys int8 decode
        apart from the float path (never loadable cross-mode)."""
        kind, shp = bucket
        extra = {}
        if self._quant:
            extra["quant"] = "int8"
        return self._cache.key(
            model=self._fingerprint,
            shape=f"gen:{kind}:{tuple(shp)}", mesh="1dev",
            devices=(self._device,), **extra)

    def cached_buckets(self) -> List[Tuple]:
        """Subset of the ladder present in the persistent cache right
        now (existence probe; loads verify later)."""
        if self._cache is None:
            return []
        return [b for b in self.buckets()
                if self._cache.contains(self._cache_key(b))]

    def warm_from_disk(self) -> Dict[Tuple, float]:
        """Warm every ladder entry the persistent cache holds —
        zero cold compiles on a warmed worker (asserted by test via
        :meth:`compile_sources`)."""
        hits = self.cached_buckets()
        if not hits:
            return {}
        return self.warmup(hits)

    def compile_sources(self) -> Dict[Tuple, str]:
        """Per built entry: "cold" (paid XLA) or "disk" (loaded off
        the persistent cache)."""
        with self._lock:
            return dict(self._compile_sources)

    def cold_compiles(self) -> int:
        with self._lock:
            return sum(1 for s in self._compile_sources.values()
                       if s == "cold")

    # -- pure (traceable) programs ---------------------------------------
    def _scopes(self):
        import contextlib
        from .. import amp as _amp_mod
        from .. import quant as _quant_mod
        if self._quant and self._quant_scales is None:
            raise MXNetError(
                "generate: quantized runner has no calibrated scales "
                "— pass quant_scales (from a ModelRunner.calibrate "
                "over the same architecture)")
        scope = contextlib.ExitStack()
        if self._quant:
            scope.enter_context(
                _quant_mod.quantize(self._quant_scales))
        if self._amp:
            scope.enter_context(_amp_mod.autocast())
        return scope

    def _eval_incremental(self, rows, tables, param_vals):
        """Trace the incremental graph once: (the row inputs, the
        state tables) -> (logits, new tables), inference mode."""
        import jax.numpy as jnp
        from .. import autograd
        from ..ndarray.ndarray import NDArray
        from ..symbol import _eval_symbol
        if self._amp and self._one_table:
            # a graph with a state spec takes its weights as they are
            # staged: its ops bring what they need to float32
            # themselves, and a float32 copy of a bfloat16 embedding
            # would be made anew in every step
            param_vals = tuple(
                v.astype(jnp.float32)
                if (jnp.issubdtype(v.dtype, jnp.floating)
                    and v.dtype != jnp.float32)
                else v for v in param_vals)
        bindings = {n: NDArray(v, None, _placed=True)
                    for n, v in zip(self._input_names,
                                    tuple(rows) + tuple(tables))}
        for n, v in zip(self._param_names, param_vals):
            bindings[n] = NDArray(v, None, _placed=True)
        prev_rec = autograd.set_recording(False)
        prev_train = autograd.set_training(False)
        try:
            with self._scopes():
                outs = _eval_symbol(self._symbol, bindings)
        finally:
            autograd.set_training(prev_train)
            autograd.set_recording(prev_rec)
        counted = 1 if self._device_counters else 0
        if len(outs) != 1 + len(tables) + counted:
            raise MXNetError(
                f"generate: incremental graph must output (logits, "
                f"cache) — one output a state table, then its counts "
                f"if it declares any — got {len(outs)} outputs")
        # the graph's counts, if any, ride behind the tables it hands back
        return (outs[0].data, tuple(o.data for o in outs[1:1 + len(tables)]),
                *(o.data for o in outs[1 + len(tables):]))

    def _prefill_pure(self):
        """(tokens (b,s), step (b,), length (b,), lane_idx (b,),
        state, params) -> (logits (b,1,V), state').  Gather-extend-
        write: each row's lane is pulled from every slot table,
        extended by its tokens at its own step offset, and written
        back — so chunked prefill of a long prompt+prefix is just
        repeated calls at advancing step offsets.  Padding rows target
        the scratch slot.  The logits are each row's last valid
        position (a row of length 0 gives a row nobody reads).  One KV
        table: the graph makes (b,s,V), of which the program keeps
        that row, and the lanes go back by one indexed update.  A
        state spec: the graph is told ``length`` and makes (b,1,V)
        itself, and each table's rows come out one lane at a time
        (``read_whole_lanes``) and go back one lane at a time, in place
        (``write_whole_lanes``), so no program holds a second copy of
        a table whose lanes are megabytes each."""
        import jax
        import jax.numpy as jnp
        from ..ndarray.rnn_impl import read_whole_lanes, write_whole_lanes

        def fn(*args):
            *rows, lane_idx, state, param_vals = args
            with jax.named_scope("gen/prefill_program"):
                idx = lane_idx.astype(jnp.int32)
                if self._one_table:
                    tokens, step, length = rows
                    kv_small = state[:, :, idx]
                    logits, (new_small,) = self._eval_incremental(
                        (tokens, step), (kv_small,), param_vals)
                    b, s = tokens.shape
                    last = jnp.clip(length.astype(jnp.int32) - 1, 0, s - 1)
                    return logits[jnp.arange(b), last][:, None, :], \
                        state.at[:, :, idx].set(
                            new_small.astype(state.dtype))
                axes = [t.lane_axis for t in self.state_spec]
                logits, new, *counts = self._eval_incremental(
                    rows, tuple(read_whole_lanes(t, idx, a)
                                for t, a in zip(state, axes)),
                    param_vals)
                return (logits, tuple(
                    write_whole_lanes(t, n, idx, a)
                    for t, n, a in zip(state, new, axes)), *counts)

        return fn

    def _decode_pure(self):
        """(tokens (slots,1), step (slots,), [length (slots,),] state,
        params) -> (logits (slots,1,V), state') — THE decode step:
        every slot advances one position; inactive slots compute
        ignored rows (masked attention keeps them finite; a row of
        length 0 leaves its recurrent state as it was)."""
        import jax

        def fn(*args):
            *rows, state, param_vals = args
            with jax.named_scope("gen/decode_program"):
                if self._one_table:
                    logits, (state,) = self._eval_incremental(
                        rows, (state,), param_vals)
                    return logits, state
                return self._eval_incremental(rows, state, param_vals)

        return fn

    def _structs(self, bucket: Tuple):
        import jax
        kind, shp = bucket

        def sds(shape, dtype=np.float32):
            return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                        sharding=self._sharding)

        state = tuple(sds(shape, jax.numpy.dtype(t.dtype))
                      for t, shape in zip(self.state_spec,
                                          self._table_shapes))
        if self._one_table:
            state = state[0]
        if kind == "prefill":
            b, s = shp
            n = b
            rows = (sds((b, s)), sds((b,)))
        elif kind == "decode":
            (n,) = shp
            rows = (sds((n, 1)), sds((n,)))
        else:
            raise MXNetError(
                f"generate: unknown executable kind {kind!r}")
        if self._takes_length(kind):
            rows += (sds((n,)),)                  # length
        if kind == "prefill":
            rows += (sds((n,)),)                  # lane_idx
        return rows + (state,)

    def _entry(self, bucket: Tuple):
        """Load-or-compile one generation executable (exactly once,
        under ``_lock``) through the persistent cache — same contract
        as ``ModelRunner._entry``."""
        bucket = (bucket[0], tuple(bucket[1]))
        with self._lock:
            entry = self._entries.get(bucket)
            if entry is not None:
                return entry
            import jax
            if self._guards:
                self._churn.note_compile(bucket)
            kind = bucket[0]
            in_structs = self._structs(bucket)
            # the KV slot table is the LAST data operand — donated on
            # accelerator backends so every step recycles it in place
            kv_argnum = len(in_structs) - 1
            t0 = time.perf_counter()
            from mxtpu import analysis
            from ..kernels import kv_write, ssm_update
            compiled, source, ckey, cmeta = None, "cold", None, {}
            with self._region(obs.SPAN_COMPILE, entry=self._entry_label,
                              kind=kind, bucket=str(bucket[1])) as rg:
                if self._cache is not None:
                    ckey = self._cache_key(bucket)
                    compiled, cmeta = self._cache.load(ckey, with_meta=True)  # mxlint: sync-point — disk, pre-serving
                    if compiled is not None:
                        source = "disk"
                if compiled is None:
                    fn = self._prefill_pure() if kind == "prefill" \
                        else self._decode_pure()
                    # donation applied only where XLA honors it; on
                    # cpu it is a silent no-op, so skipping it keeps
                    # that backend's programs byte-identical
                    apply_donate = (self._donate and
                                    jax.default_backend() != "cpu")
                    jitted = jax.jit(
                        fn, donate_argnums=(kv_argnum,)
                        if apply_donate else ())
                    with kv_write.call_sites() as traced, \
                            ssm_update.call_sites() as updated:
                        lowered = jitted.lower(
                            *in_structs, self._param_structs)
                    compiled = lowered.compile(compiler_options=(
                        ssm_update.COMPILER_OPTIONS
                        if updated[0] and self._device.platform == "tpu"
                        else None))
                    cmeta = dict(analysis.audit_stamp(),
                                 kv_kernel_writes=traced[0],
                                 ssm_kernel_updates=updated[0])
                    analysis.maybe_audit(
                        compiled, label=f"GenerateRunner{bucket}")
                    if ckey is not None:
                        self._cache.store(ckey, compiled, meta=cmeta)
                elif analysis.needs_reaudit(cmeta):
                    analysis.maybe_audit(
                        compiled, label=f"GenerateRunner{bucket}")
                # the one-token writes of the KV table that this program
                # makes by the column-store kernel (0: the lanes' loop),
                # and the one-token updates of the ssm table it makes by
                # the state-update kernel (0: XLA's two fusions); a
                # program loaded from disk says what its writer traced
                kv_kernel_writes = int(cmeta.get("kv_kernel_writes", 0))
                ssm_kernel_updates = int(cmeta.get("ssm_kernel_updates", 0))
                rg.set(source=source, kv_kernel_writes=kv_kernel_writes,
                       ssm_kernel_updates=ssm_kernel_updates)
                temp_bytes = (analysis.mem_stats(compiled) or {}).get(
                    "temp_size_in_bytes")
                if temp_bytes is not None:
                    rg.set(temp_bytes=temp_bytes)
            self.compile_seconds[bucket] = time.perf_counter() - t0
            entry = {"compiled": compiled, "in_structs": in_structs,
                     "kv_kernel_writes": kv_kernel_writes,
                     "ssm_kernel_updates": ssm_kernel_updates}
            self._entries[bucket] = entry
            self._compile_sources[bucket] = source
            if self._obs:
                if temp_bytes is not None:
                    self._m_temp_bytes.labels(
                        kind=kind, bucket=str(bucket[1])).set(temp_bytes)
                if source == "cold":
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
        """Pre-build the ladder (or a subset) so no token pays a
        compile; returns per-entry build seconds."""
        with guards.no_implicit_transfers(self._guards):
            for bucket in (buckets if buckets is not None
                           else self.buckets()):
                self._entry(bucket)
        with self._lock:
            return dict(self.compile_seconds)

    # -- execution --------------------------------------------------------
    def new_cache(self):
        """Fresh zeroed slot tables on this runner's device, each in
        the dtype its spec states: one array for a 6-tuple ``kv_spec``,
        a tuple of arrays in the spec's order for a state spec."""
        import jax.numpy as jnp
        tables = tuple(jnp.zeros(shape, jnp.dtype(t.dtype),
                                 device=self._device)
                       for t, shape in zip(self.state_spec,
                                           self._table_shapes))
        if self._obs:
            for name, nbytes in self.held_bytes(tables).items():
                self._m_state_bytes.labels(table=name).set(nbytes)
        return tables[0] if self._one_table else tables

    def held_bytes(self, tables) -> Dict[str, int]:
        """Bytes the device holds for each of ``tables`` (as
        ``new_cache()`` made them), by the table's name: the device
        tiles an array's last two axes, so a table whose last axis is
        no whole number of tiles takes more than its elements do."""
        return {t.name: int(a.on_device_size_in_bytes())
                for t, a in zip(self.state_spec, tables)}

    def state_bytes(self) -> Dict[str, int]:
        """Bytes of each state table's elements as ``new_cache()``
        allocates it (scratch slot included), by the table's name."""
        import jax.numpy as jnp
        return {t.name: int(np.prod(shape, dtype=np.int64))
                * jnp.dtype(t.dtype).itemsize
                for t, shape in zip(self.state_spec, self._table_shapes)}

    def prefill(self, tokens: np.ndarray, step: np.ndarray,
                lane_idx: np.ndarray, kv, length=None
                ) -> Tuple["DeviceLogits", Any]:
        """One prefill dispatch on already-bucketed host arrays:
        ``tokens (b, s)`` / ``step (b,)`` / ``lane_idx (b,)`` must
        match a ladder rung exactly (the batcher pads); ``length
        (b,)`` is each row's number of valid tokens (all ``s`` if not
        given).  Returns what ``decode`` returns: (the logits (b, 1, V)
        of each row's last valid position as a :class:`DeviceLogits`,
        left on the device with each row's first maximum brought over,
        and the new device state); the passed state is consumed
        (donated on accelerator backends).  Whoever wants every
        position's logits has the graph itself."""
        b, s = tokens.shape
        if length is None:
            length = np.full((b,), s, np.float32)
        fresh = int(np.count_nonzero((length > 0) & (step == 0)))
        if self._obs:
            self._m_resets.inc(fresh)
        return self._call(
            obs.SPAN_PREFILL_CALL, ("prefill", (b, s)),
            self._rows("prefill", tokens, step, length) + (lane_idx,),
            kv,
            {"rows": b, "bucket": s, "tokens": int(np.sum(length)),
             "resets": fresh, "lane_bytes": b * self._lane_bytes})

    def decode(self, tokens: np.ndarray, step: np.ndarray, kv,
               length=None) -> Tuple["DeviceLogits", Any]:
        """THE decode step: ``tokens (slots, 1)`` / ``step (slots,)``
        advance every slot one position; ``length (slots,)`` is 1 for
        a lane that decodes and 0 for an idle one (all 1 if not
        given).  Returns (the logits (slots, 1, V) as a
        :class:`DeviceLogits`, new device state): the logits stay where
        they are, and each slot's first maximum, found by a second
        small executable, is all that crosses to the host — what a
        greedy lane needs; ``np.asarray`` of them brings the numbers."""
        if length is None:
            length = np.ones((self._slots,), np.float32)
        on = length > 0
        return self._call(obs.SPAN_DECODE, ("decode", (self._slots,)),
                          self._rows("decode", tokens, step, length), kv,
                          {"slots": self._slots, "active": int(on.sum()),
                           "context_tokens": int(step[on].sum())})

    @staticmethod
    def _first_maximum_of(entry, logits, *counts):
        """The executable that finds each row's first maximum of
        ``logits (rows, 1, V)`` on the device, ``(rows,)`` int32, with
        the graph's counts (if it has any) behind them in the same
        array, so that one fetch brings both; built at the entry's
        first run (a set-up's first run of the program, never a
        token's)."""
        import jax
        import jax.numpy as jnp
        fn = entry.get("first_maximum")
        if fn is None:
            def first(rows, *counts):
                at = jnp.argmax(rows[:, 0, :], axis=-1).astype(jnp.int32)
                return jnp.concatenate((at,) + counts) if counts else at

            fn = entry["first_maximum"] = jax.jit(first).lower(
                logits, *counts).compile()
        return fn

    def _call(self, name: str, bucket: Tuple,
              host_rows: Sequence[np.ndarray], kv,
              counts: Dict[str, int]) -> Tuple["DeviceLogits", Any]:
        """One executable call, in the region ``name`` with its three
        children: the host rows staged on the device, the call itself
        with the search for each row's first maximum behind it, and
        what comes back — those maxima, 4 bytes a row.
        The call writes a closing child only for a graph's late counts
        (``moe_experts_touched``)."""
        import jax
        tokens = counts.get("tokens", counts.get("active", 0))
        counts = dict(counts, **{n: tokens * by for n, by
                                 in self._token_counters.items()})
        # the host work between the children lies inside them: the
        # entry's lookup in the staging, the staged rows' release in the
        # dispatch, the graph's counts read in the fetch
        with self._region(name, **counts) as rg:
            with self._region(name + obs.SPAN_STAGE):
                entry = self._entry(bucket)
                staged = [
                    jax.device_put(np.asarray(a, np.float32),  # mxlint: sync-point — staging host rows for device_put
                                   self._device)
                    for a in host_rows]
                if self._guards:
                    self._churn.note_call()
            with self._region(name + obs.SPAN_DISPATCH), \
                    guards.no_implicit_transfers(self._guards):
                logits, kv, *counted = entry["compiled"](
                    *staged, kv, self._param_vals)
                first = self._first_maximum_of(entry, logits, *counted)(
                    logits, *counted)
                del staged, counted
            with self._region(name + obs.SPAN_FETCH):
                # mxlint: sync-point — deliberate D2H: token ids (and the graph's counts behind them)
                first = np.asarray(first)
                if self._m_counted:
                    rows = logits.shape[0]
                    counts.update(zip(self._device_counters,
                                      (int(c) for c in first[rows:])))
                    first = first[:rows]
                    if self._obs:
                        for n, m in self._m_counted.items():
                            m.inc(counts[n])
                out = DeviceLogits(logits, first)
            if self._m_counted:
                rg.set(**{n: counts[n] for n in self._device_counters})
        return out, kv

    # -- introspection / contracts ----------------------------------------
    def default_bucket(self, kind: str = "decode") -> Tuple:
        if kind == "decode":
            return ("decode", (self._slots,))
        return ("prefill", (self.batch_buckets[-1],
                            self.prompt_buckets[-1]))

    def program_artifact(self, bucket: Optional[Tuple] = None):
        """``(hlo_text, mem_stats)`` of one executable (decode step by
        default) — what tools/hlocheck pins the ``generate_decode``
        contract on."""
        from mxtpu import analysis
        if bucket is None:
            bucket = self.default_bucket()
        compiled = self._entry(bucket)["compiled"]
        return compiled.as_text(), analysis.mem_stats(compiled)

    def program_summary(self, bucket: Optional[Tuple] = None):
        from mxtpu import analysis
        text, mem = self.program_artifact(bucket)
        return analysis.summarize(text, mem)

    def memory_summary(self, buckets: Optional[Sequence[Tuple]] = None):
        """The sanctioned memory view (``mxtpu.analysis.memflow``) of
        this runner's ladder (decode step + largest prefill rung by
        default): per-program HBM decomposition with the KV slot
        table attributed, the kv-geometry oracle, and any memory
        hazard findings."""
        from mxtpu.analysis import memflow
        if buckets is None:
            buckets = [self.default_bucket("prefill"),
                       self.default_bucket("decode")]
        record = memflow.generate_record(self, buckets=buckets)
        budgets = memflow.load_budgets(
            memflow.REPO_ROOT / "contracts")
        return memflow.summary_view(record, budgets)

    def lowered_program_text(self, bucket: Optional[Tuple] = None
                             ) -> str:
        """PRE-optimization HLO of one generation program (lowers
        only, never compiles) — mxprec's ledger substrate."""
        from mxtpu import analysis
        if bucket is None:
            bucket = self.default_bucket()
        bucket = (bucket[0], tuple(bucket[1]))
        fn = self._prefill_pure() if bucket[0] == "prefill" \
            else self._decode_pure()
        return analysis.lowered_text(fn, *self._structs(bucket),
                                     self._param_structs)

    def num_compiled(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- fleet handoff -----------------------------------------------------
    def ladder_metadata(self) -> Dict[str, Any]:
        """What a draining worker hands its replacement: which
        generation executables were actually built and what each
        cost."""
        with self._lock:
            compiled = sorted(self._entries)
            secs = dict(self.compile_seconds)
        return {"max_lanes": self.max_lanes,
                "prompt_buckets": list(self.prompt_buckets),
                "compiled_buckets": [[k, list(s)] for k, s in compiled],
                "compile_seconds": {str(k): v for k, v in secs.items()},
                "weight_bytes": self.weight_bytes()}

    def warm_from(self, metadata: Dict[str, Any]) -> Dict[Tuple, float]:
        """Warm this (replacement) runner from a donor's
        :meth:`ladder_metadata`, restricted to this runner's own
        ladder."""
        own = set(self.buckets())
        donor = [(k, tuple(s))
                 for k, s in metadata.get("compiled_buckets", [])]
        return self.warmup([b for b in donor if b in own])

    def weight_buffers(self) -> Tuple:
        return self._param_vals

    def weight_bytes(self) -> int:
        return int(sum(v.nbytes for v in self._param_vals))


class _Lane:
    """One in-flight generation: the lane's cache frontier (tokens
    written so far) and the last sampled token (next decode input)."""

    __slots__ = ("req", "frontier", "last_token", "t_last")

    def __init__(self, req: GenerateRequest, frontier: int,
                 last_token: int, t_last: float):
        self.req = req
        self.frontier = frontier
        self.last_token = last_token
        self.t_last = t_last


class GenerateBatcher:
    """Continuous (in-flight) batching over a :class:`GenerateRunner`.

    Pure, clock-injected policy: ``submit()`` enqueues, ``step(now)``
    advances the whole slot table one decode step — admitting queued
    requests into freed lanes at the step boundary first (prompt-
    bucket-grouped prefill, first token sampled from the last valid
    prompt position), then ONE decode dispatch over all slots, then
    per-lane sampling, streaming, and eviction (EOS / max_tokens /
    KV capacity / deadline).  No wall time, no threads — fake-clock
    tests drive it deterministically; the server wraps it in a
    stepping thread.

    Lock order: ``_step_lock`` (one stepper at a time) -> ``_cond``
    (queue + lane table); executions run OUTSIDE ``_cond`` so submit
    never blocks on the device."""

    def __init__(self, runner: GenerateRunner, *,
                 max_queue: Optional[int] = None,
                 max_lanes: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 stats=None,
                 default_max_tokens: Optional[int] = None,
                 stream: Optional[bool] = None,
                 on_timeout: Optional[Callable[[int], None]] = None):
        self.runner = runner
        # operational width cap (MXTPU_GEN_MAX_LANES): the runner's
        # KV table is sized at export time; this narrows how many of
        # its lanes continuous batching may occupy at once without
        # re-exporting (the decode executable still spans all slots)
        self.max_lanes = max(1, min(
            runner.max_lanes,
            int(max_lanes if max_lanes is not None
                else knobs.get("MXTPU_GEN_MAX_LANES"))))
        self.max_queue = int(max_queue) if max_queue is not None \
            else 8 * runner.max_lanes
        self._clock = clock
        self._stats = stats
        self.default_max_tokens = int(
            default_max_tokens if default_max_tokens is not None
            else knobs.get("MXTPU_GEN_MAX_TOKENS"))
        self.stream = bool(knobs.get("MXTPU_GEN_STREAM")  # mxlint: disable=host-sync (knob bool, no device data)
                           if stream is None else stream)
        self._on_timeout = on_timeout
        self._step_lock = threading.Lock()
        self._cond = threading.Condition()
        self._queue: List[GenerateRequest] = []  # guarded-by: _cond
        # guarded-by: _cond
        self._lanes: List[Optional[_Lane]] = [None] * self.max_lanes
        self._closed = False  # guarded-by: _cond
        self._joins = 0       # guarded-by: _cond — lifetime lane claims
        self._steps = 0       # guarded-by: _cond — decode steps run
        # the slot table lives here; only the stepping thread touches
        # it (single stepper enforced by _step_lock)
        self._kv = None  # guarded-by: _step_lock
        self._step_no = 0  # guarded-by: _step_lock — step() calls
        # the operator's view of what the gen/* regions count
        self._obs = obs.enabled()
        self._region = obs.region_writer(self._obs)
        self._m_admitted = obs.counter(
            "mxtpu_gen_admitted_total",
            "Generation requests that claimed a lane (joined the "
            "running decode batch).")
        self._m_evicted = obs.counter(
            "mxtpu_gen_evicted_total",
            "Lanes freed by a deadline that expired mid-decode.")
        self._m_rung = obs.counter(
            "mxtpu_gen_prefill_rung_total",
            "Prefill groups by the ladder rung they ran on (rows = "
            "batch rung, bucket = prompt bucket).",
            labels=("rows", "bucket"))
        self._m_lanes = obs.gauge(
            "mxtpu_gen_lanes_active",
            "Lanes occupied at the last decode step.")

    # -- submit side ------------------------------------------------------
    def submit(self, prompt: Sequence[int], *,
               max_tokens: Optional[int] = None,
               eos_id: Optional[int] = None, top_k: int = 1,
               seed: int = 0, prefix: Sequence[int] = (),
               timeout_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               on_token: Optional[Callable[[int, int], None]] = None
               ) -> GenerateRequest:
        """Enqueue one generation; it joins the running decode batch
        at the next step boundary with a free lane.  ``prefix`` seeds
        a replay (already-streamed tokens — prefilled, not re-emitted).
        Raises :class:`ServerBusy` when the bounded queue is full."""
        now = self._clock()
        prompt = [int(t) for t in prompt]
        prefix = [int(t) for t in prefix]
        if not prompt:
            raise MXNetError("generate: empty prompt")
        need = len(prompt) + len(prefix)
        if need >= self.runner.max_len:
            raise MXNetError(
                f"generate: prompt+prefix ({need}) fills the KV "
                f"capacity ({self.runner.max_len}) — nothing left to "
                f"generate")
        mt = int(max_tokens if max_tokens is not None
                 else self.default_max_tokens)
        if mt <= len(prefix):
            raise MXNetError(
                f"generate: max_tokens {mt} already exhausted by the "
                f"replayed prefix ({len(prefix)} tokens)")
        req = GenerateRequest(
            prompt, max_tokens=mt, eos_id=eos_id, top_k=top_k,
            seed=seed, prefix=prefix, on_token=on_token,
            group=self.runner.prompt_bucket_for(need), t_submit=now,
            deadline=None if timeout_s is None else now + timeout_s,
            trace_id=trace_id)
        with self._cond:
            if self._closed:
                raise WorkerLost(
                    "generate: batcher is closed (worker shut down "
                    "or lost) — resubmit elsewhere")
            if len(self._queue) >= self.max_queue:
                raise ServerBusy(
                    f"generate: queue full ({self.max_queue} "
                    f"waiting); retry with backoff")
            self._queue.append(req)
            self._cond.notify()
        return req

    # -- accounting (what the router's admission control reads) ----------
    @property
    def depth(self) -> int:
        with self._cond:
            return len(self._queue)

    def free_lanes(self) -> int:
        with self._cond:
            return sum(1 for l in self._lanes if l is None)

    def active(self) -> Dict[int, GenerateRequest]:
        """Lane table snapshot: {lane index: request} — the lane-
        accounting surface the join-at-step-boundary tests assert
        on."""
        with self._cond:
            return {i: l.req for i, l in enumerate(self._lanes)
                    if l is not None}

    @property
    def joins(self) -> int:
        """Lifetime lane claims (a request joining the running batch
        bumps this exactly once)."""
        with self._cond:
            return self._joins

    @property
    def steps(self) -> int:
        with self._cond:
            return self._steps

    def oldest_waiting_age(self, now: Optional[float] = None
                           ) -> Optional[float]:
        with self._cond:
            if not self._queue:
                return None
            return (self._clock() if now is None else now) \
                - self._queue[0].t_submit

    # -- the step ---------------------------------------------------------
    def step(self, now: Optional[float] = None) -> Dict[str, int]:
        """Advance the whole batch one decode step; returns counters
        ``{"admitted", "active", "emitted", "finished", "queued"}``
        (``queued``: the queue's depth after admission).  The join
        point for queued requests AND the eviction point for finished/
        expired ones — continuous batching is exactly this loop."""
        with self._step_lock:
            self._step_no += 1
            with self._region(obs.SPAN_GEN_STEP, step=self._step_no,
                              max_lanes=self.max_lanes) as rg:
                # the thread's CPU time and collector pauses over the
                # step, read only while a trace is written
                timed = self._obs and obs.recording()
                if timed:
                    cpu0, (gc0, gcn0) = (time.thread_time_ns(),
                                         obs.gc_pauses())
                out = self._step_locked(
                    self._clock() if now is None else now)
                if timed:
                    gc1, gcn1 = obs.gc_pauses()
                    rg.set(cpu_us=(time.thread_time_ns() - cpu0) // 1000,
                           gc_us=(gc1 - gc0) // 1000, gc_n=gcn1 - gcn0)
                rg.set(**out)
            return out

    def _step_locked(self, now: float) -> Dict[str, int]:
        """The step proper, under ``_step_lock``."""
        # (req, token, stream index, is_first, seconds since the
        # request's previous emission) — fired outside all locks
        emissions: List[Tuple[GenerateRequest, int, int, bool,
                              float]] = []
        finished: List[GenerateRequest] = []
        # (req, final value): resolved AFTER _fire so the future's
        # done-callbacks (the fleet watcher) observe a fully
        # delivered stream — completing first would let a watcher
        # snapshot the ledger one token short of the final emission
        completions: List[Tuple[GenerateRequest, List[int]]] = []
        with self._region(obs.SPAN_GEN_ADMIT, step=self._step_no) as rg:
            with self._cond:
                if self._closed:
                    return {"admitted": 0, "active": 0, "emitted": 0,
                            "finished": 0, "queued": 0}
                self._expire_queued_locked(now)
                evicted = self._evict_deadlines_locked(now, finished)
                admitted = self._admit_locked(now)
                queued = len(self._queue)
            rg.set(admitted=len(admitted), evicted=evicted,
                   wait_us_sum=int(sum(now - r.t_submit
                                       for _, r in admitted) * 1e6))
        if self._obs:
            self._m_admitted.inc(len(admitted))
            self._m_evicted.inc(evicted)
        if admitted:
            self._prefill_locked(admitted, now, emissions, finished,
                                 completions)
        with self._region(obs.SPAN_DECODE_ROWS, step=self._step_no):
            with self._cond:
                active = [(i, l) for i, l in enumerate(self._lanes)
                          if l is not None]
            if self._obs:
                self._m_lanes.set(len(active))
            rows = self._decode_rows(active) if active else None
        if active:
            self._decode_locked(active, rows, emissions, finished,
                                completions)
        self._fire(emissions, self._step_no)
        if completions:
            with self._region(obs.SPAN_COMPLETE, step=self._step_no,
                              requests=len(completions)):
                for r, value in completions:
                    r._complete(value, now)
        return {"admitted": len(admitted), "active": len(active),
                "emitted": len(emissions),
                "finished": len(finished), "queued": queued}

    def _finish_reason(self, r: GenerateRequest, lane: _Lane
                       ) -> Optional[str]:
        """Evaluated right after each emission: EOS terminates the
        stream; ``max_tokens`` and KV capacity (no room left to write
        the token just emitted, so it cannot be extended) finish as
        "length"."""
        if r.eos_id is not None and lane.last_token == r.eos_id:
            return "eos"
        if r.emitted >= r.max_tokens:
            return "length"
        if lane.frontier >= self.runner.max_len:
            return "length"
        return None

    def _expire_queued_locked(self, now: float) -> None:
        expired = [r for r in self._queue
                   if r.deadline is not None and now > r.deadline]
        if not expired:
            return
        self._queue = [r for r in self._queue if r not in expired]
        if self._on_timeout is not None:
            self._on_timeout(len(expired))
        for r in expired:
            r._fail(RequestTimeout(
                "generate: deadline expired while queued"), now)

    def _evict_deadlines_locked(self, now: float,
                                finished: List[GenerateRequest]
                                ) -> int:
        """Mid-decode deadline eviction: an expired lane frees at the
        step boundary — its caller gets RequestTimeout, never a late
        stream.  Returns how many lanes it freed."""
        n_evicted = 0
        for i, lane in enumerate(self._lanes):
            if lane is None:
                continue
            r = lane.req
            if r.deadline is not None and now > r.deadline:
                self._lanes[i] = None
                n_evicted += 1
                r._fail(RequestTimeout(
                    f"generate: deadline expired mid-decode after "
                    f"{r.emitted} tokens"), now)
                finished.append(r)
        if n_evicted and self._on_timeout is not None:
            self._on_timeout(n_evicted)
        return n_evicted

    def _admit_locked(self, now: float
                      ) -> List[Tuple[int, GenerateRequest]]:
        """Claim freed lanes for the oldest queued requests — one
        prompt-bucket group per step (FIFO head priority, same rule as
        DynamicBatcher)."""
        free = [i for i, l in enumerate(self._lanes) if l is None]
        if not free or not self._queue:
            return []
        head = self._queue[0]
        take = [r for r in self._queue if r.group == head.group][
            :min(len(free), self.runner.batch_buckets[-1])]
        taken = set(map(id, take))
        self._queue = [r for r in self._queue if id(r) not in taken]
        pairs = []
        for r in take:
            lane = free.pop(0)
            r.t_dequeue = now
            self._joins += 1
            pairs.append((lane, r))
        return pairs

    def _prefill_locked(self, pairs: List[Tuple[int, GenerateRequest]],
                        now: float, emissions, finished,
                        completions) -> None:
        """Prefill the joiners' prompts (+ replay prefixes) into their
        claimed lanes and sample each one's first token.  Prompts
        longer than the bucket chunk at bucket width; batch padding
        rows target the scratch slot.  Device dispatches run outside
        ``_cond``; the lane-table commit reacquires it."""
        runner = self.runner
        s = pairs[0][1].group
        b = runner.batch_rung_for(len(pairs))
        full = [r.prompt + r.prefix for _, r in pairs]
        need = [len(f) for f in full]
        chunks = max(1, math.ceil(max(need) / s))
        if self._obs:
            self._m_rung.labels(rows=b, bucket=s).inc()
        # one event for the group; trace_of finds it under each of its
        # requests' ids, as it does every batch-level span
        with self._region(obs.SPAN_PREFILL, step=self._step_no,
                          rows=len(pairs), rung=b, bucket=s,
                          chunks=chunks,
                          trace_ids=[r.trace_id for _, r in pairs
                                     if r.trace_id is not None]):
            # the call of the chunk that holds each prompt's end kept
            # that position's row for its lane
            ends = [(n - 1) // s for n in need]
            kept: Dict[int, DeviceLogits] = {}
            for c in range(chunks):
                base = c * s
                with self._region(obs.SPAN_PREFILL_ROWS, chunk=c):
                    if self._kv is None:
                        # the slot table: the first call's input too
                        self._kv = runner.new_cache()
                    tokens = np.zeros((b, s), np.float32)
                    step = np.zeros((b,), np.float32)
                    length = np.zeros((b,), np.float32)
                    lidx = np.full((b,), runner.scratch_slot, np.float32)
                    for row, (lane, r) in enumerate(pairs):
                        if base >= need[row]:
                            continue  # this row finished in an earlier chunk
                        valid = min(s, need[row] - base)
                        tokens[row, :valid] = full[row][base:base + valid]
                        # step 0 starts the lane's recurrent state from
                        # zero; a later chunk carries it on; the padded
                        # positions past ``valid`` leave it as it is
                        step[row] = base
                        length[row] = valid
                        lidx[row] = lane
                logits, self._kv = runner.prefill(tokens, step, lidx,
                                                  self._kv, length)
                if c in ends:
                    kept[c] = logits
            with self._region(obs.SPAN_SAMPLE, step=self._step_no,
                              lanes=len(pairs)):
                # need[row]: the absolute position of the 1st new token
                firsts = [sample_token(kept[ends[row]][row, 0],
                                       position=need[row], seed=r.seed,
                                       top_k=r.top_k)
                          for row, (_, r) in enumerate(pairs)]
            with self._region(obs.SPAN_COMMIT, step=self._step_no), \
                    self._cond:
                if self._closed:
                    # the batcher died between admit and commit: these
                    # joiners were already off the queue, so close()
                    # could not see them — fail them here, with
                    # partial state (nothing emitted yet) for replay
                    err = WorkerLost("generate: batcher closed during "
                                     "prefill")
                    for _, r in pairs:
                        if not r.done():
                            r._fail(_lost_for(r, err), now)
                            finished.append(r)
                    return
                self._commit_first_tokens_locked(
                    pairs, need, firsts, emissions, finished,
                    completions)
                self._cond.notify_all()

    def _commit_first_tokens_locked(self, pairs, need, firsts,
                                    emissions, finished, completions
                                    ) -> None:
        """Seat each joiner's sampled first token in its lane (under
        ``_cond``).  The tokens are the joiners' only once seated: TTFT
        and the lanes' next gaps count from this reading of the
        batcher's clock, the lock's wait included, not from the step's
        start."""
        t_emit = self._clock()
        for row, (lane, r) in enumerate(pairs):
            tok = firsts[row]
            ln = _Lane(r, frontier=need[row], last_token=tok,
                       t_last=t_emit)
            r.tokens.append(tok)
            emissions.append((r, tok, len(r.prefix), True,
                              t_emit - r.t_submit))
            reason = self._finish_reason(r, ln)
            if reason is not None:
                r.finish_reason = reason
                completions.append(
                    (r, list(r.prefix) + list(r.tokens)))
                finished.append(r)
            else:
                self._lanes[lane] = ln

    def _decode_rows(self, active: List[Tuple[int, _Lane]]):
        """The decode's host rows: each lane's last token, written at
        its own frontier; idle slots decode nothing."""
        slots = self.runner.max_lanes + 1
        tokens = np.zeros((slots, 1), np.float32)
        steps = np.zeros((slots,), np.float32)
        length = np.zeros((slots,), np.float32)
        for i, lane in active:
            tokens[i, 0] = lane.last_token
            steps[i] = lane.frontier
            length[i] = 1
        return tokens, steps, length

    def _decode_locked(self, active: List[Tuple[int, _Lane]], rows,
                       emissions, finished, completions) -> None:
        """ONE decode dispatch over the whole slot table (``rows``:
        :meth:`_decode_rows`), then per-lane sampling, finish
        evaluation, and lane release."""
        # a greedy lane needs its slot's first maximum and nothing else
        # of 400 KB of logits: the runner finds it on the device, and a
        # host whose argmax runs at half speed in one process of two
        # (PERF.md, PR 32) no longer sets the step's length
        tokens, steps, length = rows
        logits, self._kv = self.runner.decode(tokens, steps, self._kv,
                                              length)
        done: List[Tuple[int, _Lane, str]] = []
        with self._region(obs.SPAN_SAMPLE, step=self._step_no,
                          lanes=len(active)):
            # the tokens exist only now, after the decode: gaps are read
            # off the batcher's clock here, not at the step's start
            t_emit = self._clock()
            for i, lane in active:
                r = lane.req
                lane.frontier += 1   # last_token is now in the cache
                dt = t_emit - lane.t_last
                pos = lane.frontier  # absolute position of the new token
                tok = sample_token(logits[i, 0], position=pos,
                                   seed=r.seed, top_k=r.top_k)
                lane.last_token = tok
                lane.t_last = t_emit
                r.tokens.append(tok)
                emissions.append((r, tok, r.emitted - 1, False, dt))
                reason = self._finish_reason(r, lane)
                if reason is not None:
                    done.append((i, lane, reason))
        with self._region(obs.SPAN_COMMIT, step=self._step_no), \
                self._cond:
            self._steps += 1
            for i, lane, reason in done:
                if self._lanes[i] is lane:
                    self._lanes[i] = None
                r = lane.req
                r.finish_reason = reason
                completions.append(
                    (r, list(r.prefix) + list(r.tokens)))
                finished.append(r)
            self._cond.notify_all()

    def _fire(self, emissions, step_no: int) -> None:
        """Stream callbacks + per-token stats/spans, OUTSIDE every
        lock (on_token is arbitrary user code)."""
        stats = self._stats
        active = profiler.is_active()
        with self._region(obs.SPAN_FIRE, step=step_no,
                          tokens=len(emissions)):
            for r, tok, index, is_first, dt in emissions:
                if stats is not None:
                    if is_first and not r.prefix:
                        # true time-to-first-token: submit -> first emit
                        stats.record_ttft(max(0.0, dt) * 1e6)
                    else:
                        stats.record_token(max(0.0, dt) * 1e6)
                if active and r.trace_id is not None:
                    obs.span(obs.SPAN_TOKEN, profiler._now_us(), 0.0,
                             trace_id=r.trace_id, cat="gen", token=tok,
                             index=index)
                if self.stream and r.on_token is not None:
                    try:
                        r.on_token(tok, index)
                    except Exception:  # noqa: BLE001 — a stream
                        pass  # consumer must never poison the loop

    # -- wind-down ---------------------------------------------------------
    def drain(self) -> bool:
        with self._cond:
            return not self._queue and all(
                l is None for l in self._lanes)

    def close(self, error: Optional[BaseException] = None) -> None:
        """Fail everything queued AND every in-flight lane with a
        :class:`WorkerLost` carrying each request's partial-generation
        state (``partial_state()``), so the fleet layer can replay the
        stream on a surviving worker.  No waiter is left hanging."""
        with self._cond:
            self._closed = True
            now = self._clock()
            err = error if error is not None else WorkerLost(
                "generate: batcher closed — worker lost before the "
                "stream completed")
            for r in self._queue:
                r._fail(_lost_for(r, err), now)
            self._queue.clear()
            for i, lane in enumerate(self._lanes):
                if lane is not None and not lane.req.done():
                    lane.req._fail(_lost_for(lane.req, err), now)
                self._lanes[i] = None
            self._cond.notify_all()
