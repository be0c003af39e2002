# mxlint: hot-path
"""``mxtpu.parallel`` — SPMD execution over a device mesh.

This is the TPU-native replacement for the reference's multi-device
machinery (``DataParallelExecutorGroup``†, KVStore ``device``/``nccl``
reduction, ``src/kvstore/comm.h``†): instead of per-device executors
plus explicit push/pull reductions, the WHOLE training step —
forward, backward, gradient all-reduce, optimizer update, running-stat
(aux) updates — is compiled into ONE XLA executable over a
``jax.sharding.Mesh``.  The batch is sharded over the ``dp`` axis;
parameters are replicated (or sharded per ``param_spec_fn`` for tensor
parallelism); XLA inserts the all-reduce/all-gather collectives and
schedules them over ICI (SURVEY.md §2.4, §5.8).

``KVStore`` (``mxtpu.kvstore``) remains as the API-parity facade; this
module is the mechanism.

ZeRO-1 (default on single-process ``dp`` meshes, ``zero=0`` turns it
off): instead of all-reducing full gradients and keeping
a replicated optimizer-state copy per device, the step reduce-scatters
each (shape, dtype) bucket's gradients, updates the 1/dp state shard
the device owns, and all-gathers the fresh params — the in-graph form
of the reference ``dist_sync`` server-side update
(``kvstore_dist_server.h``†), cutting optimizer HBM ~dp× at equal
total comm bytes (rs + ag == ar).
"""
from __future__ import annotations

import contextlib
import weakref

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import amp as _amp_mod
from ..base import MXNetError
from .. import cache as cache_mod
from .. import guards
from .. import obs
from .. import profiler as _prof
from .. import optimizer as opt_mod
from ..ndarray import random as _rnd
from ..ndarray.ndarray import NDArray
from ..ops.registry import get_op

__all__ = ["make_mesh", "shard_batch", "replicate", "TrainStep",
           "build_train_step", "plan_zero_buckets",
           "Mesh", "PartitionSpec", "P",
           "spmd_pipeline", "stack_stage_params", "PipelineTrainStep",
           "build_pipeline_train_step", "snapshot_params",
           "restore_params", "moe"]

PartitionSpec = P

from . import moe  # noqa: E402  (expert parallelism — the ep axis)


def snapshot_params(net):
    """Parameter values of ``net`` in collect_params() order (a list
    of numpy arrays).  Pairs with :func:`restore_params` to clone one
    net's init into another INSTANCE of the same architecture: block
    auto-naming gives every instance fresh prefixes, so values must be
    carried by position, not name — keeping that subtle assumption in
    one place (r4 review)."""
    # mxlint: sync-point — deliberate checkpoint-style host snapshot
    return [p.data().asnumpy() for p in net.collect_params().values()]


def restore_params(net, values):
    """Set ``net``'s parameters from a :func:`snapshot_params` list
    (same architecture, any instance).  The net must already be
    shape-initialised (run one forward first for deferred blocks)."""
    from .. import nd as _nd
    params = list(net.collect_params().values())
    if len(params) != len(values):
        raise ValueError(
            f"parameter count mismatch: net has {len(params)}, "
            f"snapshot has {len(values)} — not the same architecture")
    for p, v in zip(params, values):
        p.set_data(_nd.array(v))


def make_mesh(axes: Optional[Dict[str, int]] = None, devices=None) -> Mesh:
    """Build a named device mesh.  ``axes`` maps axis name → size, e.g.
    ``{'dp': 4, 'mp': 2}``; defaults to pure data parallelism over all
    visible devices."""
    devices = devices if devices is not None else jax.devices()
    if axes is None:
        axes = {"dp": len(devices)}
    names = tuple(axes)
    sizes = tuple(axes[n] for n in names)
    total = int(np.prod(sizes))
    if total > len(devices):
        raise MXNetError(
            f"mesh {axes} needs {total} devices, have {len(devices)}")
    dev_array = np.asarray(  # mxlint: disable=host-sync — device objects, not data
        devices[:total]).reshape(sizes)
    return Mesh(dev_array, names)


# weakref-keyed so entries die with their mesh (an id()-keyed dict
# could hand a stale flag to a new mesh reusing the address)
_MESH_MP_CACHE: "weakref.WeakKeyDictionary[Mesh, bool]" = \
    weakref.WeakKeyDictionary()


def _mesh_is_multiprocess(mesh: Mesh) -> bool:
    # O(devices) scan once per mesh, not per step (real multi-host
    # meshes have thousands of devices)
    try:
        flag = _MESH_MP_CACHE.get(mesh)
    except TypeError:  # unhashable/unweakrefable mesh variant
        me = jax.process_index()
        return any(d.process_index != me for d in mesh.devices.flat)
    if flag is None:
        me = jax.process_index()
        flag = any(d.process_index != me for d in mesh.devices.flat)
        _MESH_MP_CACHE[mesh] = flag
    return flag


def _device_put_global(raw, mesh: Mesh, spec) -> jax.Array:
    """Place a value onto a mesh sharding, including meshes that span
    processes.  Host values: every process passes the SAME full value
    (each takes only the rows its devices own), so single- and
    multi-process code paths stay identical — `jax.device_put` alone
    would demand cross-host transfers the CPU/gloo transport refuses.
    Already-global jax.Arrays are passed through (or resharded
    in-graph) rather than fetched to host."""
    sh = NamedSharding(mesh, spec)
    if not _mesh_is_multiprocess(mesh):
        return jax.device_put(raw, sh)
    if isinstance(raw, jax.Array):
        if raw.sharding == sh:
            return raw
        if not raw.is_fully_addressable:
            # global array with a different layout: reshard with an
            # in-graph identity (XLA inserts the collectives).  Cold
            # placement path: one compile per (shape, sharding) is the
            # cost of resharding, not churn.
            return jax.jit(  # mxlint: disable=retrace-inline-jit
                lambda a: a, out_shardings=sh)(raw)
    # mxlint: sync-point — global placement fetches host values once
    host = np.asarray(raw)
    idx_map = sh.addressable_devices_indices_map(host.shape)
    shards = [jax.device_put(host[idx], d)
              for d, idx in idx_map.items()]
    return jax.make_array_from_single_device_arrays(host.shape, sh,
                                                    shards)


def shard_batch(mesh: Mesh, arr, axis_name: str = "dp", batch_axis: int = 0):
    """Place an array batch-sharded over a mesh axis."""
    raw = arr.data if isinstance(arr, NDArray) else jnp.asarray(arr)
    spec = [None] * raw.ndim
    spec[batch_axis] = axis_name
    out = _device_put_global(raw, mesh, P(*spec))
    return NDArray(out, None, _placed=True) if isinstance(arr, NDArray) \
        else out


def replicate(mesh: Mesh, arr):
    """Place an array fully replicated over the mesh."""
    raw = arr.data if isinstance(arr, NDArray) else jnp.asarray(arr)
    out = _device_put_global(raw, mesh, P())
    return NDArray(out, None, _placed=True) if isinstance(arr, NDArray) \
        else out


# functional optimizer rules for the compiled step now live in
# ``mxtpu.optimizer.functional`` (the ZeRO-1 sharded path needs their
# stacked state-init shapes); the underscored aliases remain this
# package's internal import surface (pipeline.py).
from ..optimizer.functional import (adam_bias_correction as  # noqa: E402
                                    _adam_bias_correction,
                                    opt_rule as _opt_rule)


def plan_zero_buckets(sigs, dp: int, stack_axis_only: bool = False):
    """Plan the ZeRO-1 bucket layout for one optimizer step — pure
    geometry, no arrays (also the provenance of BASELINE.md's
    optimizer-memory table and the bench accounting).

    ``sigs`` is a list of ``(shape, dtype_str)`` per trainable
    parameter, in step order.  Parameters bucket by (shape, dtype)
    and each bucket picks ONE axis of its stacked ``(n,) + shape``
    array to shard over ``dp``: the axis minimizing relative
    zero-padding (ties prefer the stack axis, whose lr/wd bookkeeping
    is simplest).  Singleton
    buckets (n=1, e.g. an embedding table) would waste (dp-1)/dp of a
    full row if only the stack axis were allowed — axis choice is what
    makes the ≤ replicated/dp × 1.15 footprint hold.  LAMB buckets are
    pinned to the stack axis (``stack_axis_only=True``): its per-slice
    trust-ratio norms reduce within a bucket row, which stays
    device-local only when whole rows live on one device.

    Zero-padding is numerically inert for every supported rule: a
    padded region starts with w = g = state = 0 and every rule maps
    zeros to zeros (LAMB's padded rows see wnorm = rnorm = 0 → trust
    ratio 1.0, still updating 0 by 0).

    Returns a list of dicts: ``jidx`` (positions within the trainable
    tuple), ``shape``/``dtype`` (per param), ``stacked_shape``,
    ``axis`` (shard axis of the stacked array; 0 = stack axis),
    ``pad`` (zero rows appended on that axis), ``padded_shape``,
    ``rows`` (local extent per device), ``param_bytes`` (logical,
    unpadded) and ``padded_bytes``."""
    if dp < 1:
        raise MXNetError(f"plan_zero_buckets needs dp >= 1, got {dp}")
    by_sig: Dict[Tuple, List[int]] = {}
    for j, (shape, dt) in enumerate(sigs):
        by_sig.setdefault((tuple(shape), str(dt)), []).append(j)
    buckets = []
    for (shape, dt), js in by_sig.items():
        stacked_shape = (len(js),) + shape
        best = None
        cands = [0] if stack_axis_only else range(len(stacked_shape))
        for ax in cands:
            size = stacked_shape[ax]
            pad = (-size) % dp
            key = (pad / size, ax)
            if best is None or key < best[0]:
                best = (key, ax, pad)
        _, axis, pad = best
        padded = list(stacked_shape)
        padded[axis] += pad
        itemsize = jnp.dtype(dt).itemsize
        buckets.append({
            "jidx": js, "shape": shape, "dtype": dt,
            "stacked_shape": stacked_shape, "axis": axis, "pad": pad,
            "padded_shape": tuple(padded),
            "rows": padded[axis] // dp,
            "param_bytes": int(np.prod(stacked_shape, dtype=np.int64))
            * itemsize,
            "padded_bytes": int(np.prod(padded, dtype=np.int64))
            * itemsize,
        })
    return buckets


def _batch_spec(ndim: int, axis: int, name: str) -> P:
    """Spec of a rank-``ndim`` batch array sharded over mesh axis
    ``name`` on its ``axis`` (replicated where it has no such axis)."""
    return P(*[name if d == axis else None for d in range(ndim)])


# the two phases of every step body, by the names their operations
# carry in the program's op_name metadata (bucket packing and unpacking
# belong to the optimizer's)
_SCOPE_FWD_BWD = "train/forward_backward"
_SCOPE_OPTIMIZER = "train/optimizer"


def _mem_stats(compiled):
    """``memory_analysis()`` of a compiled program as a plain dict
    (None when the backend doesn't report) — delegates to the ONE
    memory analyzer, :func:`mxtpu.analysis.memflow.mem_stats`, which
    owns the ``hbm_peak`` = temp + argument convention."""
    from mxtpu.analysis import memflow
    return memflow.mem_stats(compiled)


class _Gspmd:
    """The exchange of an unsharded step: every array is global, so
    each piece is the identity and the gradient all-reduce stays
    implicit, GSPMD's to insert."""

    def wrap(self, step, x_raw, y_raw, n_extra):
        return step

    def shard_key(self, key_data):
        return key_data

    def reduce(self, loss, raw_aux):
        return loss, raw_aux

    def scatter(self, g, b):
        return g

    def mean(self, g):
        return g

    def own(self, v, b, axis):
        return v

    def agree(self, finite):
        return finite

    def gather(self, w2, b):
        return w2


class _Zero1(_Gspmd):
    """The ZeRO-1 exchange: the step is an explicit ``shard_map`` over
    ``dp_axis``, each bucket's gradients are reduce-scattered in and
    its updated weights all-gathered out.  GSPMD's
    ReduceScatterCreator pass is GPU/TPU only, so sharding constraints
    alone cannot guarantee the reduce-scatter on every backend — the
    explicit collectives make the comm layout part of the program,
    testable from the HLO on the CPU virtual mesh."""

    def __init__(self, mesh, dp_axis, batch_axis, state_specs):
        self.mesh, self.dp_axis = mesh, dp_axis
        self.dp = mesh.shape[dp_axis]
        self.batch_axis, self.state_specs = batch_axis, state_specs

    def wrap(self, step, x_raw, y_raw, n_extra):
        rep = (P(),) * n_extra
        in_specs = (P(), P(), self.state_specs, P(), P(), P()) + tuple(
            _batch_spec(v.ndim, self.batch_axis, self.dp_axis)
            for v in (x_raw, y_raw)) + rep
        out_specs = (P(), P(), self.state_specs, P()) + rep
        # check_vma=False: the checker can't infer that the tiled
        # all_gather output is replicated
        return jax.shard_map(step, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def shard_key(self, key_data):
        # decorrelate dropout across shards (the GSPMD path gets this
        # for free from its globally-sharded RNG)
        return jax.random.key_data(jax.random.fold_in(
            jax.random.wrap_key_data(key_data),
            lax.axis_index(self.dp_axis)))

    def reduce(self, loss, raw_aux):
        # loss_flat reduces over the LOCAL shard; equal shard sizes
        # make the mean of shard means the global mean
        return lax.psum(loss, self.dp_axis) / self.dp, tuple(
            lax.pmean(a, self.dp_axis)
            if jnp.issubdtype(a.dtype, jnp.inexact) else a
            for a in raw_aux)

    def scatter(self, g, b):
        # THE ZeRO exchange: reduce-scatter replaces the gradient
        # all-reduce; this device gets the summed rows it owns
        return lax.psum_scatter(g, self.dp_axis,
                                scatter_dimension=b["axis"], tiled=True)

    def mean(self, g):
        # the sum over shards becomes the mean that matches the
        # mean-of-shard-means loss
        return g / self.dp

    def own(self, v, b, axis):
        # rows [me*rows, (me+1)*rows) of the padded bucket
        return lax.dynamic_slice_in_dim(
            v, lax.axis_index(self.dp_axis) * b["rows"], b["rows"], axis)

    def agree(self, finite):
        return lax.psum((~finite).astype(jnp.int32), self.dp_axis) == 0

    def gather(self, w2, b):
        ax = b["axis"]
        w2 = lax.all_gather(w2, self.dp_axis, axis=ax, tiled=True)
        if b["pad"]:
            w2 = lax.slice_in_dim(w2, 0, b["stacked_shape"][ax], axis=ax)
        return w2


class TrainStep:
    """One fused XLA executable per (shape signature): fwd + bwd +
    collectives + optimizer + aux writeback.  Call with (x, y) batches;
    parameters update in place (rebound buffers).

    **ZeRO-1** (``zero``): on a single-process mesh whose ``dp_axis``
    has size > 1 (and no ``param_spec_fn``), the step defaults to
    ZeRO-1 sharded optimizer states: gradients are reduce-scattered
    per (shape, dtype) bucket (see :func:`plan_zero_buckets`), each
    device updates only the 1/dp state shard it owns, and the fresh
    params are all-gathered back to replicated — optimizer HBM drops
    ~dp× at the same total comm bytes as the all-reduce it replaces.
    ``zero=0`` restores the replicated GSPMD path; ``zero=1`` insists
    and raises where ZeRO can't apply.  The ZeRO step is an explicit ``shard_map`` over
    ``dp_axis``, with three contract changes vs the GSPMD path:

    * the batch dim must divide the dp size (error otherwise);
    * the loss must reduce as a mean over examples (the gluon losses
      do): the global loss is the mean of per-shard means.  BatchNorm
      accumulates per-shard batch statistics (averaged into the
      running stats — the reference's non-sync DDP behaviour) and
      dropout draws an independent stream per shard;
    * optimizer updates run bucket-stacked (the ZeRO exchange is per
      bucket and the state lives stacked); an unsharded step updates
      one parameter at a time and stacks nothing.

    ``save_states`` always writes the canonical per-parameter layout
    (gather-on-save), so checkpoints are interchangeable between ZeRO
    and replicated steps in both directions."""

    def __init__(self, net, loss_fn, optimizer, mesh: Optional[Mesh] = None,
                 dp_axis: str = "dp", batch_axis: int = 0,
                 param_spec_fn: Optional[Callable] = None, donate=True,
                 compute_dtype=None, cast_batch=True, zero=None,
                 cache: Any = "auto", amp=None):
        from ..gluon.block import _traced_forward
        self._traced_forward = _traced_forward
        self.net = net
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.batch_axis = batch_axis
        self.param_spec_fn = param_spec_fn
        self.donate = donate
        # mixed precision: forward/backward in compute_dtype (bf16 puts
        # the matmuls/convs on the MXU's fast path), master weights,
        # loss, and optimizer state stay f32 — the reference's
        # multi_precision=True AMP recipe, compiled into the one program.
        # cast_batch=False keeps the raw batch dtype — REQUIRED when x
        # carries integer ids in a float array (Embedding inputs):
        # bf16 can't represent ids > 256 exactly, so casting would
        # silently fetch wrong rows; the bf16 embedding table already
        # makes everything downstream compute in bf16.
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)
        self.cast_batch = cast_batch
        # policy-driven AMP (mxtpu.amp): params stored bf16 over f32
        # masters, contraction-only bf16 casts from the committed
        # policy, dynamic loss scaling.  MXTPU_AMP=0 forces this off
        # everywhere; the off path traces the exact pre-AMP program.
        self.amp = _amp_mod.resolve(amp)
        if self.amp and self.compute_dtype is not None:
            raise MXNetError(
                "amp and compute_dtype are two mixed-precision "
                "recipes — pass one (amp supersedes compute_dtype)")
        self._amp_state = None
        if self.amp:
            (self._amp_scaler, self._amp_init_scale,
             self._amp_window) = _amp_mod.scaler_config()
        else:
            self._amp_scaler = False
        self._compiled = {}
        self._params: Optional[List] = None
        self._t = 0
        self._last_mem: Optional[Dict[str, int]] = None
        self.zero = self._decide_zero(zero)
        # Guard rails (mxtpu.guards, MXTPU_GUARDS=1): enabled() is read
        # ONCE here so the disabled hot path costs a single cached-bool
        # test per step (bench.py asserts the zero-overhead contract).
        self._guards = guards.enabled()
        self._churn = guards.ChurnDetector(
            f"TrainStep[{type(net).__name__}]")
        # ISSUE 8: obs registry instruments — step wall time, compile
        # events, and the compiler-estimated FLOPs/step (the MFU
        # numerator, set by cost_analysis).  Same cached-bool contract
        # as _guards: MXTPU_OBS=0 costs one bool test per step, and the
        # step's four regions one call each of a writer that does
        # nothing (bound here, once; obs.region itself reads no knob).
        self._obs = obs.enabled()
        self._region = obs.region_writer(self._obs)
        _entry = self._entry_label = f"TrainStep[{type(net).__name__}]"
        self._m_step = obs.histogram(
            "mxtpu_train_step_seconds",
            "Host time of one TrainStep call from the executable's "
            "dispatch to the end of write-back: the enqueue, not the "
            "step's completion on the device (run_steps: per step of "
            "the scan).",
            labels=("entry",)).labels(entry=_entry)
        self._m_compile = obs.counter(
            "mxtpu_train_compile_total",
            "TrainStep executable builds (one per new signature).",
            labels=("entry",)).labels(entry=_entry)
        self._m_flops = obs.gauge(
            "mxtpu_train_flops_per_step",
            "XLA cost_analysis FLOPs of the one-step program "
            "(MFU numerator; 0 until cost_analysis runs).",
            labels=("entry",)).labels(entry=_entry)
        # ISSUE 13: persistent executable cache — the AOT build
        # becomes load-or-compile, with the cold-vs-disk split
        # labeled on the build-time histogram and disk hits counted
        # next to ChurnDetector's miss counter.
        self._cache = cache_mod.default_cache() if cache == "auto" \
            else cache
        _h = obs.histogram(
            "mxtpu_train_compile_seconds",
            "AOT TrainStep build wall time (source=cold: XLA "
            "compile; source=disk: verified load from the persistent "
            "cache).", labels=("entry", "source"))
        self._m_compile_s = {
            src: _h.labels(entry=_entry, source=src)
            for src in ("cold", "disk")}
        self._m_cache_hit = obs.counter(
            "mxtpu_compile_cache_hit_total",
            "In-process compile-cache misses served from the "
            "persistent disk cache instead of XLA.",
            labels=("entry",)).labels(entry=_entry)
        if self.amp:
            self._m_amp_scale = obs.gauge(
                "mxtpu_amp_loss_scale",
                "Current dynamic loss scale (1.0 when scaling is "
                "disabled via MXTPU_AMP_LOSS_SCALE=0).",
                labels=("entry",)).labels(entry=_entry)
            self._m_amp_skipped = obs.gauge(
                "mxtpu_amp_skipped_steps",
                "Optimizer steps skipped because non-finite gradients "
                "tripped the loss-scaler backoff.",
                labels=("entry",)).labels(entry=_entry)

    def _decide_zero(self, zero) -> bool:
        """Resolve the ZeRO-1 mode: ``zero=0/1`` decides where it is
        given, and the auto default is ON exactly when the mechanism
        applies — a single-process mesh with a >1-sized ``dp_axis``
        and no tensor-parallel ``param_spec_fn``."""
        if zero is not None and not zero:
            return False
        if self.mesh is None or self.dp_axis not in self.mesh.shape \
                or self.mesh.shape[self.dp_axis] <= 1:
            why = ("zero=1 needs a mesh whose dp axis "
                   f"({self.dp_axis!r}) has size > 1")
        elif self.param_spec_fn is not None:
            why = ("zero=1 does not compose with param_spec_fn "
                   "(tensor parallelism) yet — drop one of the two")
        elif _mesh_is_multiprocess(self.mesh):
            why = ("zero=1 needs a single-process mesh (multi-host "
                   "ZeRO is pending transport validation)")
        else:
            return True
        if zero:
            raise MXNetError(why)
        return False

    def _amp_extra(self) -> tuple:
        """Trailing loss-scaler argument for the step callables —
        empty when AMP (or scaling) is off, so the off path keeps the
        exact pre-AMP signature and traced program."""
        if self._amp_scaler and self._amp_state is not None:
            return (self._amp_state,)
        return ()

    # -- parameter bookkeeping -----------------------------------------
    def _collect(self, x):
        if self._params is None:
            import mxtpu.autograd as autograd
            if not all(p._data is not None
                       for p in self.net.collect_params().values()):
                with autograd.pause():
                    self.net(x)  # deferred shape inference
            allp = list(self.net.collect_params().values())
            self._params = allp
            self._train_idx = [i for i, p in enumerate(allp)
                               if p.grad_req != "null"]
            # Honour per-parameter lr_mult/wd_mult (Parameter attrs plus
            # any name-keyed overrides set on the optimizer) without
            # touching the optimizer's own param_dict/idx2name — those
            # may be indexed by a different ordering (e.g. a shared
            # gluon.Trainer instance).
            self._opt_init, self._opt_update = _opt_rule(self.optimizer)
            if self.amp:
                # fp32 masters by construction: trainable f32 params
                # are STORED bf16 from here on (halving param comm and
                # the all-gather under ZeRO-1), and the optimizer's
                # multi-precision rule — which seeds a master copy for
                # every sub-f32 weight — keeps the f32 truth in the
                # optimizer state.  Aux-named params (BN running
                # stats) are never trainable and stay f32.
                from ..symbol import _is_aux_name
                for i in self._train_idx:
                    p = allp[i]
                    v = p._data._data
                    if (v.dtype == jnp.float32
                            and not _is_aux_name(p.name)):
                        p._data._data = v.astype(jnp.bfloat16)
            if self.mesh is not None:
                for p in allp:
                    spec = None
                    if self.param_spec_fn is not None:
                        spec = self.param_spec_fn(p)
                    p._data._data = _device_put_global(
                        p._data._data, self.mesh,
                        spec if spec is not None else P())
            if self.zero:
                self._init_zero_state()
            else:
                self._opt_state = tuple(
                    self._opt_init(self._params[i]._data._data)
                    for i in self._train_idx)
                if self.mesh is not None:
                    self._opt_state = jax.tree_util.tree_map(
                        lambda v: _device_put_global(v, self.mesh, P()),
                        self._opt_state)
            if self._amp_scaler and self._amp_state is None:
                st = _amp_mod.scaler_init(self._amp_init_scale)
                if self.mesh is not None:
                    st = tuple(_device_put_global(v, self.mesh, P())
                               for v in st)
                self._amp_state = st

    def _init_zero_state(self):
        """ZeRO-1 state: one stacked, padded array per (shape, dtype)
        bucket, carried dp-sharded on the bucket's planned axis.
        ``out_shardings`` makes XLA materialize each device's slice
        directly — no transient replicated copy exists at any point."""
        mesh, dp_axis = self.mesh, self.dp_axis
        dp = mesh.shape[dp_axis]
        params = self._params
        sigs = [(params[i]._data._data.shape,
                 str(params[i]._data._data.dtype))
                for i in self._train_idx]
        lamb = isinstance(self.optimizer, opt_mod.LAMB)
        self._zero_dp = dp
        self._zero_buckets = plan_zero_buckets(sigs, dp,
                                               stack_axis_only=lamb)
        specs, shardings = [], []
        for b in self._zero_buckets:
            leaf_shapes = jax.eval_shape(
                lambda b=b: self._opt_init(
                    jnp.zeros(b["padded_shape"], b["dtype"]),
                    stacked=True))
            bspecs = []
            for leaf in leaf_shapes:
                # full-rank leaves shard on the planned axis; rank-1
                # per-row leaves (LAMB's t) ride the stack axis, which
                # is the planned axis whenever they exist
                s = [None] * len(leaf.shape)
                s[b["axis"] if b["axis"] < len(leaf.shape) else 0] = \
                    dp_axis
                bspecs.append(P(*s))
            specs.append(tuple(bspecs))
            shardings.append(tuple(NamedSharding(mesh, sp)
                                   for sp in bspecs))
        self._zero_state_specs = tuple(specs)
        self._zero_state_shardings = tuple(shardings)
        buckets = self._zero_buckets
        opt_init = self._opt_init

        def init_all(train_vals):
            # init from the REAL stacked+padded weights, not zeros:
            # the multi-precision rule seeds its f32 master copies
            # here, and a zero master would erase every bf16 param on
            # the first step.  For f32 params every supported rule's
            # state is zeros_like regardless of w, so this is
            # value-identical to the old zeros-based init.
            out = []
            for b in buckets:
                w_s = jnp.stack([train_vals[j] for j in b["jidx"]])
                if b["pad"]:
                    widths = [(0, 0)] * w_s.ndim
                    widths[b["axis"]] = (0, b["pad"])
                    w_s = jnp.pad(w_s, widths)
                out.append(opt_init(w_s, stacked=True))
            return tuple(out)

        train_vals = tuple(self._params[i]._data._data
                           for i in self._train_idx)
        # one setup-time compile per TrainStep, not a hot path
        self._opt_state = jax.jit(  # mxlint: disable=retrace-inline-jit
            init_all,
            out_shardings=self._zero_state_shardings)(train_vals)

    def _partition(self):
        """The partition decision: the step's buckets — each with
        ``jidx`` (positions in the trainable tuple), its shard
        ``axis``, ``pad`` and whether it updates ``stacked`` — and
        ``take``/``put``, which fetch a bucket's weights, gradients and
        optimizer state and put the updated ones back.  These two are
        the ONE place where the two state layouts meet, one case each:
        per-parameter tuples, handed through untouched (unsharded:
        every parameter is its own bucket, always), or the bucket's
        resident dp-sharded stack (ZeRO-1, ``_init_zero_state``).
        ``self.zero`` decides, nothing else."""
        if self.zero:
            buckets = [dict(b, stacked=True) for b in self._zero_buckets]
        else:
            # One parameter a bucket: parameters and state live
            # unstacked, so a (shape, dtype) stack would be built from
            # them and cut apart again every step — two more passes over
            # every weight, gradient and moment, a third of the
            # BERT-Large step on the chip (PERF.md §6, PR 31).  XLA
            # fuses each parameter's update chain by itself; only
            # ZeRO-1, whose state is resident stacked, stacks.
            buckets = [{"jidx": [j], "axis": None, "pad": 0,
                        "stacked": False}
                       for j in range(len(self._train_idx))]

        def take(k, b, train_vals, grads, opt_state):
            js = b["jidx"]
            if not b["stacked"]:
                return train_vals[js[0]], grads[js[0]], opt_state[js[0]]
            return (jnp.stack([train_vals[j] for j in js]),
                    jnp.stack([grads[j] for j in js]), opt_state[k])

        def put(k, b, w2, st2, new_vals, new_state):
            js = b["jidx"]
            if not b["stacked"]:
                new_vals[js[0]], new_state[js[0]] = w2, st2
                return
            for a, j in enumerate(js):
                new_vals[j] = w2[a]
            new_state[k] = st2
        return buckets, take, put

    def _exchange(self):
        """The exchange decision: what crosses replicas in a step."""
        if not self.zero:
            return _Gspmd()
        return _Zero1(self.mesh, self.dp_axis, self.batch_axis,
                      self._zero_state_specs)

    def _build(self, key, x_raw, y_raw):
        params = self._params
        train_idx = self._train_idx
        frozen_idx = [i for i in range(len(params)) if i not in
                      set(train_idx)]
        n_param = len(params)
        loss_fn = self.loss_fn
        net = self.net
        traced_forward = self._traced_forward
        aux_box: Dict[str, Any] = {}

        compute_dtype = self.compute_dtype
        cast_batch = self.cast_batch
        amp_on = self.amp

        def loss_flat(train_vals, frozen_vals, key_data, x, y):
            pvals: List[Any] = [None] * n_param
            for i, v in zip(train_idx, train_vals):
                pvals[i] = v
            for i, v in zip(frozen_idx, frozen_vals):
                pvals[i] = v
            if compute_dtype is not None:
                # BN running stats (aux-named params) stay f32: their
                # EMA updates are too small for a bf16 mantissa
                from ..symbol import _is_aux_name
                pvals = [v.astype(compute_dtype)
                         if v is not None
                         and not _is_aux_name(params[i].name)
                         and jnp.issubdtype(v.dtype, jnp.floating)
                         else v
                         for i, v in enumerate(pvals)]
                if cast_batch and jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(compute_dtype)
            elif amp_on:
                # AMP entry upcast: every float param re-enters the
                # graph in f32, so ONLY the policy's allow-listed
                # contractions ever see bf16 (via the autocast scope
                # below) and every accumulating reduce stays f32 —
                # zero dtype-flow hazards by construction.  XLA folds
                # the bf16→f32→bf16 convert pair at the weight→dot
                # edges, and the AD transpose of this upcast is what
                # hands back bf16 grads at the param boundary.
                pvals = [v.astype(jnp.float32)
                         if v is not None
                         and jnp.issubdtype(v.dtype, jnp.floating)
                         and v.dtype != jnp.float32
                         else v
                         for v in pvals]
            scope = _amp_mod.autocast() if amp_on \
                else contextlib.nullcontext()
            with scope:
                raw_outs, _, aux_params, raw_aux = traced_forward(
                    net, params, pvals, [NDArray(x, None, _placed=True)],
                    True, key_data)
                outs = [NDArray(r, None, _placed=True) for r in raw_outs]
                # Multi-output nets hand ALL outputs to the loss (a
                # custom loss_fn must unpack them) rather than silently
                # training only the first head.
                pred = outs[0] if len(outs) == 1 else outs
                l = loss_fn(pred, NDArray(y, None, _placed=True))
            raw_l = l.data if isinstance(l, NDArray) else l
            aux_box["aux_params"] = aux_params
            # loss and aux (running stats) leave the bf16 region in f32
            if compute_dtype is not None:
                raw_aux = [a.astype(jnp.float32)
                           if jnp.issubdtype(a.dtype, jnp.floating)
                           else a for a in raw_aux]
            return jnp.mean(raw_l.astype(jnp.float32)), tuple(raw_aux)

        # The step is one sequence whatever its options; three pieces,
        # each chosen here once from what the object already knows,
        # say what the variants differ in: the partition (buckets and
        # the state's layout), the exchange (what crosses replicas) and
        # the precision (what happens to a gradient before its update).
        buckets, take, put = self._partition()
        ex = self._exchange()
        opt_update = self._opt_update
        window = self._amp_window if self._amp_scaler else None

        def rates(b, ndim, *per_param):
            """lr and wd of a bucket's rows, broadcast over its rank
            ``ndim`` — the one place that does it."""
            if not b["stacked"]:
                return [v[b["jidx"][0]] for v in per_param]
            # mxlint: disable=host-sync — Python index lists
            idx = jnp.asarray(np.asarray(b["jidx"], np.int32))
            out = []
            for v in per_param:
                r = jnp.take(v, idx)
                if b["axis"] == 0:
                    # per-row rates follow the rows this device owns;
                    # an inner-axis shard sees every row
                    if b["pad"]:
                        r = jnp.pad(r, (0, b["pad"]))
                    r = ex.own(r, b, 0)
                out.append(r.reshape(r.shape + (1,) * (ndim - 1)))
            return out

        def apply_updates(train_vals, grads, opt_state, lrs, wds, scale):
            """Every bucket: exchange in, update, exchange out.
            Returns ``(new_vals, new_state, finite)``; ``finite`` is
            None where no loss scale asks for the test."""
            new_vals: List[Any] = [None] * len(train_vals)
            new_state: List[Any] = [None] * len(opt_state)

            def exchanged(k, b):
                w, g, st = take(k, b, train_vals, grads, opt_state)
                if b["pad"]:
                    widths = [(0, 0)] * w.ndim
                    widths[b["axis"]] = (0, b["pad"])
                    w, g = jnp.pad(w, widths), jnp.pad(g, widths)
                g = ex.scatter(g, b)
                if amp_on:
                    # grads reach the param edge in bf16 (AD transpose
                    # of the entry upcast; half the reduce-scatter
                    # bytes under ZeRO-1): unscale in f32, so the
                    # finite test and the optimizer see full range
                    g = g.astype(jnp.float32)
                g = ex.mean(g)
                if scale is not None:
                    g = g / scale
                return k, b, w, g, st

            todo = (exchanged(k, b) for k, b in enumerate(buckets))
            finite = None
            if scale is not None:
                # ONE finite consensus gates every update — all shards
                # must agree to skip, or padded-row mismatches would
                # desynchronize the replicated params — so every bucket
                # is exchanged before the first update; without a
                # scale the generator interleaves the two per bucket
                todo = list(todo)
                finite = ex.agree(_amp_mod.all_finite(
                    [g for _, _, _, g, _ in todo]))
            for k, b, w, g, st in todo:
                w_loc = ex.own(w, b, b["axis"])
                w2, st2 = opt_update(w_loc, g, st,
                                     *rates(b, w.ndim, lrs, wds),
                                     stacked=b["stacked"])
                if finite is not None:
                    # skipped step: keep params AND state, back off
                    w2, st2 = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(finite, n, o),
                        (w2, st2), (w_loc, st))
                put(k, b, ex.gather(w2, b), st2, new_vals, new_state)
            return tuple(new_vals), tuple(new_state), finite

        def step(train_vals, frozen_vals, opt_state, key_data, lrs, wds,
                 x, y, *extra):
            # ``extra`` is the loss scaler's state, or nothing
            # (_amp_extra): the off path keeps the pre-AMP signature
            scale = extra[0][0] if extra else None

            def objective(tv, fv, kd, xx, yy):
                l, aux = loss_flat(tv, fv, kd, xx, yy)
                return (l if scale is None
                        else l * scale.astype(l.dtype)), (l, aux)

            with jax.named_scope(_SCOPE_FWD_BWD):
                (_, (loss, raw_aux)), grads = jax.value_and_grad(
                    objective, has_aux=True)(
                        train_vals, frozen_vals, ex.shard_key(key_data),
                        x, y)
                loss, raw_aux = ex.reduce(loss, raw_aux)
            with jax.named_scope(_SCOPE_OPTIMIZER):
                new_vals, new_state, finite = apply_updates(
                    train_vals, grads, opt_state, lrs, wds, scale)
                if scale is not None:
                    extra = (_amp_mod.scaler_update(extra[0], finite,
                                                    window),)
            return (loss, new_vals, new_state, raw_aux) + extra

        step = ex.wrap(step, x_raw, y_raw, len(self._amp_extra()))
        train_vals, frozen_vals = self._vals(frozen_idx)
        zeros = jnp.zeros(len(train_idx), jnp.float32)
        step_args = (train_vals, frozen_vals, self._opt_state,
                     jax.random.key_data(key), zeros, zeros,
                     x_raw, y_raw) + self._amp_extra()
        donate = (0, 2) if self.donate else ()
        fitted = jax.jit(step, donate_argnums=donate)
        fn = fitted
        mem = None
        if self._aot:
            # AOT-compile now: the lowering trace doubles as the aux
            # discovery pass (no separate eval_shape), the first step
            # pays no tracing, and memory_analysis / cost_analysis /
            # hlo_text come for free afterwards.
            # ISSUE 13: load-or-compile through the persistent cache.
            # The lowering trace does double duty: it is the aux
            # discovery pass AND the cache fingerprint — the lowered
            # StableHLO text IS the traced program, so two nets with
            # identical container class and param signatures but
            # different computations (relu vs tanh, a loss built with
            # different flags, distinct lambdas) can never share a
            # key.  A verified disk hit skips only the XLA compile.
            t0 = _prof._now_us()
            # groups / stacked_groups: what the partition did
            with self._region(obs.SPAN_COMPILE, entry=self._entry_label,
                              kind="train", bucket=str(x_raw.shape),
                              groups=len(buckets),
                              stacked_groups=sum(
                                  b["stacked"] for b in buckets)) as rg:
                lowered = fitted.lower(*step_args)
                source, ckey, loaded, cmeta = "cold", None, None, {}
                if self._cache is not None:
                    ckey = self._train_cache_key(lowered, x_raw, y_raw)
                    loaded, cmeta = self._cache.load(ckey,
                                                     with_meta=True)
                if loaded is not None:
                    source = "disk"
                    fn = loaded
                else:
                    fn = lowered.compile()
                rg.set(source=source)
            mem = _mem_stats(fn)
            self._last_mem = mem
            from mxtpu import analysis
            if source == "cold":
                # audit (which may raise under MXTPU_HLO_AUDIT=2)
                # runs BEFORE the store: a failing program never
                # reaches disk
                analysis.maybe_audit(fn, label="TrainStep", mem=mem)
                if ckey is not None:
                    self._cache.store(ckey, fn,
                                      meta=analysis.audit_stamp())
            elif analysis.needs_reaudit(cmeta):
                # audit knobs are per-process: the entry's writer
                # audited less strictly than this process asks for,
                # so the reloaded program is re-audited here
                analysis.maybe_audit(fn, label="TrainStep", mem=mem)
            if self._obs:
                if source == "disk":
                    self._m_cache_hit.inc()
                self._m_compile_s[source].observe(
                    (_prof._now_us() - t0) / 1e6)
        else:
            # learn the aux structure without device work
            jax.eval_shape(step, *step_args)
        # aux (BN running stats) positions inside the frozen tuple, in
        # aux_params order, for the scanned multi-step path to thread
        # them through the carry (None if an aux is somehow trainable)
        id2pos = {id(params[i]): j for j, i in enumerate(frozen_idx)}
        aux_pos = [id2pos.get(id(p)) for p in aux_box["aux_params"]]
        return {"fn": fn, "raw_step": step,
                "aux_params": aux_box["aux_params"],
                "frozen_idx": frozen_idx, "aux_pos": aux_pos,
                "mem": mem,
                # what one call hands the executable (train/dispatch)
                "leaves": 5 + len(jax.tree_util.tree_leaves(
                    (train_vals, frozen_vals, self._opt_state,
                     self._amp_extra())))}

    # -- the hot call ----------------------------------------------------
    def _prep(self, x, y):
        """Collect params, place the batch on the mesh, and return
        ``(x_raw, y_raw, sig)`` — shared by __call__ and the
        introspection entry points."""
        # under a multi-process mesh, keep non-NDArray inputs as HOST
        # buffers: _device_put_global shards them directly, avoiding a
        # wasted H2D→D2H round trip through the default device
        mp = self.mesh is not None and _mesh_is_multiprocess(self.mesh)
        wrap = np.asarray if mp else jnp.asarray
        x_raw = x.data if isinstance(x, NDArray) else wrap(x)
        y_raw = y.data if isinstance(y, NDArray) else wrap(y)
        self._collect(x if isinstance(x, NDArray)
                      else NDArray(x_raw, None, _placed=True))
        x_raw, y_raw = self._place_batch(x_raw, y_raw, self.batch_axis,
                                         "batch")
        sig = (x_raw.shape, str(x_raw.dtype), y_raw.shape,
               str(y_raw.dtype))
        return x_raw, y_raw, sig

    def _place_batch(self, x_raw, y_raw, axis, what):
        """One call's batch on the mesh, sharded over ``dp_axis`` on
        ``axis`` — after the check ZeRO-1's ``shard_map`` needs."""
        if self.zero and x_raw.shape[axis] % self._zero_dp:
            raise MXNetError(
                f"ZeRO-1 shards the batch over dp={self._zero_dp}; "
                f"{what} dim {x_raw.shape[axis]} is not divisible "
                f"(pad the batch, or pass zero=0)")
        if self.mesh is None:
            return x_raw, y_raw
        return tuple(_device_put_global(
            v, self.mesh, _batch_spec(v.ndim, axis, self.dp_axis))
            for v in (x_raw, y_raw))

    def _vals(self, frozen_idx):
        """The parameters' current buffers as the step takes them:
        ``(train_vals, frozen_vals)``."""
        params = self._params
        return (tuple(params[i]._data._data for i in self._train_idx),
                tuple(params[i]._data._data for i in frozen_idx))

    @property
    def _aot(self) -> bool:
        """Whether programs are compiled ahead of their first call.
        Multi-process meshes keep the jit wrapper — its dispatch
        handles cross-host arrays.  So does tensor-parallel
        (param_spec_fn): GSPMD may return updated params with a
        compiler-chosen sharding that differs from the placement the
        program was lowered with, and AOT executables reject input
        shardings that drift between steps."""
        return self.param_spec_fn is None and (
            self.mesh is None or not _mesh_is_multiprocess(self.mesh))

    def _train_cache_key(self, lowered, x_raw, y_raw):
        """Persistent-cache key of the AOT one-step program (ISSUE
        13): the model component hashes the LOWERED StableHLO text —
        the traced computation itself, the same program-is-the-
        fingerprint rule ModelRunner applies to its symbol graph — so
        everything that shapes the compiled step (architecture and
        activations, loss flags/lambdas, optimizer rule and baked-in
        hyperparams, precision/donation, ZeRO layout) is fingerprinted
        by construction; class names and param signatures alone could
        alias two different programs.  Weight/optimizer VALUES enter
        the text only as shapes (they are runtime arguments), and
        debug locations stay off (``as_text()`` default) so the text
        is checkout-independent.  The environment components (jax
        version, backend, device kind and ids, contract hash, salt)
        are added by ``ExecutableCache.key``."""
        import hashlib
        prog = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()[:24]
        mesh = "none" if self.mesh is None else \
            str(sorted(self.mesh.shape.items()))
        shape = str(((tuple(x_raw.shape), str(x_raw.dtype)),
                     (tuple(y_raw.shape), str(y_raw.dtype))))
        # net/opt class names ride along as debuggable context in the
        # entry header (the program hash already subsumes them)
        devices = tuple(x_raw.devices()) if self.mesh is None \
            else tuple(self.mesh.devices.flat)
        return self._cache.key(
            model=prog, shape=shape, mesh=mesh, devices=devices,
            net=type(self.net).__name__,
            opt=type(self.optimizer).__name__)

    def _entry_for(self, x_raw, y_raw, sig, key):
        entry = self._compiled.get(sig)
        if entry is None:
            if self._guards:
                self._churn.note_compile(sig)
            if self._obs:
                self._m_compile.inc()
            entry = self._build(key, x_raw, y_raw)
            self._compiled[sig] = entry
        return entry

    def _commit_small(self, *vals):
        """AOT executables validate input shardings — commit the small
        per-step scalars (lr/wd vectors, RNG key data) to the mesh
        replicated layout (single-process meshes only; multi-process
        keeps the jit path whose dispatch handles placement)."""
        if self.mesh is None or _mesh_is_multiprocess(self.mesh):
            return vals
        rs = NamedSharding(self.mesh, P())
        return tuple(jax.device_put(v, rs) for v in vals)

    def __call__(self, x, y):
        with self._region(obs.SPAN_TRAIN_STEP, t=self._t + 1):
            with self._region(obs.SPAN_TRAIN_PREP):
                x_raw, y_raw, sig = self._prep(x, y)
                key = _rnd._next_key(None)
                entry = self._entry_for(x_raw, y_raw, sig, key)
                self._t += 1
                lrs, wds = self._lrs_wds()
                lrs, wds, kd = self._commit_small(
                    lrs, wds, jax.random.key_data(key))
                params = self._params
                train_vals, frozen_vals = self._vals(
                    entry["frozen_idx"])
            if self._guards:
                self._churn.note_call()
            t0 = _prof._now_us() if self._obs else 0.0
            with self._region(obs.SPAN_TRAIN_DISPATCH,
                              leaves=entry["leaves"]), \
                    guards.no_implicit_transfers(self._guards):
                out = entry["fn"](
                    train_vals, frozen_vals, self._opt_state,
                    kd, lrs, wds, x_raw, y_raw, *self._amp_extra())
            with self._region(obs.SPAN_TRAIN_WRITEBACK):
                loss, new_vals, new_state, raw_aux = out[:4]
                if self._amp_scaler:
                    self._amp_state = out[4]
                for i, v in zip(self._train_idx, new_vals):
                    params[i]._data._data = v
                self._opt_state = new_state
                for p, v in zip(entry["aux_params"], raw_aux):
                    p._data._data = v
            if self._obs:
                self._m_step.observe((_prof._now_us() - t0) / 1e6)
            return NDArray(loss, None, _placed=True)

    # -- bulked execution -------------------------------------------------
    def run_steps(self, x, y, steps: int, reuse_batch: bool = False):
        """Run ``steps`` optimizer steps in ONE compiled program via
        ``lax.scan`` over microbatches — the TPU-native form of the
        reference's bulked graph execution (``MXNET_EXEC_BULK_EXEC_
        TRAIN``†, ``src/executor/graph_executor.cc`` bulking): host
        dispatch cost is paid once per ``steps`` instead of per step.

        ``x``/``y`` carry ``steps`` microbatches stacked on the batch
        axis (leading dim ``steps * B``), or — with
        ``reuse_batch=True`` — ONE batch stepped ``steps`` times
        (benchmarking / steady-state measurement, where stacking real
        microbatches would waste HBM).  lr/wd schedules are sampled
        once per call (per-``steps`` granularity).  Returns the
        per-step losses as a ``(steps,)`` NDArray."""
        with self._region(obs.SPAN_TRAIN_STEP, t=self._t + steps,
                          steps=steps):
            with self._region(obs.SPAN_TRAIN_PREP):
                (entry, multi, train_vals, frozen_vals, keys, lrs, wds,
                 xs, ys) = self._scan_prep(x, y, steps, reuse_batch)
            if self._guards:
                self._churn.note_call()
            t0 = _prof._now_us() if self._obs else 0.0
            with self._region(obs.SPAN_TRAIN_DISPATCH,
                              leaves=entry["leaves"]), \
                    guards.no_implicit_transfers(self._guards):
                out = multi(
                    train_vals, frozen_vals, self._opt_state, keys,
                    lrs, wds, xs, ys, *self._amp_extra())
            with self._region(obs.SPAN_TRAIN_WRITEBACK):
                losses, tv, frozen, st = out[:4]
                if self._amp_scaler:
                    self._amp_state = out[4]
                params = self._params
                for i, v in zip(self._train_idx, tv):
                    params[i]._data._data = v
                for j, i in enumerate(entry["frozen_idx"]):
                    params[i]._data._data = frozen[j]
                self._opt_state = st
            if self._obs:
                # one sample of amortized per-step host time —
                # dispatch is paid once for the whole scan, which is
                # the point
                self._m_step.observe(
                    (_prof._now_us() - t0) / 1e6 / steps)
            return NDArray(losses, None, _placed=True)

    def _scan_prep(self, x, y, steps: int, reuse_batch: bool):
        """Everything of :meth:`run_steps` before the dispatch: the
        microbatches placed, the one-step entry and the scanned
        program built (once per signature), and the call's
        arguments."""
        if steps <= 0:
            raise MXNetError("run_steps needs steps >= 1")
        x_raw = x.data if isinstance(x, NDArray) else jnp.asarray(x)
        y_raw = y.data if isinstance(y, NDArray) else jnp.asarray(y)
        if self.batch_axis != 0:
            raise MXNetError("run_steps supports batch_axis=0")
        if reuse_batch:
            B = x_raw.shape[0]
            xs, ys = x_raw, y_raw
        else:
            if x_raw.shape[0] % steps:
                raise MXNetError(
                    f"leading dim {x_raw.shape[0]} not divisible into "
                    f"{steps} microbatches")
            B = x_raw.shape[0] // steps
            xs = x_raw.reshape((steps, B) + x_raw.shape[1:])
            ys = y_raw.reshape((steps, B) + y_raw.shape[1:]) \
                if y_raw.ndim else y_raw
        self._collect(NDArray(x_raw[:B], None, _placed=True))
        batch_dim = 0 if reuse_batch else 1
        xs, ys = self._place_batch(xs, ys, batch_dim, "microbatch")
        key = _rnd._next_key(None)
        one_shape = xs.shape[batch_dim:] if not reuse_batch else xs.shape
        y_one = ys.shape[batch_dim:] if not reuse_batch else ys.shape
        sig = (one_shape, str(xs.dtype), y_one, str(ys.dtype))
        entry = self._compiled.get(sig)
        if entry is None:
            entry = self._entry_for(
                xs if reuse_batch else xs[0],
                ys if reuse_batch or not ys.ndim else ys[0], sig, key)
        msig = ("multi", steps, reuse_batch) + sig
        self._t += steps
        lrs, wds = self._lrs_wds()
        train_vals, frozen_vals = self._vals(entry["frozen_idx"])
        keys = jax.vmap(jax.random.key_data)(
            jax.random.split(key, steps))
        lrs, wds, keys = self._commit_small(lrs, wds, keys)
        multi = self._compiled.get(msig)
        if multi is None:
            if self._guards:
                self._churn.note_compile(msig)
            if self._obs:
                self._m_compile.inc()
            raw_step = entry["raw_step"]
            aux_pos = entry["aux_pos"]

            def multi_fn(train_vals, frozen_vals, opt_state, key_data,
                         lrs, wds, xs, ys, *extra):
                def body(carry, inp):
                    tv, frozen, st, extra = carry
                    if reuse_batch:
                        (kd,) = inp
                        xb, yb = xs, ys
                    else:
                        xb, yb, kd = inp
                    loss, tv2, st2, raw_aux, *extra2 = raw_step(
                        tv, frozen, st, kd, lrs, wds, xb, yb, *extra)
                    frozen2 = list(frozen)
                    for pos, v in zip(aux_pos, raw_aux):
                        if pos is not None:
                            frozen2[pos] = v
                    return (tv2, tuple(frozen2), st2, tuple(extra2)), loss
                scanned = (key_data,) if reuse_batch else \
                    (xs, ys, key_data)
                (tv, frozen, st, extra), losses = lax.scan(
                    body, (train_vals, frozen_vals, opt_state, extra),
                    scanned)
                return (losses, tv, frozen, st) + extra

            donate = (0, 1, 2) if self.donate else ()
            multi = jax.jit(multi_fn, donate_argnums=donate)
            if self._aot:
                # AOT (as in _build): the scanned program's memory
                # stats are what bench.py's hbm_peak reports
                with self._region(obs.SPAN_COMPILE,
                                  entry=self._entry_label,
                                  kind="train_scan", bucket=str(msig),
                                  source="cold"):
                    multi = multi.lower(
                        train_vals, frozen_vals, self._opt_state,
                        keys, lrs, wds, xs, ys,
                        *self._amp_extra()).compile()
                self._last_mem = _mem_stats(multi)
                from mxtpu import analysis
                analysis.maybe_audit(multi, label="TrainStep.run_steps",
                                     mem=self._last_mem)
            self._compiled[msig] = multi
        return (entry, multi, train_vals, frozen_vals, keys, lrs, wds,
                xs, ys)

    # -- introspection ----------------------------------------------------
    def cost_analysis(self, x, y):
        """XLA ``cost_analysis`` of the ONE-STEP compiled program for
        this batch signature: {'flops', 'bytes accessed', ...} as
        reported by the backend.  This is the provenance of every
        MFU denominator in bench.py/BASELINE.md (fwd+bwd+optimizer,
        XLA's own count — not an analytic 6N estimate).  Note Pallas
        custom calls (flash attention, fused LN) hide their FLOPs from
        XLA, so on TPU the count is a floor; the CPU lowering runs the
        lax reference paths and counts everything.  Compiles the
        program if this signature has not stepped yet."""
        compiled = self._compiled_for(x, y)
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        ca = dict(ca)
        if self._obs and ca.get("flops"):
            # cost_analysis returns host floats — no device sync here
            self._m_flops.set(float(ca["flops"]))  # mxlint: sync-point
        return ca

    def _compiled_for(self, x, y):
        """The compiled one-step executable for this (x, y) signature
        (building it if needed).  On the AOT path this is the very
        executable the step runs; the multi-process jit path lowers a
        twin for inspection."""
        entry, args = self._step_args(x, y)
        fn = entry["fn"]
        if not hasattr(fn, "lower"):  # AOT: already a Compiled
            return fn
        return fn.lower(*args).compile()

    def _step_args(self, x, y):
        """The one-step entry for this (x, y) signature (built if
        needed) and the arguments a call would hand its program."""
        x_raw, y_raw, sig = self._prep(x, y)
        key = _rnd._next_key(None)
        entry = self._entry_for(x_raw, y_raw, sig, key)
        lrs, wds = self._lrs_wds()
        return entry, self._vals(entry["frozen_idx"]) + (
            self._opt_state, jax.random.key_data(key), lrs, wds,
            x_raw, y_raw) + self._amp_extra()

    def memory_analysis(self, x, y):
        """Per-device memory footprint of the one-step compiled
        program for this batch signature: argument/output/temp/alias
        bytes from XLA's ``memory_analysis()``, plus ``hbm_peak``
        (temp + argument) and ``opt_state_bytes`` (bytes of optimizer
        state resident per device — under ZeRO-1, only the local
        shard).  Compiles the program if this signature has not
        stepped yet."""
        compiled = self._compiled_for(x, y)
        mem = dict(_mem_stats(compiled) or {})
        mem["opt_state_bytes"] = self.opt_state_bytes()
        return mem

    def memory_summary(self, x, y):
        """The sanctioned memory view (``mxtpu.analysis.memflow``) of
        the one-step program for this batch signature: peak HBM per
        device decomposed into params / optimizer state /
        activations+temps / collectives scratch / donated bytes, the
        ZeRO shard oracle when a dp>1 mesh is active, and any memory
        hazard findings — what tests and operators read instead of
        raw ``memory_analysis()`` grepping (mxlint ``mem-hygiene``)."""
        from mxtpu.analysis import memflow
        record = memflow.train_step_record(self, x, y)
        budgets = memflow.load_budgets(
            memflow.REPO_ROOT / "contracts")
        return memflow.summary_view(record, budgets)

    def hlo_text(self, x, y):
        """Compiled HLO of the one-step program for this batch
        signature.  Tests should prefer :meth:`program_summary` —
        mxlint's ``hlo-raw-assert`` rule bans regexing this text in
        ``tests/``."""
        return self._compiled_for(x, y).as_text()

    def lowered_hlo_text(self, x, y):
        """PRE-optimization HLO (with source metadata) of the
        one-step program — the dtype-flow substrate ``python -m
        tools.mxprec`` analyzes: every cast is still where the model
        code put it, before backend float normalization rewrites
        sub-f32 math."""
        from mxtpu import analysis
        entry, args = self._step_args(x, y)
        return analysis.lowered_text(entry["raw_step"], *args)

    def param_sigs(self, x=None, y=None):
        """``(name, shape, dtype)`` per trainable parameter, in step
        order — what mxprec's ``master-weight`` rule audits against
        the optimizer's functional rule.  Pass a batch to trigger
        collection if no step has run yet."""
        if self._params is None:
            if x is None:
                raise MXNetError(
                    "param_sigs before parameter collection — run a "
                    "step or pass a batch")
            self._prep(x, y if y is not None else x)
        return [(self._params[i].name,
                 tuple(self._params[i]._data._data.shape),
                 str(self._params[i]._data._data.dtype))
                for i in self._train_idx]

    def program_summary(self, x, y):
        """Contract-shaped static summary (``mxtpu.analysis``) of the
        one-step compiled program for this batch signature:
        collective inventory, custom-call brackets, dtype policy,
        fusion/memory budgets, host transfers.  What the comm-layout
        regression tests assert on (reduce-scatter/all-gather under
        ZeRO-1, all-reduce on the replicated path) instead of
        grepping ``hlo_text``."""
        from mxtpu import analysis
        compiled = self._compiled_for(x, y)
        return analysis.summarize(compiled.as_text(),
                                  _mem_stats(compiled))

    def last_memory_analysis(self):
        """Memory stats of the most recently compiled program (the
        one-step executable or the ``run_steps`` scan program) as a
        dict with ``hbm_peak`` = temp + argument bytes; None if
        nothing compiled yet or the backend doesn't report."""
        return self._last_mem

    def opt_state_bytes(self) -> int:
        """Optimizer-state bytes resident PER DEVICE.  Replicated
        states count in full; ZeRO-1 sharded states count only the
        local shard — the dp× saving this mode exists for."""
        if self._params is None:
            raise MXNetError(
                "opt_state_bytes before parameter collection — run a "
                "step (or _collect) first")
        from mxtpu.analysis import memflow
        return memflow.opt_state_leaf_bytes(self._opt_state)

    # -- checkpoint/resume (SURVEY §5.4: preemption-safe from day one) --
    def _canonical_state(self):
        """Optimizer state in the canonical per-parameter layout
        (train-idx order, LAMB ``t`` a scalar per param).  The
        replicated path already stores this; ZeRO-1 gathers its
        bucketed shards and strips the padding — so checkpoints are
        interchangeable between zero and replicated steps in both
        directions."""
        if not self.zero:
            return self._opt_state
        per_param: List[Any] = [None] * len(self._train_idx)
        for b, st in zip(self._zero_buckets, self._opt_state):
            js, ax = b["jidx"], b["axis"]
            leaves = []
            for leaf in st:
                # mxlint: sync-point — checkpoint save gathers shards
                a = np.asarray(leaf)
                axk = ax if a.ndim == len(b["padded_shape"]) else 0
                orig = b["stacked_shape"][axk]
                if a.shape[axk] != orig:
                    sl = [slice(None)] * a.ndim
                    sl[axk] = slice(0, orig)
                    a = a[tuple(sl)]
                leaves.append(a)
            for pos, j in enumerate(js):
                per_param[j] = tuple(leaf[pos] for leaf in leaves)
        return tuple(per_param)

    def _state_from_canonical(self, loaded):
        """Restack a canonical per-parameter state into ZeRO-1's
        padded bucket layout, placed shard-per-device."""
        new_state = []
        for b, shardings in zip(self._zero_buckets,
                                self._zero_state_shardings):
            js, ax = b["jidx"], b["axis"]
            n_leaves = len(loaded[js[0]])
            leaves = []
            for k in range(n_leaves):
                # mxlint: sync-point — checkpoint load stages host data
                stk = np.stack([np.asarray(loaded[j][k]) for j in js])
                axk = ax if stk.ndim == len(b["padded_shape"]) else 0
                tgt = b["padded_shape"][axk]
                if stk.shape[axk] != tgt:
                    widths = [(0, 0)] * stk.ndim
                    widths[axk] = (0, tgt - stk.shape[axk])
                    stk = np.pad(stk, widths)
                leaves.append(jax.device_put(jnp.asarray(stk),
                                             shardings[k]))
            new_state.append(tuple(leaves))
        return tuple(new_state)

    def save_states(self, fname: str) -> None:
        """Serialize optimizer state + step counter.  Pair with
        ``net.save_parameters`` for a full resumable checkpoint.
        Always writes the canonical per-parameter layout
        (gather-on-save under ZeRO-1)."""
        import pickle
        if self._params is None:
            raise MXNetError("nothing to save: step never ran")
        state_np = jax.tree_util.tree_map(np.asarray,
                                          self._canonical_state())
        blob = {"t": self._t, "opt_state": state_np}
        if self._amp_scaler and self._amp_state is not None:
            # checkpoint save reads the scaler scalars
            blob["amp"] = {
                "scale": float(np.asarray(self._amp_state[0])),  # mxlint: sync-point
                "good_steps": int(np.asarray(self._amp_state[1])),  # mxlint: sync-point
                "skipped_steps": int(np.asarray(self._amp_state[2]))}  # mxlint: sync-point
        with open(fname, "wb") as f:
            pickle.dump(blob, f)

    def load_states(self, fname: str, x_example=None) -> None:
        """Restore optimizer state; the step counter resumes bias
        correction / schedules where they left off.  Checkpoints are
        canonical per-parameter (see ``save_states``), so a ZeRO-1
        step reshards on load and a replicated step loads a
        ZeRO-written file unchanged."""
        import pickle
        with open(fname, "rb") as f:
            data = pickle.load(f)  # mxlint: disable=raw-deserialize (optimizer-state checkpoint: own save_states framing, arrays not executables)
        if self._params is None:
            if x_example is None:
                raise MXNetError(
                    "load_states before any step: pass x_example so "
                    "parameter collection can run")
            self._collect(x_example if isinstance(x_example, NDArray)
                          else NDArray(jnp.asarray(x_example), None,
                                       _placed=True))
        loaded = data["opt_state"]
        cur = jax.tree_util.tree_structure(tuple(
            jax.eval_shape(
                self._opt_init,
                jax.ShapeDtypeStruct(
                    self._params[i]._data._data.shape,
                    self._params[i]._data._data.dtype))
            for i in self._train_idx))
        got = jax.tree_util.tree_structure(loaded)
        if cur != got:
            raise MXNetError(
                f"optimizer state structure mismatch: {got} vs {cur}")
        self._t = data["t"]
        if self._amp_scaler and "amp" in data:
            # loss-scale state rides the checkpoint: a resumed run
            # neither re-warms the scale from init nor forgets its
            # skipped-step accounting (absent in pre-AMP files → the
            # fresh scaler_init from _collect stands)
            st = (jnp.asarray(data["amp"]["scale"], jnp.float32),
                  jnp.asarray(data["amp"]["good_steps"], jnp.int32),
                  jnp.asarray(data["amp"]["skipped_steps"], jnp.int32))
            if self.mesh is not None:
                st = tuple(_device_put_global(v, self.mesh, P())
                           for v in st)
            self._amp_state = st
        if self.zero:
            self._opt_state = self._state_from_canonical(loaded)
            return
        loaded = jax.tree_util.tree_map(jnp.asarray, loaded)
        if self.mesh is not None:
            loaded = jax.tree_util.tree_map(
                lambda v: _device_put_global(v, self.mesh, P()),
                loaded)
        self._opt_state = loaded

    def amp_stats(self):
        """Host-readable loss-scaler state — ``{'loss_scale',
        'good_steps', 'skipped_steps'}`` — and the obs gauge sync
        point (``mxtpu_amp_loss_scale``, ``mxtpu_amp_skipped_steps``).
        None when AMP is off; static 1.0/0/0 when scaling is disabled
        (``MXTPU_AMP_LOSS_SCALE=0``)."""
        if not self.amp:
            return None
        if not self._amp_scaler or self._amp_state is None:
            stats = {"loss_scale": 1.0, "good_steps": 0,
                     "skipped_steps": 0}
        else:
            # explicit introspection read
            stats = {
                "loss_scale": float(np.asarray(self._amp_state[0])),  # mxlint: sync-point
                "good_steps": int(np.asarray(self._amp_state[1])),  # mxlint: sync-point
                "skipped_steps": int(np.asarray(self._amp_state[2]))}  # mxlint: sync-point
        if self._obs:
            self._m_amp_scale.set(stats["loss_scale"])
            self._m_amp_skipped.set(stats["skipped_steps"])
        return stats

    def _lrs_wds(self):
        """Per-parameter (lr, wd) vectors for this step — two traced
        array args (one transfer each), so scheduler/mult changes never
        trigger a recompile.  The raw ``adam_update`` op does not
        bias-correct, so the correction is folded into the lr here
        (matches the eager ``Adam.update``)."""
        opt = self.optimizer
        opt.num_update = self._t
        base_lr = opt.learning_rate
        bias = _adam_bias_correction(opt, self._t)
        # Mults are read live (not cached at setup) so mid-training
        # changes to Parameter.lr_mult/wd_mult or optimizer.set_lr_mult
        # take effect on the next step — matching the eager Trainer.
        allp = self._params
        lr_mults = np.asarray(  # mxlint: disable=host-sync — Python floats
            [allp[i].lr_mult * opt.lr_mult.get(allp[i].name, 1.0)
             for i in self._train_idx], np.float32)
        wd_mults = np.asarray(  # mxlint: disable=host-sync — Python floats
            [allp[i].wd_mult * opt.wd_mult.get(allp[i].name, 1.0)
             for i in self._train_idx], np.float32)
        lrs = jnp.asarray(base_lr * bias * lr_mults)
        wds = jnp.asarray(opt.wd * wd_mults)
        return lrs, wds


def build_train_step(net, loss_fn, optimizer="sgd", optimizer_params=None,
                     mesh: Optional[Mesh] = None, dp_axis: str = "dp",
                     batch_axis: int = 0, param_spec_fn=None,
                     donate: bool = True, compute_dtype=None,
                     cast_batch: bool = True, zero=None,
                     cache: Any = "auto", amp=None) -> TrainStep:
    """Compile net+loss+optimizer into a single SPMD train step.

    ``mesh=None`` → single-device executable (still one fused program).
    With a mesh, batches shard over ``dp_axis`` and XLA inserts the
    gradient all-reduce; ``param_spec_fn(param) -> PartitionSpec`` adds
    tensor-parallel sharding.  On single-process dp meshes the step
    defaults to ZeRO-1 sharded optimizer states (reduce-scatter +
    all-gather instead of all-reduce; see :class:`TrainStep`) —
    ``zero=0`` restores the replicated path, ``zero=1`` insists.

    ``amp=1`` turns on policy-driven mixed precision (``mxtpu.amp``):
    bf16 parameter storage over f32 master weights, bf16 casts on the
    allow-listed contractions only (f32 accumulation everywhere),
    dynamic loss scaling, and — under ZeRO-1 — a bf16 reduce-scatter
    at half the f32 comm bytes.  ``MXTPU_AMP=0`` kills it globally,
    ``MXTPU_AMP=1`` enables it globally; ``amp=None`` defers to the
    environment."""
    if not isinstance(optimizer, opt_mod.Optimizer):
        optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
    return TrainStep(net, loss_fn, optimizer, mesh=mesh, dp_axis=dp_axis,
                     batch_axis=batch_axis, param_spec_fn=param_spec_fn,
                     donate=donate, compute_dtype=compute_dtype,
                     cast_batch=cast_batch, zero=zero, cache=cache,
                     amp=amp)


from .pipeline import (spmd_pipeline, stack_stage_params,  # noqa: E402
                       PipelineTrainStep, build_pipeline_train_step)
