"""Pipeline parallelism — GPipe microbatch schedule over a mesh axis.

The reference era expressed model parallelism as manual per-device
layer placement (``mx.AttrScope(ctx_group=...)`` + ``group2ctx`` in
bind); there is no pipelined schedule in the 2018 codebase at all.
This module supplies the modern capability TPU-natively: the layer
stack is sharded over a ``pp`` mesh axis (each device holds a
contiguous stage of layers), the batch is split into microbatches, and
activations flow stage-to-stage via ``lax.ppermute`` — XLA lowers the
rotation to neighbour-to-neighbour collective-permutes over ICI.

The schedule is written as ONE ``lax.scan`` over
``n_microbatches + n_stages - 1`` ticks inside ``shard_map``, so both
the forward and (via reverse-mode AD through the scan) the backward
pipeline compile into a single SPMD program.  Bubble fraction is the
GPipe ``(S-1)/(M+S-1)``; raise ``n_microbatches`` to amortise.

Composes with data parallelism: run over a ``{'pp': S, 'dp': D}`` mesh
and pass ``batch_spec=P('dp')`` — gradient all-reduce over ``dp`` is
inserted by XLA as usual.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["spmd_pipeline", "stack_stage_params", "PipelineTrainStep",
           "build_pipeline_train_step"]


def spmd_pipeline(stage_fn: Callable, stage_params: Any, x: jax.Array,
                  *, mesh: Mesh, axis: str = "pp",
                  n_microbatches: int = 4,
                  batch_spec: Optional[P] = None,
                  key: Optional[jax.Array] = None) -> jax.Array:
    """Apply a homogeneous layer pipeline to ``x`` with GPipe scheduling.

    ``stage_params``: pytree whose leaves have leading dim ``L`` (total
    layers), sharded over ``mesh[axis]`` so each of the ``S`` stages
    holds ``L/S`` layers.  ``stage_fn(local_params, x[, key])`` applies
    one stage's layers to a microbatch activation and must preserve its
    shape (the homogeneous-stack contract — exactly the transformer
    case).  ``x``: (B, ...) with ``B % n_microbatches == 0``.

    ``batch_spec``: PartitionSpec for the per-microbatch activation
    dims (e.g. ``P('dp')`` to keep the batch dim sharded over a data-
    parallel axis).  ``key``: optional uint32 key-data; when given,
    ``stage_fn`` receives a per-(microbatch, stage) folded key for
    dropout.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    if B % n_microbatches:
        raise MXNetError(f"batch {B} not divisible by "
                         f"n_microbatches {n_microbatches}")
    mb = B // n_microbatches
    x_mb = x.reshape((n_microbatches, mb) + x.shape[1:])
    n_ticks = n_microbatches + S - 1
    with_key = key is not None

    def local_fn(params_loc, x_all, key_data):
        stage = lax.axis_index(axis)
        perm = [(j, (j + 1) % S) for j in range(S)]
        state0 = jnp.zeros(x_all.shape[1:], x_all.dtype)

        def tick(state, t):
            # stage 0 ingests a fresh microbatch; later stages consume
            # what the ring delivered last tick
            inp = lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, n_microbatches - 1), 0,
                keepdims=False)
            state = jnp.where(stage == 0, inp, state)
            if with_key:
                mb_idx = jnp.clip(t - stage, 0, n_microbatches - 1)
                k = jax.random.fold_in(jax.random.fold_in(
                    jax.random.wrap_key_data(key_data), mb_idx), stage)
                out = stage_fn(params_loc, state, jax.random.key_data(k))
            else:
                out = stage_fn(params_loc, state)
            return lax.ppermute(out, axis, perm), out

        _, outs = lax.scan(tick, state0, jnp.arange(n_ticks))
        # on the last stage, tick (S-1)+m emitted microbatch m's result
        outs = outs[S - 1:]
        # broadcast the last stage's rows to every device (masked psum:
        # cheap at these sizes, and replicated-out keeps out_specs simple)
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, axis)

    bspec = tuple(batch_spec) if batch_spec is not None else ()
    x_spec = P(*((None,) + bspec))
    p_specs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    out_spec = x_spec
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(p_specs, x_spec, P()),
        out_specs=out_spec, check_vma=False)
    key_data = key if key is not None else jnp.zeros((), jnp.uint32)
    from . import _device_put_global, _mesh_is_multiprocess
    if _mesh_is_multiprocess(mesh):
        # cross-process mesh: place host values as global arrays per
        # spec (every process passes the same full value)
        stage_params = jax.tree_util.tree_map(
            lambda leaf, s: _device_put_global(leaf, mesh, s),
            stage_params, p_specs)
        x_mb = _device_put_global(x_mb, mesh, x_spec)
        key_data = _device_put_global(key_data, mesh, P())
        y_mb = fn(stage_params, x_mb, key_data)
        # reshape stays in-graph: eager ops on non-addressable global
        # arrays are rejected by jax.  Output replicated (the merged
        # batch axis has no single-axis sharding after the collapse).
        # Cold multiprocess path: one compile per pipeline shape.
        return jax.jit(  # mxlint: disable=retrace-inline-jit
            lambda a: a.reshape((B,) + a.shape[2:]),
            out_shardings=jax.NamedSharding(mesh, P()))(y_mb)
    y_mb = fn(stage_params, x_mb, key_data)
    return y_mb.reshape((B,) + y_mb.shape[2:])


def stack_stage_params(per_layer_vals: Sequence[Sequence[jax.Array]]):
    """Stack per-layer parameter value lists into leading-dim-L leaves:
    ``[[w0,b0],[w1,b1],...] -> [stack(w),stack(b)]``.  All layers must
    be structurally identical (the homogeneous-stack contract)."""
    n = {len(v) for v in per_layer_vals}
    if len(n) != 1:
        raise MXNetError(f"layers are not homogeneous: param counts {n}")
    return [jnp.stack([vals[j] for vals in per_layer_vals])
            for j in range(n.pop())]


class PipelineTrainStep:
    """Compiled training step: replicated embed → layer pipeline over
    the ``pp`` axis → replicated head → loss; fwd+bwd+optimizer in one
    XLA program.

    ``cells`` must be structurally identical HybridBlocks (e.g.
    ``TransformerEncoderCell``s) whose forward maps (mb, ...) → same
    shape; ``len(cells)`` divisible by ``mesh.shape[pp_axis]``.  The
    stacked cell parameters live sharded over ``pp`` between steps;
    call :meth:`sync_params` to write them back into the Parameter
    objects (for checkpointing).
    """

    def __init__(self, embed, cells, head, loss_fn, optimizer,
                 mesh: Mesh, pp_axis: str = "pp",
                 n_microbatches: int = 4, dp_axis: Optional[str] = None,
                 donate: bool = True):
        from .. import optimizer as opt_mod
        from . import _opt_rule
        if not isinstance(optimizer, opt_mod.Optimizer):
            optimizer = opt_mod.create(optimizer)
        self.embed, self.cells, self.head = embed, cells, head
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.pp_axis = pp_axis
        self.dp_axis = dp_axis
        self.n_microbatches = n_microbatches
        self.donate = donate
        S = mesh.shape[pp_axis]
        if len(cells) % S:
            raise MXNetError(f"{len(cells)} layers not divisible by "
                             f"pipeline size {S}")
        self._opt_init, self._opt_update = _opt_rule(optimizer)
        self._built = False
        self._compiled: Dict[Any, Any] = {}
        self._t = 0

    # -- setup ----------------------------------------------------------
    def _setup(self, x_nd):
        import mxtpu.autograd as autograd
        from ..ndarray.ndarray import NDArray

        # deferred init through one eager pass of the whole model
        need = any(p._data is None for blk in
                   [self.embed, *self.cells, self.head]
                   for p in blk.collect_params().values())
        if need:
            with autograd.pause():
                h = self.embed(x_nd)
                h = h[0] if isinstance(h, (list, tuple)) else h
                for c in self.cells:
                    h = c(h)
                self.head(h)

        def pvals(blk):
            ps = list(blk.collect_params().values())
            return ps, [p._data._data for p in ps]

        self._embed_params, ev = pvals(self.embed)
        self._head_params, hv = pvals(self.head)
        cell_vals = []
        self._cell_params = []
        for c in self.cells:
            ps, vs = pvals(c)
            self._cell_params.append(ps)
            cell_vals.append(vs)
        for ps in self._cell_params:
            if [tuple(v.shape) for v in cell_vals[0]] != \
                    [p._data._data.shape for p in ps]:
                raise MXNetError("pipeline cells are not homogeneous")
        from ..symbol import _is_aux_name
        for blk in [self.embed, *self.cells, self.head]:
            # BN-style aux updates would need per-tick writeback through
            # the scan — unsupported; transformer stacks carry none.
            # (_apply_block also hard-fails if a trace EMITS aux, so
            # unconventionally-named running stats can't slip through.)
            for p in blk.collect_params().values():
                if _is_aux_name(p.name):
                    raise MXNetError(
                        "pipeline stages with aux (running stats) "
                        "are unsupported")

        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        self._ev = [jax.device_put(v, repl) for v in ev]
        self._hv = [jax.device_put(v, repl) for v in hv]
        stacked = stack_stage_params(cell_vals)
        self._sv = [jax.device_put(v, NamedSharding(mesh, P(self.pp_axis)))
                    for v in stacked]
        # honour grad_req='null' (frozen params).  For the stacked cell
        # params this must be uniform across layers per slot — the
        # stacked leaf updates as one unit.
        eh = self._embed_params + self._head_params
        self._eh_train = [i for i, p in enumerate(eh)
                          if p.grad_req != "null"]
        self._slot_train = []
        for j in range(len(self._sv)):
            reqs = {ps[j].grad_req for ps in self._cell_params}
            if len(reqs) > 1:
                raise MXNetError(
                    f"grad_req must be uniform across pipeline layers "
                    f"for param slot {j}: {reqs}")
            if reqs.pop() != "null":
                self._slot_train.append(j)
            mults = {(ps[j].lr_mult, ps[j].wd_mult)
                     for ps in self._cell_params}
            if len(mults) > 1:
                raise MXNetError(
                    f"lr_mult/wd_mult must be uniform across pipeline "
                    f"layers for param slot {j}: {mults}")
        self._opt_state = jax.device_put(
            tuple(self._opt_init(eh[i]._data._data)
                  for i in self._eh_train), repl)
        self._opt_state_s = tuple(
            jax.device_put(self._opt_init(self._sv[j]),
                           NamedSharding(mesh, P(self.pp_axis)))
            for j in self._slot_train)
        self._built = True

    # -- trace helpers --------------------------------------------------
    def _apply_block(self, blk, params, vals, x_raw, training, key_data):
        from ..gluon.block import _traced_forward
        from ..ndarray.ndarray import NDArray
        outs, _, aux_params, _ = _traced_forward(
            blk, params, vals, [NDArray(x_raw, None, _placed=True)],
            training, key_data)
        if aux_params:
            raise MXNetError(
                f"pipeline stages with aux (running-stat) updates are "
                f"unsupported: {[p.name for p in aux_params]}")
        return outs[0] if len(outs) == 1 else outs

    def _build(self, x_raw, y_raw, training):
        cell0 = self.cells[0]
        cell0_params = self._cell_params[0]
        loss_fn = self.loss_fn
        n_embed = len(self._ev)
        mesh, pp_axis, dp_axis = self.mesh, self.pp_axis, self.dp_axis
        n_micro = self.n_microbatches
        apply_block = self._apply_block

        def stage_fn(params_loc, h, key_data):
            # params_loc leaves: (L/S, ...) — scan this stage's layers
            def layer(carry, xs):
                lp, k = xs
                return apply_block(cell0, cell0_params, list(lp), carry,
                                   training, k), None
            nloc = params_loc[0].shape[0]
            # key_data is already unique per (microbatch, stage); fold
            # the local layer index for per-layer dropout masks
            keys = jax.vmap(
                lambda i: jax.random.key_data(jax.random.fold_in(
                    jax.random.wrap_key_data(key_data), i)))(
                jnp.arange(nloc))
            h, _ = lax.scan(layer, h, (tuple(params_loc), keys))
            return h

        def loss_flat(ev, hv, sv, key_data, x, y):
            from ..ndarray.ndarray import NDArray
            kf = jax.random.wrap_key_data(key_data)
            ke, kp, kh = (jax.random.key_data(jax.random.fold_in(kf, i))
                          for i in range(3))
            h = apply_block(self.embed, self._embed_params, list(ev),
                            x, training, ke)
            h = spmd_pipeline(
                stage_fn, list(sv), h, mesh=mesh, axis=pp_axis,
                n_microbatches=n_micro,
                batch_spec=P(dp_axis) if dp_axis else None, key=kp)
            out = apply_block(self.head, self._head_params, list(hv), h,
                              training, kh)
            pred = NDArray(out, None, _placed=True)
            l = loss_fn(pred, NDArray(y, None, _placed=True))
            raw = l.data if hasattr(l, "data") else l
            return jnp.mean(raw.astype(jnp.float32))

        if not training:
            return {"eval": jax.jit(loss_flat)}

        eh_train = self._eh_train
        slot_train = self._slot_train

        def step(ev, hv, sv, opt_state, opt_state_s, key_data,
                 lrs, wds, lrs_s, wds_s, x, y):
            loss, (ge, gh, gs) = jax.value_and_grad(
                loss_flat, argnums=(0, 1, 2))(ev, hv, sv, key_data, x, y)
            vals = list(ev) + list(hv)
            grads = list(ge) + list(gh)
            new_st = []
            for k, i in enumerate(eh_train):
                w2, st2 = self._opt_update(vals[i], grads[i],
                                           opt_state[k], lrs[k], wds[k])
                vals[i] = w2
                new_st.append(st2)
            new_s = list(sv)
            new_st_s = []
            for k, j in enumerate(slot_train):
                w2, st2 = self._opt_update(sv[j], gs[j], opt_state_s[k],
                                           lrs_s[k], wds_s[k])
                new_s[j] = w2
                new_st_s.append(st2)
            return (loss, tuple(vals[:n_embed]), tuple(vals[n_embed:]),
                    tuple(new_s), tuple(new_st), tuple(new_st_s))

        donate = (0, 1, 2, 3, 4) if self.donate else ()
        return {"fn": jax.jit(step, donate_argnums=donate)}

    # -- the hot call ---------------------------------------------------
    def __call__(self, x, y, training: bool = True):
        from ..ndarray import random as _rnd
        from ..ndarray.ndarray import NDArray
        x_raw = x.data if isinstance(x, NDArray) else jnp.asarray(x)
        y_raw = y.data if isinstance(y, NDArray) else jnp.asarray(y)
        if not self._built:
            self._setup(x if isinstance(x, NDArray)
                        else NDArray(x_raw, None, _placed=True))
        repl = NamedSharding(self.mesh, P())
        if self.dp_axis is not None:
            spec = [None] * x_raw.ndim
            spec[0] = self.dp_axis
            x_raw = jax.device_put(
                x_raw, NamedSharding(self.mesh, P(*spec)))
            yspec = [None] * max(y_raw.ndim, 1)
            yspec[0] = self.dp_axis
            y_raw = jax.device_put(
                y_raw,
                NamedSharding(self.mesh, P(*yspec[:y_raw.ndim])))
        else:
            x_raw = jax.device_put(x_raw, repl)
            y_raw = jax.device_put(y_raw, repl)
        sig = (x_raw.shape, str(x_raw.dtype), y_raw.shape,
               str(y_raw.dtype), training)
        entry = self._compiled.get(sig)
        if entry is None:
            entry = self._build(x_raw, y_raw, training)
            self._compiled[sig] = entry
        key = _rnd._next_key(None)
        key_data = jax.device_put(jax.random.key_data(key), repl)
        if not training:
            # eval: loss only — no optimizer update, no step-counter
            # advance, parameters untouched
            loss = entry["eval"](tuple(self._ev), tuple(self._hv),
                                 tuple(self._sv), key_data, x_raw, y_raw)
            return NDArray(loss, None, _placed=True)
        self._t += 1
        opt = self.optimizer
        opt.num_update = self._t
        from . import _adam_bias_correction
        base = opt.learning_rate * _adam_bias_correction(opt, self._t)
        # live per-param mults, matching TrainStep._lrs_wds semantics
        eh = self._embed_params + self._head_params
        lrs = jnp.asarray([base * eh[i].lr_mult for i in self._eh_train],
                          jnp.float32)
        wds = jnp.asarray([opt.wd * eh[i].wd_mult
                           for i in self._eh_train], jnp.float32)
        # mults are read live each step, but the stacked leaf updates as
        # one unit — re-validate uniformity so a mid-training change on
        # one cell can't be silently ignored
        for j in self._slot_train:
            mults = {(ps[j].lr_mult, ps[j].wd_mult)
                     for ps in self._cell_params}
            if len(mults) > 1:
                raise MXNetError(
                    f"lr_mult/wd_mult diverged across pipeline layers "
                    f"for param slot {j}: {mults} (stacked layers "
                    f"update as one unit)")
        c0 = self._cell_params[0]
        lrs_s = jnp.asarray([base * c0[j].lr_mult
                             for j in self._slot_train], jnp.float32)
        wds_s = jnp.asarray([opt.wd * c0[j].wd_mult
                             for j in self._slot_train], jnp.float32)
        lrs, wds, lrs_s, wds_s = (jax.device_put(a, repl)
                                  for a in (lrs, wds, lrs_s, wds_s))
        loss, ev, hv, sv, st, st_s = entry["fn"](
            tuple(self._ev), tuple(self._hv), tuple(self._sv),
            self._opt_state, self._opt_state_s,
            key_data, lrs, wds, lrs_s, wds_s, x_raw, y_raw)
        self._ev, self._hv, self._sv = list(ev), list(hv), list(sv)
        self._opt_state, self._opt_state_s = st, st_s
        return NDArray(loss, None, _placed=True)

    # -- parameter writeback -------------------------------------------
    def sync_params(self) -> None:
        """Write the (replicated / pp-sharded) training values back into
        the source Parameter objects, unstacking the layer dimension —
        so ``save_parameters`` checkpoints see the trained weights."""
        if not self._built:
            return
        # stage through host so the written-back buffers are ordinary
        # single-device arrays (eager ops reject mixed mesh/plain
        # placements)
        for p, v in zip(self._embed_params, self._ev):
            p._data._data = jnp.asarray(np.asarray(v))
        for p, v in zip(self._head_params, self._hv):
            p._data._data = jnp.asarray(np.asarray(v))
        for j, stacked in enumerate(self._sv):
            host = np.asarray(stacked)
            for i, ps in enumerate(self._cell_params):
                ps[j]._data._data = jnp.asarray(host[i])


    # -- checkpoint/resume (parity with TrainStep) ----------------------
    def save_states(self, fname: str) -> None:
        """Serialize optimizer state + step counter; pair with
        :meth:`sync_params` + ``save_parameters`` for a full resumable
        checkpoint."""
        import pickle
        if not self._built:
            raise MXNetError("nothing to save: step never ran")
        with open(fname, "wb") as f:
            pickle.dump({
                "t": self._t,
                "opt_state": jax.tree_util.tree_map(
                    np.asarray, self._opt_state),
                "opt_state_s": jax.tree_util.tree_map(
                    np.asarray, self._opt_state_s),
            }, f)

    def load_states(self, fname: str) -> None:
        import pickle
        if not self._built:
            raise MXNetError("load_states requires a built step: run "
                             "one step (or call _setup) first")
        with open(fname, "rb") as f:
            data = pickle.load(f)  # mxlint: disable=raw-deserialize (optimizer-state checkpoint: own save_states framing, arrays not executables)
        self._t = data["t"]
        repl = NamedSharding(self.mesh, P())
        self._opt_state = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, data["opt_state"]), repl)
        self._opt_state_s = tuple(
            jax.device_put(jax.tree_util.tree_map(jnp.asarray, st),
                           NamedSharding(self.mesh, P(self.pp_axis)))
            for st in data["opt_state_s"])


def build_pipeline_train_step(embed, cells, head, loss_fn,
                              optimizer="sgd", optimizer_params=None,
                              mesh: Optional[Mesh] = None,
                              pp_axis: str = "pp",
                              n_microbatches: int = 4,
                              dp_axis: Optional[str] = None,
                              donate: bool = True) -> PipelineTrainStep:
    """Compile embed→cells-pipeline→head into one SPMD GPipe step."""
    from .. import optimizer as opt_mod
    if not isinstance(optimizer, opt_mod.Optimizer):
        optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
    if mesh is None:
        raise MXNetError("pipeline parallelism requires a mesh with a "
                         f"'{pp_axis}' axis")
    return PipelineTrainStep(embed, cells, head, loss_fn, optimizer,
                             mesh, pp_axis=pp_axis,
                             n_microbatches=n_microbatches,
                             dp_axis=dp_axis, donate=donate)
