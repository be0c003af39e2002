"""Driver of ``"kind": "train"`` cells: a compiled train step, timed.

A copy of ``chip_smoke.train_phase`` with its checks taken out and a
timed window put in.  Set-up builds ONE object — the program's model,
its ``build_train_step`` step and optimizer state — gives it the
benchmark's weights, drives it through its first ``check_steps`` steps by
the window's own call and feed, and hands the same object to the window.
What those steps produced (each loss, the first gradient's norm per leaf
from adam's first moment, each leaf's change) is compared with the plain
reference after the window, once the program's state is freed.
"""
import functools
import gc
import time

import numpy as np

from .. import harness, loadgen, weights
from . import bert_program


# ----------------------------------------------------------------------
# the feed: one fresh host batch per step, rows all different
# ----------------------------------------------------------------------
def batch(seed, step, mix, vocab):
    """(token ids, labels) of step ``step``: integers (batch, seq)."""
    rng = loadgen.rng_for(seed, 50_000 + step)
    shape = (int(mix["batch"]), int(mix["seq"]))
    return rng.integers(0, vocab, shape), rng.integers(0, vocab, shape)


def rng_seed_of(seed):
    """The program's random stream takes a 31-bit seed."""
    return int(seed) % (2 ** 31 - 1)


class Program:
    """The system under test: the program's model, step and state."""

    def __init__(self, cfg, mix, seed):
        import mxtpu as mx
        from mxtpu import nd, parallel
        from mxtpu.gluon import loss as gloss
        self.cfg, self.mix, self.nd, self.mx = cfg, mix, nd, mx
        vocab = cfg["vocab_size"]
        t0 = time.perf_counter()
        mx.random.seed(rng_seed_of(seed))
        self.net = bert_program.build_net(cfg)
        self.net.initialize(init="xavier")
        t1 = time.perf_counter()

        def mlm_loss(pred, y):
            return gloss.SoftmaxCrossEntropyLoss()(
                pred.reshape((-1, vocab)), y.reshape((-1,)))

        # cast_batch=False: token ids must not be rounded through bf16
        self.step = parallel.build_train_step(
            self.net, mlm_loss, mix["optimizer"],
            {"learning_rate": float(mix["learning_rate"])},
            compute_dtype=mix.get("compute_dtype"), cast_batch=False)
        x, y = self.feed(seed, 0)
        self.hlo_text = self.step.hlo_text(x, y)   # builds THE executable
        self.pmap = bert_program.param_map(self.net, cfg)
        self.build_seconds = {"model_s": round(t1 - t0, 2), "step_s": round(
            time.perf_counter() - t1, 2)}

    def feed(self, seed, step):
        """The window's feed: a fresh host batch made into the arrays
        the step takes (float32 ids, as the program's models expect)."""
        x, y = batch(seed, step, self.mix, self.cfg["vocab_size"])
        return (self.nd.array(x.astype(np.float32)),
                self.nd.array(y.astype(np.float32)))

    def load(self, seed):
        """The benchmark's weights for ``seed`` into the program's
        parameters; the random stream set where the reference starts."""
        w = weights.make(self.cfg, seed)
        for (p, _), a in zip(self.pmap,
                             bert_program.program_arrays(self.pmap, w)):
            p._data._data = a
        self.mx.random.seed(rng_seed_of(seed))

    def params(self):
        return [p._data._data for p, _ in self.pmap]

    def first_moments(self):
        """adam's first moment per entry of pmap."""
        by_param = {id(self.step._params[i]): self.step._opt_state[j][0]
                    for j, i in enumerate(self.step._train_idx)}
        return [by_param[id(p)] for p, _ in self.pmap]


@functools.lru_cache(maxsize=8)
def _norms_of(counts, with_base):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def go(arrays, base):
        out = []
        for k, a in zip(counts, [x - y for x, y in zip(arrays, base)]
                        if with_base else arrays):
            n = a.shape[0] // k
            for j in range(k):
                piece = a if k == 1 else a[j * n:(j + 1) * n]
                out.append(jnp.sqrt(jnp.sum(jnp.square(
                    piece.astype(jnp.float32)))))
        return out

    return go


def _split_norms(pmap, arrays, base=None):
    """``{reference leaf: norm}`` of program-layout arrays (minus
    ``base`` where given), in one jitted call: a program leaf that joins
    several of the reference's is cut back into them."""
    go = _norms_of(tuple(len(names) for _, names in pmap), base is not None)
    names = [n for _, ns in pmap for n in ns]
    return dict(zip(names, (float(v) for v in go(arrays, base))))


def checked_steps(prog, seed, n_steps, call=None):
    """Drive ``prog`` through its first steps from ``seed`` by ``call``
    (the window's own call: ``prog.step`` unless a test has broken it
    underneath); returns what ``compare`` takes."""
    from ..reference import ADAM
    call = prog.step if call is None else call
    prog.load(seed)
    beta1 = ADAM["beta1"]
    losses, grad_norms = [], None
    for s in range(n_steps):
        x, y = prog.feed(seed, s)
        losses.append(float(call(x, y).asnumpy()))
        if s == 0:
            grad_norms = {k: v / (1.0 - beta1) for k, v in _split_norms(
                prog.pmap, prog.first_moments()).items()}
    w0 = bert_program.program_arrays(prog.pmap,
                                     weights.make(prog.cfg, seed))
    change = _split_norms(prog.pmap, prog.params(), w0)
    del w0
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


# ----------------------------------------------------------------------
# the comparison
# ----------------------------------------------------------------------
def worst_leaf_gap(got, ref, leaves=None, floor=True):
    """The widest gap between the program's norm of a leaf and the
    reference's, and the leaf it is at.  With ``floor`` the gap is taken
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (a leaf far under the median is then judged by
    the median's measure); without it, against the leaf's own norm."""
    names = list(ref if leaves is None else leaves)
    median = float(np.median([ref[k] for k in names])) \
        if names and floor else 0.0
    worst, at = 0.0, None
    for k in names:
        gap = abs(got[k] - ref[k]) / max(ref[k], median, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def moved_leaves(ref):
    """Leaves whose gradient in the reference is not nought to rounding:
    at least a thousandth of the median leaf's.  The others (a key's
    bias under softmax, an embedding that is never added) move under
    adam by round-off alone, and are left out of the change."""
    g = ref["grad_norms"]
    floor = 1e-3 * float(np.median(list(g.values())))
    return [k for k, v in g.items() if v >= floor]


def compare(got, ref):
    """The numbers compared, ``{name: (value, where)}``.  The two
    ``*_own`` numbers are the same gaps against each leaf's own norm,
    over the leaves whose gradient is not nought to rounding."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(got["losses"], ref["losses"]))
    moved = moved_leaves(ref)
    grad, g_at = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    change, c_at = worst_leaf_gap(got["change_norms"], ref["change_norms"],
                                  moved)
    grad_own, go_at = worst_leaf_gap(got["grad_norms"], ref["grad_norms"],
                                     moved, floor=False)
    change_own, co_at = worst_leaf_gap(
        got["change_norms"], ref["change_norms"], moved, floor=False)
    return {"loss_rel_gap": (loss, "worst of the checked steps"),
            "grad_norm_gap": (grad, g_at),
            "change_norm_gap": (change, c_at),
            "grad_norm_gap_own": (grad_own, go_at),
            "change_norm_gap_own": (change_own, co_at)}


def reference_run(cfg, mix, seed, n_steps, cast=None, fault=None):
    from .. import reference
    batches = [batch(seed, s, mix, cfg["vocab_size"]) for s in range(n_steps)]
    if fault == "half_batch":
        half = int(mix["batch"]) // 2
        batches = [(x[:half], y[:half]) for x, y in batches]
    elif fault is not None:
        raise ValueError(f"train: unknown fault {fault!r}")
    return reference.train(cfg, seed, rng_seed_of(seed), batches,
                           mix["learning_rate"], cast=cast)


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def run(ctx, tamper=None):
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    n_check = int(mix.get("check_steps", 3))
    tokens_per_step = int(mix["batch"]) * int(mix["seq"])

    prog = Program(cfg, mix, seed)
    t_built = time.perf_counter()
    step = prog.step if tamper is None else tamper(prog.step)
    got = checked_steps(prog, seed, n_check, step)
    # the window's steps go on from the checked ones: same object, same
    # feed, the step counter running on
    n = n_check
    x, y = prog.feed(seed, n)
    ctx.note("train", checked_losses=got["losses"])
    ctx.note("setup", imports_s=round(t_built - ctx.t_start
                                      - sum(prog.build_seconds.values()), 2),
             **prog.build_seconds,
             checked_steps_s=round(time.perf_counter() - t_built, 2))

    ctx.open_window()
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    host_call = []
    loss = None
    while time.perf_counter() < t_end:
        with ctx.span("train_step_call"):
            c0 = time.perf_counter()
            loss = step(x, y)
            host_call.append(time.perf_counter() - c0)
        n += 1
        with ctx.span("feed"):
            x, y = prog.feed(seed, n)
    with ctx.span("wait_last_step"):
        last = float(loss.asnumpy())          # blocks: every step is done
    t1 = time.perf_counter()
    ctx.close_window(t0, t1)
    steps = n - n_check

    ctx.read_memory()
    hlo_text = prog.hlo_text
    del prog, step, x, y, loss
    gc.collect()

    ref = reference_run(cfg, mix, seed, n_check)
    checks = compare(got, ref)
    ok = bool(np.isfinite(last))
    return harness.Outcome(
        attempted=steps, failed=0 if ok else steps,
        end_to_end={"train_tokens_per_s": steps * tokens_per_step / (t1 - t0)},
        checks=checks, limits=mix["limits"],
        facts={"steps": steps, "tokens_per_step": tokens_per_step,
               "window_s": t1 - t0, "host_call_s": host_call,
               "hlo_text": hlo_text})


def readings(ctx, seeds, control=None, fault=None):
    """``{seed: {who: checks}}`` on several seeds in one process: the
    program's (one object, its state set back for each seed), or the
    reference in its place at the ``control`` precision or with a
    ``fault`` planted.  No window."""
    import jax
    import jax.numpy as jnp
    cfg, mix = ctx.cfg, ctx.mix
    n_check = int(mix.get("check_steps", 3))
    who = "program" if control is None and fault is None else \
        "+".join(f"{k}:{v}" for k, v in (("control", control),
                                         ("fault", fault)) if v)
    gots = {}
    if who == "program":
        prog = Program(cfg, mix, seeds[0])
        for seed in seeds:
            prog.step._opt_state = jax.tree_util.tree_map(
                jnp.zeros_like, prog.step._opt_state)
            prog.step._t = 0
            gots[seed] = checked_steps(prog, seed, n_check)
        del prog
        gc.collect()
    out = {}
    for seed in seeds:
        ref = reference_run(cfg, mix, seed, n_check)
        got = gots.get(seed) or reference_run(cfg, mix, seed, n_check,
                                              cast=control, fault=fault)
        out[seed] = {who: compare(got, ref)}
    return out
