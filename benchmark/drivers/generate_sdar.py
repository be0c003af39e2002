"""Driver of ``"kind": "generate_sdar"`` cells: a block-diffusion decoder
with routed experts, streamed generation, served.

The window, the load generator and the rule of ``generate_hybrid`` —
never a result line the driver cannot stand behind: with no request due
and finished inside the window, a NaN among the numbers compared, or
(traced) a metric of the cell that reads no value, it exits non-zero
with a message on standard error — are ``drivers/generate.py``'s and
``drivers/generate_hybrid.py``'s; what differs is the model's
configuration (``model_type: sdar_moe``: one table of keys and values,
the counts the graph hands back, and the diffusion spec the runner and
the batcher read), the weights (``weights_sdar``), the reference
(``reference_sdar``), the counts (``flops_sdar``), the answers' lengths
(the traffic's output lengths rounded up so that a request ends on a
block's end: its ``max_tokens``) and what ``correct`` compares.

A pass decides tokens from logits, so the LOGITS of every pass of a
sampled request are held against the reference's: ``PassLogits`` keeps
128 vocabulary columns of each position of every pass on the device,
beside the pass's host rows (what each slot fed, from where) and which
request each slot held; after the window the passes of the sample's
requests are found by slot and rebuilt into ``(start, fed tokens)``, and
``reference_sdar.pass_logits`` computes the same positions from one
forward of the request's final sequence.  The sample always holds the
two longest finished requests.

No model code lives here.  The first statement below reads a name that
only a program with block-diffusion serving has: on a program without it
the driver fails there, at once, with no result line.
"""
from mxtpu.models.hybrid import HybridDecoderModel  # noqa: I001
HybridDecoderModel.diffusion_spec  # noqa: B018 — the program must serve blocks

import gc  # noqa: E402
import os  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

from .. import flops_sdar, harness, loadgen, weights_sdar  # noqa: E402
from . import generate_hybrid  # noqa: E402
from .generate import _OnDevice, reduce_window  # noqa: E402
from .generate_hybrid import _read_every_metric, _stop, _whole  # noqa: E402
from .generate_mellum2 import columns, logit_numbers  # noqa: E402


class PassLogits:
    """Round ``runner.decode``: what each pass's logits read at
    ``columns(vocab)`` stays on the device, (slots, block, 128) float32 a
    pass — one small slice dispatched behind the step, nothing fetched —
    beside the pass's host rows (tokens, start and length of each slot),
    the request each slot held (read off the batcher's lane table by the
    stepping thread that owns it) and when the pass ended; and round
    ``runner.prefill``, whose rows and times ``useful_flops`` reads.
    After the window ``of(sample)`` rebuilds each request's passes."""

    def __init__(self, runner, batcher_of, vocab):
        import jax
        import jax.numpy as jnp
        cols = columns(vocab)
        stride, n = int(vocab // len(cols)), len(cols)
        self._cut = jax.jit(
            lambda rows: rows[:, :, ::stride][:, :, :n].astype(jnp.float32))
        self.clear()
        decode, prefill = runner.decode, runner.prefill
        whole = lambda a: np.asarray(a).astype(np.int64)

        def kept_decode(tokens, step, kv, length=None, **kw):
            lanes = batcher_of()._lanes if batcher_of() is not None else ()
            held = {i: lane.req for i, lane in enumerate(lanes)
                    if lane is not None}
            logits, kv = decode(tokens, step, kv, length, **kw)
            self.passes.append((time.perf_counter(), whole(tokens),
                                whole(step), whole(length), held,
                                self._cut(logits.rows)))
            return logits, kv

        def kept_prefill(tokens, step, lane_idx, kv, length=None):
            out = prefill(tokens, step, lane_idx, kv, length)
            self.prefills.append((time.perf_counter(), whole(step),
                                  whole(length)))
            return out

        runner.decode, runner.prefill = kept_decode, kept_prefill

    def clear(self):
        self.passes, self.prefills = [], []

    def of(self, sample):
        """For each request of ``sample``: ``(passes, logits)`` — its
        passes as ``(start, fed tokens)`` in order and their kept logits
        ``(passes, block, columns)`` — or None where none is found."""
        host, whose = {}, {}
        wanted = {tuple(int(t) for t in r.prompt): k
                  for k, r in enumerate(sample)}
        found = [([], []) for _ in sample]
        for at, (_, tokens, step, length, held, cut) in \
                enumerate(self.passes):
            for slot, req in held.items():
                if id(req) not in whose:
                    k = wanted.get(tuple(req.prompt))
                    whose[id(req)] = k if k is not None and \
                        req.tokens == list(sample[k].tokens) else None
                k = whose[id(req)]
                if k is None:
                    continue
                s, n = int(step[slot]), int(length[slot])
                found[k][0].append((s, tokens[slot, :n].tolist()))
                if at not in host:
                    host[at] = np.asarray(cut)
                found[k][1].append(host[at][slot])
        return [(mine, np.stack(rows)) if mine else None
                for mine, rows in found]


def agrees(final, passes, prompt_len, mask_id):
    """Where the passes' fed tokens disagree with ``final`` (a position
    fed as decided, or of the prompt, whose token is not the final one),
    as ``[(pass, position)]``."""
    bad = []
    for i, (start, fed) in enumerate(passes):
        for p, t in enumerate(fed, start):
            if p < len(final) and (t != mask_id or p < prompt_len) \
                    and t != final[p]:
                bad.append((i, p))
    return bad


def checks_of(c):
    n = f"{c['logit_tokens']} positions of {c['passes']} passes' logits, " \
        f"{c['logit_columns']} columns each"
    return {"served_logit_err": (c["logit_err"], n),
            "served_logit_err_q1": (c["logit_err_q1"], n)}


class Program(generate_hybrid.Program):
    """``generate_hybrid``'s program (its reload, its way in) built from
    this family's configuration and weights, with a block-diffusion
    runner."""

    def __init__(self, ctx, seed):
        os.environ["MXTPU_GEN_MAX_LANES"] = str(int(ctx.mix["lanes"]))
        import jax
        from mxtpu import obs
        from mxtpu import symbol as sym_mod
        from mxtpu.serving import GenerateRunner, InferenceServer
        cfg, mix = ctx.cfg, ctx.mix
        self.cfg, self.mix = cfg, mix
        marks = [("imports", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        net = HybridDecoderModel.from_config(cfg)
        spec = net.state_spec(int(mix["lanes"]), int(mix["kv_capacity"]),
                              kv_dtype=cfg.get("kv_cache_dtype", "float32"))
        out = net(*[sym_mod.var(f"data{i}") for i in range(3 + len(spec))])
        leaves = net.named_leaves()
        # program parameter name -> the benchmark's leaf it is given
        self.leaf_of = {p.name: leaf for leaf, p in leaves.items()}
        if {leaf: tuple(p.shape) for leaf, p in leaves.items()} \
                != weights_sdar.leaf_shapes(cfg):
            raise RuntimeError("generate_sdar: the program's leaves are "
                               "not the reference's")
        mark("model_and_graph")
        self.amp = cfg.get("param_dtype") == "bfloat16"
        rungs = [int(b) for b in mix["warm_batch_rungs"]]
        self.runner = GenerateRunner(
            sym_mod.Group(list(out)), self._params(seed), spec,
            prompt_buckets=tuple(mix["prompt_buckets"]),
            max_prefill_batch=max(rungs), amp=self.amp,
            device=jax.devices()[0], counters=net.counter_spec(),
            diffusion=net.diffusion_spec())
        self.diffusion = net.diffusion_spec()
        del net, out
        self.batcher = None
        # before the first runs: its slice compiles in set-up
        self.kept = PassLogits(self.runner, lambda: self.batcher,
                               int(cfg["vocab_size"]))
        slots = self.runner.max_lanes + 1
        programs = [("prefill", (b, s)) for s in mix["prompt_buckets"]
                    for b in rungs] + [("decode", (slots,))]
        if sorted(rungs) != list(self.runner.batch_buckets):
            raise RuntimeError(
                f"generate_sdar: the cell warms rungs {rungs}, the "
                f"runner's ladder is {self.runner.batch_buckets}")
        mark("weights_and_runner")
        self.runner.warmup(buckets=programs)
        mark("compile_or_load")
        self._run_each_once(programs)
        self.kept.clear()
        mark("first_runs")
        self.server = InferenceServer()
        self.server.register_generator(
            self.NAME, self.runner, max_queue=mix.get("max_queue"))
        self.batcher = self.server._gen[self.NAME][1].batcher
        if self.batcher.max_lanes != int(mix["lanes"]):
            raise RuntimeError(
                f"generate_sdar: the server's batcher uses "
                f"{self.batcher.max_lanes} lanes, the cell states "
                f"{mix['lanes']}")
        ctx.wrap(self.runner, "decode", "decode")
        ctx.wrap(self.runner, "prefill", "prefill")
        ctx.wrap(self.batcher, "step", "batcher_step")
        held = {v["labels"]["table"]: int(v["value"]) for v in obs.snapshot()
                .get("mxtpu_gen_state_bytes", {}).get("series", [])}
        from mxtpu import analysis
        ctx.note("programs", state_bytes=self.runner.state_bytes(),
                 held_bytes=held,
                 temp_bytes={f"{k}{shape}": (analysis.mem_stats(
                     self.runner._entry((k, shape))["compiled"]) or {}).get(
                         "temp_size_in_bytes") for k, shape in programs})
        ctx.note("setup", **{name + "_s": round(t - ctx.t_start if i == 0
                                                else t - marks[i - 1][1], 2)
                             for i, (name, t) in enumerate(marks)})

    def _params(self, seed):
        """The benchmark's leaves, on the device, under the program's
        names: one to one, so the runner holds the very arrays."""
        import jax.numpy as jnp
        w = weights_sdar.make(self.cfg, seed)
        if not self.amp:
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return {name: _OnDevice(w[leaf])
                for name, leaf in self.leaf_of.items()}

    def _run_each_once(self, programs):
        """A program's first run pays what a compile does not: pay it in
        set-up (padding rows only: scratch slot, nothing valid)."""
        r = self.runner
        kv = r.new_cache()
        width = 2 * int(self.diffusion["block"])
        for kind, shape in programs:
            if kind == "prefill":
                b, s = shape
                _, kv = r.prefill(np.ones((b, s), np.float32),
                                  np.zeros(b, np.float32),
                                  np.full(b, r.scratch_slot, np.float32), kv,
                                  np.zeros(b, np.float32))
            else:
                _, kv = r.decode(np.ones((shape[0], width), np.float32),
                                 np.zeros(shape[0], np.float32), kv,
                                 np.zeros(shape[0], np.float32))
        del kv

    def reload(self, seed):
        super().reload(seed)
        self.kept.clear()

    def passes_of(self, sample):
        """The timed path's passes of ``sample`` and their logits, or no
        result line: a sample whose passes are not among those kept, or
        whose passes disagree with what it streamed, cannot be held
        against the reference."""
        mask_id = int(self.diffusion["mask_id"])
        got = self.kept.of(sample)
        lost = [r.index for r, g in zip(sample, got) if g is None]
        if lost:
            _stop(f"the passes of requests {lost} are not among the "
                  f"{len(self.kept.passes)} kept")
        for r, (passes, _) in zip(sample, got):
            final = list(r.prompt) + list(r.tokens)
            bad = agrees(final, passes, len(r.prompt), mask_id)
            if bad:
                _stop(f"request {r.index}: passes fed tokens its stream "
                      f"does not hold at (pass, position) {bad[:5]}")
        return got


def block_lengths(mix, seed, block):
    """The generator's ``(prompt_len, output_len)`` pool for ``seed``
    with each output length rounded up (by 0 to ``block - 1``) to where
    prompt and answer end on a block's end: a request's last block is
    streamed whole."""
    return [(p, -(-(p + o) // block) * block - p)
            for p, o in loadgen.lengths(mix, seed)]


def serve_window(ctx, prog, seed, seconds, tamper=None):
    """``generate.serve_window`` with each request's ``max_tokens`` from
    ``block_lengths``, and prompt ids
    drawn below the mask id (every id of the vocabulary the tokenizer
    has)."""
    mix = ctx.mix
    b = int(prog.diffusion["block"])
    submit = prog.submit if tamper is None else tamper(prog.submit)
    batcher = prog.batcher
    gen = loadgen.LoadGen(mix, seed, int(prog.diffusion["mask_id"]), submit,
                          probe=lambda: (batcher.depth, batcher.free_lanes()))
    gen._lengths = block_lengths(mix, seed, b)
    t0, t1 = gen.run(seconds, on_open=ctx.open_window)
    ctx.close_window(t0, t1)
    gen.drain(t1 + float(mix.get("drain_s", 60.0)) - time.perf_counter())
    return gen, reduce_window(gen.requests, t0, t1)


def useful_flops(cfg, kept, t0, t1):
    """Forward operations of every position the window's calls fed (a
    pass's clean block and the block it denoises, a prefill's chunk),
    each against the keys it reads, and the head over each row's last
    block."""
    ops = 0.0
    for t, _, step, length, _, _ in kept.passes:
        if t0 <= t < t1:
            ops += sum(flops_sdar.row_flops(cfg, int(step[i]), int(length[i]))
                       for i in np.flatnonzero(length > 0))
    for t, step, length in kept.prefills:
        if t0 <= t < t1:
            ops += sum(flops_sdar.row_flops(cfg, int(step[i]), int(length[i]))
                       for i in np.flatnonzero(length > 0))
    return ops


def sample_for_check(requests, seed, n):
    """The finished requests to hold against the reference: the two
    longest (prompt and answer together), and the rest drawn from the
    seed."""
    done = sorted((r for r in requests
                   if not r.error and len(r.tokens) == r.max_tokens),
                  key=lambda r: -(len(r.prompt) + len(r.tokens)))
    longest, rest = done[:2], done[2:]
    pick = loadgen.rng_for(seed, 3).permutation(len(rest))[:max(0, n - 2)]
    return longest + [rest[i] for i in sorted(pick)]


def check(ctx, seed, sample, program, casts=(None,)):
    """``{cast: numbers}``: under ``None`` the timed path's logits of
    every pass of ``sample`` (``Program.passes_of``) against the
    reference's at the same positions and columns; under a cast the
    control's logits in their place."""
    from .. import reference_sdar as reference
    cfg, mix = ctx.cfg, ctx.mix
    w = weights_sdar.make(cfg, seed)
    cols = columns(int(cfg["vocab_size"]))
    pad_to = int(mix["check"].get("pad_to", 1280))
    chunk = int(mix["check"].get("chunk", 64))
    seen = {cast: [] for cast in casts}
    exact = []
    for r, (passes, logits) in zip(sample, program):
        final = list(r.prompt) + list(r.tokens)
        for cast in casts:
            got = reference.pass_logits(cfg, w, final, passes, cols,
                                        cast=cast, chunk=chunk, pad_to=pad_to)
            flat = got.reshape(-1, got.shape[-1])
            if cast is None:
                exact.append(flat)
                seen[None].append(logits.reshape(flat.shape))
            else:
                seen[cast].append(flat)
    n = sum(len(p) for p, _ in program)
    return {cast: dict(logit_numbers(seen[cast], exact), passes=n)
            for cast in casts}


def run(ctx, tamper=None):
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    prog = Program(ctx, seed)
    gen, seen = serve_window(ctx, prog, seed, ctx.seconds, tamper)
    t0, t1 = ctx.window
    ctx.read_memory()
    from mxtpu import obs
    snap = obs.snapshot()
    ctx.note("diffusion", **{name: sum(int(v["value"]) for v in snap.get(
        name, {}).get("series", []))
        for name in ("mxtpu_gen_diffusion_decided_total",
                     "mxtpu_gen_blocks_committed_total",
                     "mxtpu_moe_assignments_total",
                     "mxtpu_moe_experts_touched_total")})
    prog.close()
    sample = sample_for_check(seen["mine"], seed,
                              int(mix["check"]["requests"]))
    program = prog.passes_of(sample) if sample else []
    ops = useful_flops(cfg, prog.kept, t0, t1)
    ctx.note("window", tokens_per_s=seen["tokens"] / (t1 - t0),
             attempted=len(seen["mine"]), failed=len(seen["failed"]),
             memory_peak_bytes=ctx.memory_peak_bytes,
             pass_calls_kept=len(prog.kept.passes),
             lane_passes_checked=sum(len(p) for p, _ in program))
    if gen.probes:
        n = len(gen.probes)
        ctx.note("at_each_send", sends=n,
                 found_a_queue_share=sum(1 for d, _ in gen.probes if d) / n,
                 found_no_free_lane_share=sum(
                     1 for _, f in gen.probes if not f) / n,
                 mean_busy_lanes=int(mix["lanes"]) - sum(
                     f for _, f in gen.probes) / n)
    # the generator holds the program's way in, and with it the weights
    # and the tables: both go before the reference needs the memory
    del prog, gen
    gc.collect()
    import jax
    ctx.note("freed", bytes_in_use=(jax.devices()[0].memory_stats()
                                    or {}).get("bytes_in_use"))
    if not seen["mine"]:
        _stop("no request was due inside the window")
    if not sample:
        _stop(f"none of the {len(seen['mine'])} requests due inside the "
              f"window finished")
    if not seen["tokens"]:
        _stop("no token was streamed inside the window")

    checked = check(ctx, seed, sample, program)[None]
    ctx.note("check", requests=len(sample), **checked)
    checks = checks_of(checked)
    _whole(checks)
    window_s = t1 - t0
    outcome = harness.Outcome(
        attempted=len(seen["mine"]), failed=len(seen["failed"]),
        end_to_end={"serve_tokens_per_s": seen["tokens"] / window_s},
        checks=checks, limits=mix["limits"],
        facts={"window_s": window_s, "useful_flops": ops,
               "slots": int(mix["lanes"]) + 1,
               "kv_capacity": int(mix["kv_capacity"])})
    if ctx.trace:
        _read_every_metric(ctx, outcome)
    return outcome


def readings(ctx, seeds, control=None, fault=None):
    """``{seed: {who: checks}}`` on several seeds in one process, as
    ``generate_hybrid.readings``; ``control`` may name several controls,
    joined by commas (``fp8,causal_block``)."""
    if fault is not None:
        raise ValueError(f"generate_sdar: unknown fault {fault!r}")
    mix = ctx.mix
    prog = Program(ctx, seeds[0])
    samples = {}
    for i, seed in enumerate(seeds):
        if i:
            prog.reload(seed)
        # the generator holds the program's way in: it is not kept
        seen = serve_window(ctx, prog, seed, ctx.seconds)[1]
        sample = sample_for_check(seen["mine"], seed,
                                  int(mix["check"]["requests"]))
        ctx.note("readings", seed=seed, attempted=len(seen["mine"]),
                 failed=len(seen["failed"]), sampled=len(sample),
                 tokens_per_s=seen["tokens"] / ctx.seconds)
        if not sample:
            _stop(f"seed {seed}: no request due in the window finished")
        samples[seed] = (sample, prog.passes_of(sample))
    prog.close()
    del prog
    gc.collect()
    casts = (None,) + tuple(control.split(",") if control else ())
    out = {}
    for seed in seeds:
        got = check(ctx, seed, *samples[seed], casts)
        out[seed] = {"program" if cast is None else "control:" + cast:
                     checks_of(numbers) for cast, numbers in got.items()}
        # each seed's numbers as they come: a long set of readings that
        # is cut leaves what it had read
        for cast, numbers in got.items():
            ctx.note("read", seed=seed, who=cast or "program", **numbers)
    return out
