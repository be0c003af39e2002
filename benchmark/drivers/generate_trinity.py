"""Driver of ``"kind": "generate_trinity"`` cells: one device's share of
an expert-parallel decoder — sliding-window and full attention, gated
and under sandwich norms, a dense layer, then routed layers that hold a
share of their experts beside a shared expert — streamed generation,
served.

The window, the load generator and the rule of ``generate_hybrid`` —
never a result line the driver cannot stand behind: with no request due
and finished inside the window, a NaN among the numbers compared, or
(traced) a metric of the cell that reads no value, it exits non-zero
with a message on standard error — are ``drivers/generate.py``'s and
``drivers/generate_hybrid.py``'s; the sample held against the reference
(the two longest finished requests and six from the seed) and the
logits kept on the device behind every decode call (``DecodeLogits``)
are ``drivers/generate_mellum2.py``'s.  What differs is the model's
configuration (``model_type: afmoe``, with ``expert_share``: the graph
counts the experts it touched and the assignments that landed on them),
the weights (``weights_trinity``), the reference (``reference_trinity``),
the counts (``flops_trinity``), and what ``correct`` holds: the decode
calls' LOGITS alone, against the reference's at the same positions
(``served_logit_err``, ``served_logit_err_q1``).  Each decode call also
tells its region how many keys its lanes' sliding layers read
(``window_tokens``: ``min(p + 1, sliding_window)`` summed over the
lanes), for ``ep_decode_step_roofline``.

No model code lives here.  The first lines below name what only a
program with the ``afmoe`` family has: on a program without it the
driver fails there, at once, with no result line.
"""
from mxtpu.models.hybrid import HybridDecoderModel  # noqa: I001
HybridDecoderModel._from_afmoe  # noqa: B018

import gc  # noqa: E402
import os  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

from .. import flops_trinity, harness, weights_trinity  # noqa: E402
from . import generate_hybrid  # noqa: E402
from .generate import _OnDevice, serve_window  # noqa: E402
from .generate_hybrid import _read_every_metric, _stop, _whole  # noqa: E402
from .generate_mellum2 import (DecodeLogits, columns,  # noqa: E402
                               logit_numbers, sample_for_check)


def with_window_tokens(runner, window):
    """Round ``runner.decode``: each call's region is told the keys its
    decoding lanes' sliding layers read, ``window_tokens`` = the sum of
    ``min(step + 1, window)`` over them (the program's own counts give
    only the sum of the frontiers)."""
    decode = runner.decode

    def counted(tokens, step, kv, length=None):
        step = np.asarray(step)
        on = np.ones(len(step), bool) if length is None \
            else np.asarray(length) > 0
        return decode(tokens, step, kv, length, facts={
            "window_tokens": int(np.minimum(step[on] + 1, window).sum())})

    runner.decode = counted


class Program(generate_hybrid.Program):
    """``generate_hybrid``'s program (its first runs, its reload, its
    way in) built from this family's configuration and weights."""

    def __init__(self, ctx, seed):
        os.environ["MXTPU_GEN_MAX_LANES"] = str(int(ctx.mix["lanes"]))
        import jax
        from mxtpu import analysis, obs
        from mxtpu import symbol as sym_mod
        from mxtpu.serving import GenerateRunner, InferenceServer
        cfg, mix = ctx.cfg, ctx.mix
        self.cfg, self.mix = cfg, mix
        marks = [("imports", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        net = HybridDecoderModel.from_config(cfg)
        spec = net.state_spec(
            int(mix["lanes"]), int(mix["kv_capacity"]),
            kv_dtype=cfg.get("kv_cache_dtype", "float32"),
            max_chunk=max(mix["prompt_buckets"]))
        out = net(*[sym_mod.var(f"data{i}") for i in range(3 + len(spec))])
        leaves = net.named_leaves()
        # program parameter name -> the benchmark's leaf it is given
        self.leaf_of = {p.name: leaf for leaf, p in leaves.items()}
        if {leaf: tuple(p.shape) for leaf, p in leaves.items()} \
                != weights_trinity.leaf_shapes(cfg):
            raise RuntimeError("generate_trinity: the program's leaves "
                               "are not the reference's")
        mark("model_and_graph")
        self.amp = cfg.get("param_dtype") == "bfloat16"
        rungs = [int(b) for b in mix["warm_batch_rungs"]]
        self.runner = GenerateRunner(
            sym_mod.Group(list(out)), self._params(seed), spec,
            prompt_buckets=tuple(mix["prompt_buckets"]),
            max_prefill_batch=max(rungs), amp=self.amp,
            device=jax.devices()[0], counters=net.counter_spec())
        del net, out
        with_window_tokens(self.runner, int(cfg["sliding_window"]))
        # before the first runs: its slice compiles in set-up
        self.kept = DecodeLogits(self.runner, int(cfg["vocab_size"]))
        slots = self.runner.max_lanes + 1
        programs = [("prefill", (b, s)) for s in mix["prompt_buckets"]
                    for b in rungs] + [("decode", (slots,))]
        if sorted(rungs) != list(self.runner.batch_buckets):
            raise RuntimeError(
                f"generate_trinity: the cell warms rungs {rungs}, the "
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
                f"generate_trinity: the server's batcher uses "
                f"{self.batcher.max_lanes} lanes, the cell states "
                f"{mix['lanes']}")
        ctx.wrap(self.runner, "decode", "decode")
        ctx.wrap(self.runner, "prefill", "prefill")
        ctx.wrap(self.batcher, "step", "batcher_step")
        entries = {f"{k}{shape}": self.runner._entry((k, shape))
                   for k, shape in programs}
        held = {v["labels"]["table"]: int(v["value"]) for v in obs.snapshot()
                .get("mxtpu_gen_state_bytes", {}).get("series", [])}
        ctx.note("programs", state_bytes=self.runner.state_bytes(),
                 held_bytes=held,
                 attention_kernel_sites={
                     n: e.get("attention_kernel_sites")
                     for n, e in entries.items()},
                 temp_bytes={n: (analysis.mem_stats(e["compiled"])
                                 or {}).get("temp_size_in_bytes")
                             for n, e in entries.items()})
        ctx.note("setup", **{name + "_s": round(t - ctx.t_start if i == 0
                                                else t - marks[i - 1][1], 2)
                             for i, (name, t) in enumerate(marks)})

    def _params(self, seed):
        """The benchmark's leaves, on the device, under the program's
        names: one to one, so the runner holds the very arrays."""
        import jax.numpy as jnp
        w = weights_trinity.make(self.cfg, seed)
        if not self.amp:
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return {name: _OnDevice(w[leaf])
                for name, leaf in self.leaf_of.items()}

    def reload(self, seed):
        super().reload(seed)
        self.kept.clear()

    def logits_of(self, sample):
        """The timed path's logits of ``sample``'s decode steps, or no
        result line."""
        got = self.kept.of(sample)
        lost = [r.index for r, g in zip(sample, got) if g is None]
        if lost:
            _stop(f"the decode calls of requests {lost} are not among "
                  f"the {len(self.kept.decodes)} kept")
        return got


def useful_flops(cfg, requests, t0, t1):
    """Forward operations the window's tokens needed on this device:
    each prompt of a request whose first token fell in the window, and
    each token decoded in it against the context it had."""
    ops = 0.0
    for r in requests:
        times, p = r.token_times, len(r.prompt)
        if times and t0 <= times[0] < t1:
            ops += flops_trinity.prompt_flops(cfg, p)
        for j, t in enumerate(times[1:], 1):
            if t0 <= t < t1:
                ops += flops_trinity.decode_flops_per_token(cfg, p + j)
    return ops


def checks_of(c):
    n = f"{c['logit_tokens']} decode steps' logits, " \
        f"{c['logit_columns']} columns each"
    return {"served_logit_err": (c["logit_err"], n),
            "served_logit_err_q1": (c["logit_err_q1"], n)}


def check(ctx, seed, sample, program_logits, casts=(None,), moves=False):
    """``{cast: numbers}`` over the reference's forwards of ``sample``:
    under ``None`` ``program_logits`` (``Program.logits_of``) against
    the reference's, under a cast the control's in their place.  With
    ``moves`` also, on standard error, how many (position, routed
    layer) pairs of the longest sampled request the selection bias
    gave another set of experts (one more forward: for ``limits_why``,
    not for a run)."""
    from .. import reference_trinity as reference
    cfg, mix = ctx.cfg, ctx.mix
    w = weights_trinity.make(cfg, seed)
    rows = [(r.prompt, r.tokens) for r in sample]
    pad_to = int(mix["check"].get("pad_to", 1024))
    by_cast = reference.served_logits(
        cfg, w, rows, casts, columns(int(cfg["vocab_size"])), pad_to=pad_to)
    if moves:
        longest = list(rows[0][0]) + list(rows[0][1])
        padded = np.zeros((1, -(-len(longest) // pad_to) * pad_to), np.int32)
        padded[0, :len(longest)] = longest
        moved, pairs = reference.bias_moves(cfg, w, padded, len(longest))
        ctx.note("bias_moves", positions=len(longest), pairs=pairs,
                 chosen_set_changed=moved, share=moved / max(pairs, 1))
    # the first token of a request is a prefill call's: its logits are
    # not among those kept, on either side
    exact = [logits[1:] for logits in by_cast[None]]
    return {cast: logit_numbers(program_logits if cast is None
                                else [g[1:] for g in held], exact)
            for cast, held in by_cast.items()}


def _note_moe(ctx):
    from mxtpu import obs
    snap = obs.snapshot()
    ctx.note("moe", **{name: sum(int(v["value"]) for v in snap.get(
        name, {}).get("series", []))
        for name in ("mxtpu_moe_assignments_total",
                     "mxtpu_moe_assignments_held_total",
                     "mxtpu_moe_experts_touched_total")})


def run(ctx, tamper=None):
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    prog = Program(ctx, seed)
    gen, seen = serve_window(ctx, prog, seed, ctx.seconds, tamper)
    t0, t1 = ctx.window
    ctx.read_memory()
    hlo_text = prog.runner.program_artifact()[0] if ctx.trace else None
    # a prefill program for each (rows, bucket): instruction names repeat
    # from program to program, so a reader needs each one's own text
    prefill_texts = {
        f"{b}x{s}": prog.runner.program_artifact(("prefill", (b, s)))[0]
        for s in mix["prompt_buckets"] for b in mix["warm_batch_rungs"]} \
        if ctx.trace else None
    _note_moe(ctx)
    prog.close()
    sample = sample_for_check(seen["mine"], seed,
                              int(mix["check"]["requests"]))
    program_logits = prog.logits_of(sample) if sample else None
    ops = useful_flops(cfg, gen.requests, t0, t1)
    ctx.note("window", tokens_per_s=seen["tokens"] / (t1 - t0),
             attempted=len(seen["mine"]), failed=len(seen["failed"]),
             memory_peak_bytes=ctx.memory_peak_bytes,
             prompts_over_window=sum(
                 len(r.prompt) > int(cfg["sliding_window"]) for r in sample),
             decode_calls_kept=len(prog.kept.decodes))
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

    checked = check(ctx, seed, sample, program_logits)[None]
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
               "kv_capacity": int(mix["kv_capacity"]),
               "hlo_text": hlo_text, "prefill_hlo_texts": prefill_texts})
    if ctx.trace:
        _read_every_metric(ctx, outcome)
    return outcome


def readings(ctx, seeds, control=None, fault=None):
    """``{seed: {who: checks}}`` on several seeds in one process, as
    ``generate_hybrid.readings``; ``control`` may name several controls,
    joined by commas (``fp8,bias_weights``); with none, what the
    selection bias does to each seed's longest sampled request is
    counted too."""
    if fault is not None:
        raise ValueError(f"generate_trinity: unknown fault {fault!r}")
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
        samples[seed] = (sample, prog.logits_of(sample))
    _note_moe(ctx)
    prog.close()
    del prog
    gc.collect()
    casts = (None,) + tuple(control.split(",") if control else ())
    out = {}
    for seed in seeds:
        got = check(ctx, seed, *samples[seed], casts, moves=not control)
        out[seed] = {"program" if cast is None else "control:" + cast:
                     checks_of(numbers) for cast, numbers in got.items()}
        for cast, numbers in got.items():
            ctx.note("read", seed=seed, who=cast or "program", **numbers)
    return out
