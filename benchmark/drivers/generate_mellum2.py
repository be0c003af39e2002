"""Driver of ``"kind": "generate_mellum2"`` cells: a decoder of sliding-
window and full attention layers with routed experts, streamed
generation, served.

The window, the load generator, the numbers ``correct`` compares and the
rule of ``generate_hybrid`` — never a result line the driver cannot
stand behind: with no request due and finished inside the window, a NaN
among the numbers compared, or (traced) a metric of the cell that reads
no value, it exits non-zero with a message on standard error — are
``drivers/generate.py``'s and ``drivers/generate_hybrid.py``'s; what
differs is the model's configuration (``model_type: mellum``: two tables
of keys and values, one of them a ring, and the counts the graph hands
back), the weights (``weights_mellum2``), the reference
(``reference_mellum2``), the counts (``flops_mellum2``) and the sample
held against the reference, which always holds the two longest finished
requests (prompts past the window, past a wrap of the ring and at the
longest length the window saw) — and one more thing compared: the
LOGITS of the decode steps that chose the sample's tokens, as the timed
path made them (``DecodeLogits``: 128 vocabulary columns of every decode
call, kept on the device), against the reference's at the same
positions.  The served tokens say whether a greedy user would have
noticed; the logits say whether the mathematics is all there: a token's
eighth expert left out moves few tokens' ranks and every token's logits.

No model code lives here.  The first import below is of a name that
only a program with routed experts on the serving path has: on a program
without it the driver fails there, at once, with no result line.
"""
from mxtpu.models.hybrid import SparseMLP  # noqa: F401, I001

import gc
import os
import time

import numpy as np

from .. import flops_mellum2, harness, loadgen, weights_mellum2
from . import generate, generate_hybrid
from .generate import _OnDevice, serve_window
from .generate_hybrid import _read_every_metric, _stop, _whole

COLUMNS = 128


def columns(vocab):
    """The vocabulary columns whose logits are held against the
    reference's: ``COLUMNS`` of them, evenly spread."""
    n = min(COLUMNS, vocab)
    return np.arange(n) * (vocab // n)


class DecodeLogits:
    """Round ``runner.decode``: what each call's logits read at
    ``columns(vocab)`` stays on the device, (slots, 128) float32 a call
    — one small slice dispatched behind the step, nothing fetched —
    beside the call's host rows (each slot's input token, its position,
    whether it decoded); and round ``runner.prefill``, whose host rows
    say which prompt went into which slot.  After the window
    ``of(sample)`` finds the calls that chose each request's tokens and
    brings their rows over."""

    def __init__(self, runner, vocab):
        import jax
        import jax.numpy as jnp
        cols = columns(vocab)
        stride, n = int(vocab // len(cols)), len(cols)
        self._cut = jax.jit(
            lambda rows: rows[:, 0, ::stride][:, :n].astype(jnp.float32))
        self.clear()
        decode, prefill = runner.decode, runner.prefill
        whole = lambda a: np.asarray(a).astype(np.int64)

        def kept_decode(tokens, step, kv, length=None):
            logits, kv = decode(tokens, step, kv, length)
            on = np.ones(len(step), bool) if length is None \
                else np.asarray(length) > 0
            self.decodes.append((self._calls, whole(tokens)[:, 0],
                                 whole(step), on, self._cut(logits.rows)))
            self._calls += 1
            return logits, kv

        def kept_prefill(tokens, step, lane_idx, kv, length=None):
            out = prefill(tokens, step, lane_idx, kv, length)
            self.prefills.append((
                self._calls, whole(tokens), whole(step), whole(lane_idx),
                np.full(len(step), np.shape(tokens)[1], np.int64)
                if length is None else whole(length)))
            self._calls += 1
            return out

        runner.decode, runner.prefill = kept_decode, kept_prefill

    def clear(self):
        self.decodes, self.prefills, self._calls = [], [], 0

    def of(self, sample):
        """For each request of ``sample`` the logits that chose its
        ``tokens[1:]``, ``(len(tokens) - 1, columns)``: the slot is the
        one whose prefill call ended the request's very prompt (the
        call's row reads the prompt's last chunk, token for token, at
        its place), and the decode calls are that slot's next ones,
        whose input tokens must read ``tokens[:-1]`` at positions
        ``len(prompt)`` and on (a request's first token is the prefill
        call's and is not kept).  None for a request whose calls are
        not found."""
        ended = {}      # (slot, prompt length) -> [(call, last chunk)]
        for call, tokens, step, lane, length in self.prefills:
            for row in np.flatnonzero(length > 0):
                ended.setdefault(
                    (int(lane[row]), int(step[row] + length[row])),
                    []).append((call, int(step[row]),
                                tokens[row, :length[row]].tolist()))
        by_slot = {}    # slot -> [(call, position, input token, logits)]
        for call, tok, step, on, rows in self.decodes:
            for slot in np.flatnonzero(on):
                by_slot.setdefault(int(slot), []).append(
                    (call, int(step[slot]), int(tok[slot]), rows))

        def rows_of(r):
            prompt = [int(t) for t in r.prompt]
            want = [(len(prompt) + j, int(t))
                    for j, t in enumerate(r.tokens[:-1])]
            for (slot, end), calls in ended.items():
                if end != len(prompt):
                    continue
                for call, start, chunk in calls:
                    if chunk != prompt[start:]:
                        continue
                    mine = [m for m in by_slot.get(slot, ())
                            if m[0] > call][:len(want)]
                    if [m[1:3] for m in mine] == want:
                        return np.stack([np.asarray(m[3])[slot]
                                         for m in mine])
            return None

        return [rows_of(r) for r in sample]


def logit_numbers(seen, exact):
    """How far logits ``seen`` lie from the reference's ``exact`` (two
    lists of (tokens, columns) arrays, a request each): a token's error
    is the norm of the difference over the norm of the reference's, over
    the columns kept.  ``err``: all tokens as one (root of the summed
    squares); ``q1``: the first quartile of the tokens' errors —
    rounding and a rare tie between two experts broken the other way
    leave most tokens near the rounding, a piece of the mathematics
    left out moves every one."""
    a = np.concatenate(seen).astype(np.float64)
    b = np.concatenate(exact).astype(np.float64)
    d2, n2 = ((a - b) ** 2).sum(-1), (b ** 2).sum(-1)
    each = np.sqrt(d2 / n2)
    return {"logit_err": float(np.sqrt(d2.sum() / n2.sum())),
            "logit_err_q1": float(np.quantile(each, 0.25)),
            "logit_tokens": int(each.size), "logit_columns": a.shape[-1]}


def checks_of(c):
    n = f"{c['logit_tokens']} decode steps' logits, " \
        f"{c['logit_columns']} columns each"
    return dict(generate.checks_of(c),
                served_logit_err=(c["logit_err"], n),
                served_logit_err_q1=(c["logit_err_q1"], n))


class Program(generate_hybrid.Program):
    """``generate_hybrid``'s program (its first runs, its reload, its
    way in) built from this family's configuration and weights."""

    def __init__(self, ctx, seed):
        os.environ["MXTPU_GEN_MAX_LANES"] = str(int(ctx.mix["lanes"]))
        import jax
        from mxtpu import analysis, obs
        from mxtpu import symbol as sym_mod
        from mxtpu.models.hybrid import HybridDecoderModel
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
                != weights_mellum2.leaf_shapes(cfg):
            raise RuntimeError("generate_mellum2: the program's leaves "
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
        # before the first runs: its slice compiles in set-up
        self.kept = DecodeLogits(self.runner, int(cfg["vocab_size"]))
        slots = self.runner.max_lanes + 1
        programs = [("prefill", (b, s)) for s in mix["prompt_buckets"]
                    for b in rungs] + [("decode", (slots,))]
        if sorted(rungs) != list(self.runner.batch_buckets):
            raise RuntimeError(
                f"generate_mellum2: the cell warms rungs {rungs}, the "
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
                f"generate_mellum2: the server's batcher uses "
                f"{self.batcher.max_lanes} lanes, the cell states "
                f"{mix['lanes']}")
        ctx.wrap(self.runner, "decode", "decode")
        ctx.wrap(self.runner, "prefill", "prefill")
        ctx.wrap(self.batcher, "step", "batcher_step")
        entries = {f"{k}{shape}": self.runner._entry((k, shape))
                   for k, shape in programs}
        # what the device holds for each table, tile padding and all: the
        # program's own gauge, set when the tables were made
        held = {v["labels"]["table"]: int(v["value"]) for v in obs.snapshot()
                .get("mxtpu_gen_state_bytes", {}).get("series", [])}
        ctx.note("programs", state_bytes=self.runner.state_bytes(),
                 held_bytes=held,
                 kv_kernel_writes={n: e["kv_kernel_writes"]
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
        w = weights_mellum2.make(self.cfg, seed)
        if not self.amp:
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return {name: _OnDevice(w[leaf])
                for name, leaf in self.leaf_of.items()}

    def reload(self, seed):
        super().reload(seed)
        self.kept.clear()

    def logits_of(self, sample):
        """The timed path's logits of ``sample``'s decode steps, or no
        result line: a sample whose steps are not among the calls kept
        cannot be held against the reference."""
        got = self.kept.of(sample)
        lost = [r.index for r, g in zip(sample, got) if g is None]
        if lost:
            _stop(f"the decode calls of requests {lost} are not among "
                  f"the {len(self.kept.decodes)} kept")
        return got


def useful_flops(cfg, requests, t0, t1):
    """Forward operations the window's tokens needed, a token's ACTIVE
    ones (attention within its bounds, its 8 experts, the head once a
    prompt and once a decode step): each prompt of a request whose
    first token fell in the window, and each token decoded in it
    against the context it had."""
    ops = 0.0
    for r in requests:
        times, p = r.token_times, len(r.prompt)
        if times and t0 <= times[0] < t1:
            ops += flops_mellum2.prompt_flops(cfg, p)
        for j, t in enumerate(times[1:], 1):
            if t0 <= t < t1:
                ops += flops_mellum2.decode_flops_per_token(cfg, p + j)
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


def check(ctx, seed, sample, program_logits, casts=(None,), ties=False):
    """As ``generate_hybrid.check``, against ``reference_mellum2``:
    ``{cast: numbers}`` over one exact forward — under ``None`` the
    served tokens and ``program_logits`` (``Program.logits_of``), under
    a cast the control's tokens and logits in their place.  With
    ``ties`` also, on standard error, how many (position, layer) pairs
    of the longest sampled request lie within one bfloat16 rounding of
    a tie between the eighth and the ninth expert (one more forward:
    for ``limits_why``, not for a run)."""
    from .. import reference_mellum2 as reference
    cfg, mix = ctx.cfg, ctx.mix
    w = weights_mellum2.make(cfg, seed)
    rows = [(r.prompt, r.tokens) for r in sample]
    pad_to = int(mix["check"].get("pad_to", 1024))
    by_cast = reference.token_gaps_of(
        cfg, w, rows, casts, columns(int(cfg["vocab_size"])),
        block=int(mix["check"].get("block", 1)), pad_to=pad_to)
    if ties:
        longest = list(rows[0][0]) + list(rows[0][1])
        padded = np.zeros((1, -(-len(longest) // pad_to) * pad_to), np.int32)
        padded[0, :len(longest)] = longest
        near, pairs = reference.near_ties(cfg, w, padded, len(longest))
        ctx.note("near_ties", positions=len(longest), pairs=pairs,
                 within_one_bf16_rounding=near)
    # the first token of a request is a prefill call's: its logits are
    # not among those kept, on either side
    exact = [logits[1:] for _, logits in by_cast[None]]
    out = {}
    for cast, held in by_cast.items():
        gaps = [g for g, _ in held]
        worst, at = 0.0, None
        for r, g in zip(sample, gaps):
            j = int(np.argmax(g))
            if float(g[j]) >= worst:
                worst, at = float(g[j]), f"request {r.index} token {j}"
        flat = np.concatenate(gaps).astype(np.float64)
        out[cast] = dict(
            {"worst": worst, "at": at, "mean": float(flat.mean()),
             "mean_sq": float(np.mean(flat ** 2)), "tokens": int(flat.size),
             "not_first": int((flat > 0).sum())},
            **logit_numbers(program_logits if cast is None
                            else [logits[1:] for _, logits in held], exact))
    return out


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
    # which rungs of the prefill ladder the run's groups took (ramp,
    # window and drain; the program's own counter)
    from mxtpu import obs
    snap = obs.snapshot()
    took = {(int(v["labels"]["rows"]), int(v["labels"]["bucket"])):
            int(v["value"]) for v in snap.get(
                "mxtpu_gen_prefill_rung_total", {}).get("series", [])}
    ctx.note("prefill_groups_by_rung", **{
        f"{b}x{s}": took.get((b, s), 0)
        for s in mix["prompt_buckets"] for b in mix["warm_batch_rungs"]})
    ctx.note("moe", **{name: sum(int(v["value"]) for v in snap.get(
        name, {}).get("series", []))
        for name in ("mxtpu_moe_assignments_total",
                     "mxtpu_moe_experts_touched_total")})
    prog.close()
    sample = sample_for_check(seen["mine"], seed,
                              int(mix["check"]["requests"]))
    program_logits = prog.logits_of(sample)
    ops = useful_flops(cfg, gen.requests, t0, t1)
    ctx.note("window", tokens_per_s=seen["tokens"] / (t1 - t0),
             attempted=len(seen["mine"]), failed=len(seen["failed"]),
             memory_peak_bytes=ctx.memory_peak_bytes,
             prompts_over_4096=sum(len(r.prompt) > 4096 for r in sample),
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
        end_to_end={"serve_tokens_per_s": seen["tokens"] / window_s,
                    # a per-layer reading of the serving layer
                    # (metrics/token_gap_p95_ms.py reads it from here)
                    "token_gap_p95_ms":
                    1e3 * loadgen.percentile(seen["gaps"], 95)
                    if seen["gaps"] else float("nan")},
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
    joined by commas (``fp8,top7,window_off``); with none, the near-ties
    of each seed's longest sampled request are counted too."""
    if fault is not None:
        raise ValueError(f"generate_mellum2: unknown fault {fault!r}")
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
    prog.close()
    del prog
    gc.collect()
    casts = (None,) + tuple(control.split(",") if control else ())
    out = {}
    for seed in seeds:
        got = check(ctx, seed, *samples[seed], casts, ties=not control)
        out[seed] = {"program" if cast is None else "control:" + cast:
                     checks_of(numbers) for cast, numbers in got.items()}
        # each seed's numbers as they come: a long set of readings that
        # is cut leaves what it had read
        for cast, numbers in got.items():
            ctx.note("read", seed=seed, who=cast or "program", **numbers)
    return out
