"""Driver of ``"kind": "generate"`` cells: streamed generation, served.

A copy of ``chip_smoke.serve_phase`` with its checks taken out and a
timed window put in.  The program's causal model is traced to its
incremental graph, given the benchmark's weights on the device (no
parameter file is written), loaded into a ``GenerateRunner``, warmed for
the programs this cell's traffic can reach, and put behind
``InferenceServer.register_generator``.  The window drives
``submit_generate(on_token=...)`` from the load generator; tokens are
stamped as they are streamed.  After the window a sample of the finished
requests is held against the plain reference: one forward over each
prompt with its served tokens, and how far below the reference's best
each served token lies.
"""
import gc
import hashlib
import json
import os
import time

import numpy as np

from .. import flops, harness, loadgen, weights
from . import bert_program


class _OnDevice:
    """A weight that is already on the device: the runner asks a
    parameter for ``asnumpy()`` and puts what it gets on its device,
    which for a device array is no copy."""

    def __init__(self, array):
        self._array = array

    def asnumpy(self):
        return self._array


class Program:
    """The system under test: runner, server, and the way in."""

    NAME = "gen"

    def __init__(self, ctx, seed):
        # the server's batcher uses as many of the runner's lanes as this
        # knob of the program allows (8 unless the operator sets it)
        os.environ["MXTPU_GEN_MAX_LANES"] = str(int(ctx.mix["lanes"]))
        import jax
        from mxtpu.serving import GenerateRunner, InferenceServer
        cfg, mix = ctx.cfg, ctx.mix
        self.cfg, self.mix = cfg, mix
        marks = [("imports", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        symbol, self.groups = self._graph()
        heads = cfg["num_attention_heads"]
        kv_spec = (cfg["num_hidden_layers"], 2, int(mix["lanes"]), heads,
                   int(mix["kv_capacity"]), cfg["hidden_size"] // heads)
        mark("model_and_graph")
        self.runner = GenerateRunner(
            symbol, self._params(seed), kv_spec,
            prompt_buckets=tuple(mix["prompt_buckets"]),
            device=jax.devices()[0])
        slots = self.runner.max_lanes + 1
        programs = [("prefill", (b, s)) for s in mix["prompt_buckets"]
                    for b in mix["warm_batch_rungs"]] + [("decode", (slots,))]
        mark("weights_and_runner")
        self.runner.warmup(buckets=programs)
        mark("compile_or_load")
        self._run_each_once(programs)
        mark("first_runs")
        self.server = InferenceServer()
        self.server.register_generator(
            self.NAME, self.runner, max_queue=mix.get("max_queue"))
        self.batcher = self.server._gen[self.NAME][1].batcher
        if self.batcher.max_lanes != int(mix["lanes"]):
            raise RuntimeError(
                f"generate: the server's batcher uses "
                f"{self.batcher.max_lanes} lanes, the cell states "
                f"{mix['lanes']}")
        ctx.wrap(self.runner, "decode", "decode")
        ctx.wrap(self.runner, "prefill", "prefill")
        ctx.wrap(self.batcher, "step", "batcher_step")
        ctx.note("setup", **{name + "_s": round(t - ctx.t_start if i == 0
                                                else t - marks[i - 1][1], 2)
                             for i, (name, t) in enumerate(marks)})

    def _graph(self):
        """The model's incremental graph (tokens, step, cache) and, per
        program parameter, the benchmark's leaves it is made of.  Neither
        depends on the seed, so a checkout builds them once (model,
        initializer, an eager forward, a symbolic trace: what
        ``chip_smoke._export_causal`` does) and keeps them in an ignored
        directory for its later runs; the weights are never kept."""
        import mxtpu as mx
        from mxtpu import nd
        from mxtpu import symbol as sym_mod
        cfg = self.cfg
        keep = os.path.join(harness.HERE, ".cache")
        sizes = sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, bool)))
        tag = cfg.get("name", "model") + "-" + hashlib.sha1(
            json.dumps(sizes).encode()).hexdigest()[:12]
        sym_file = os.path.join(keep, tag + "-symbol.json")
        names_file = os.path.join(keep, tag + "-params.json")
        if os.path.exists(sym_file) and os.path.exists(names_file):
            with open(sym_file) as f:
                symbol = sym_mod.load_json(f.read())
            return symbol, harness.load_json(names_file)
        mx.random.seed(0)
        net = bert_program.build_net(cfg)
        net.initialize(init="xavier")
        net.hybridize()
        net(nd.array(np.ones((2, 3), np.float32)),
            nd.array(np.zeros(2, np.float32)),
            nd.array(np.zeros(net.kv_cache_spec(2), np.float32)))
        out = net(*[sym_mod.var(f"data{i}") for i in range(3)])
        symbol = sym_mod.Group(list(out))
        groups = [[p.name, names]
                  for p, names in bert_program.param_map(net, cfg)]
        os.makedirs(keep, exist_ok=True)
        symbol.save(sym_file + ".tmp")
        with open(names_file + ".tmp", "w") as f:
            json.dump(groups, f)
        os.replace(sym_file + ".tmp", sym_file)
        os.replace(names_file + ".tmp", names_file)
        del net, out
        gc.collect()
        return symbol, groups

    def _params(self, seed):
        w = weights.make(self.cfg, seed)
        arrays = bert_program.program_arrays(self.groups, w)
        return {n: _OnDevice(a)
                for (n, _), a in zip(self.groups, arrays)}

    def _run_each_once(self, programs):
        """A program's first run pays what a compile does not (loading,
        the first transfer of its shapes): pay it in set-up."""
        r = self.runner
        kv = r.new_cache()
        for kind, shape in programs:
            if kind == "prefill":
                b, s = shape
                _, kv = r.prefill(np.ones((b, s), np.float32),
                                  np.zeros(b, np.float32),
                                  np.full(b, r.scratch_slot, np.float32), kv)
            else:
                _, kv = r.decode(np.ones((shape[0], 1), np.float32),
                                 np.zeros(shape[0], np.float32), kv)
        del kv

    def reload(self, seed):
        """Other weights into the same runner (readings over many seeds
        in one process; a run never does this)."""
        import jax
        vals = self._params(seed)
        self.runner._param_vals = tuple(
            jax.device_put(vals[n].asnumpy(), self.runner._device)
            for n in self.runner._param_names)

    def submit(self, prompt, max_tokens, on_token):
        return self.server.submit_generate(
            self.NAME, prompt, max_tokens=max_tokens, top_k=1,
            on_token=on_token)

    def close(self):
        self.server.close()


# ----------------------------------------------------------------------
# from stamped tokens to the end-to-end numbers
# ----------------------------------------------------------------------
def reduce_window(requests, t0, t1):
    """What the window's users saw.  A request belongs to the window
    when it was due inside it; a token or a gap belongs to it when it
    (the later token of the gap) was streamed inside it, whichever
    request it is of."""
    mine = [r for r in requests if t0 <= r.due < t1]
    failed = [r for r in mine
              if r.error or len(r.tokens) != r.max_tokens]
    tokens, gaps = 0, []
    for r in requests:
        times = r.token_times
        tokens += sum(1 for t in times if t0 <= t < t1)
        gaps += [b - a for a, b in zip(times, times[1:]) if t0 <= b < t1]
    return {"mine": mine, "failed": failed, "tokens": tokens, "gaps": gaps}


def useful_flops(cfg, requests, t0, t1):
    """Forward operations the window's tokens needed: each prompt token
    of a request whose first token fell in the window, and each token
    decoded in it against the context it had."""
    ops = 0.0
    for r in requests:
        times, p = r.token_times, len(r.prompt)
        if times and t0 <= times[0] < t1:
            ops += p * flops.forward_flops_per_token(cfg, p, causal=True)
        for j, t in enumerate(times[1:], 1):
            if t0 <= t < t1:
                ops += flops.decode_flops_per_token(cfg, p + j)
    return ops


def sample_for_check(requests, seed, n):
    """The finished requests to hold against the reference: the longest,
    and the rest drawn from the seed."""
    done = [r for r in requests
            if not r.error and len(r.tokens) == r.max_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = loadgen.rng_for(seed, 3)
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def check(cfg, seed, sample, cast=None):
    """One reference forward over each sampled prompt with its served
    tokens; per served token, how far its logit lies below the
    reference's best (0 where it is the best).  Returns the widest such
    gap, the mean and the mean square over all the sample's tokens (a
    lower precision has both more tokens off the best and wider gaps, so
    the mean square parts the program from it furthest), and what else a
    reader of the log may want."""
    from .. import reference
    if not sample:
        nan = float("nan")
        return {"worst": nan, "at": "no finished request to check",
                "mean": nan, "mean_sq": nan, "tokens": 0, "not_first": 0}
    w = weights.make(cfg, seed)
    rows = [(r.prompt, r.tokens) for r in sample]
    gaps = reference.token_gaps(cfg, w, rows, cast=cast)
    worst, at = 0.0, None
    for r, g in zip(sample, gaps):
        j = int(np.argmax(g))
        if float(g[j]) >= worst:
            worst, at = float(g[j]), f"request {r.index} token {j}"
    flat = np.concatenate(gaps).astype(np.float64)
    return {"worst": worst, "at": at, "mean": float(flat.mean()),
            "mean_sq": float(np.mean(flat ** 2)),
            "tokens": int(flat.size), "not_first": int((flat > 0).sum())}


def checks_of(c):
    n = f"{c['tokens']} tokens, {c['not_first']} not the reference's first"
    return {"served_token_gap": (c["worst"], c["at"]),
            "served_token_gap_mean": (c["mean"], n),
            "served_token_gap_sq": (c["mean_sq"], "mean of the squares")}


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def serve_window(ctx, prog, seed, seconds, tamper=None):
    cfg, mix = ctx.cfg, ctx.mix
    submit = prog.submit if tamper is None else tamper(prog.submit)
    batcher = prog.batcher
    gen = loadgen.LoadGen(mix, seed, cfg["vocab_size"], submit,
                          probe=lambda: (batcher.depth, batcher.free_lanes()))
    t0, t1 = gen.run(seconds, on_open=ctx.open_window)
    ctx.close_window(t0, t1)
    gen.drain(t1 + float(mix.get("drain_s", 60.0)) - time.perf_counter())
    return gen, reduce_window(gen.requests, t0, t1)


def run(ctx, tamper=None):
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    prog = Program(ctx, seed)
    gen, seen = serve_window(ctx, prog, seed, ctx.seconds, tamper)
    t0, t1 = ctx.window
    ctx.read_memory()
    prog.close()
    sample = sample_for_check(seen["mine"], seed,
                              int(mix["check"]["requests"]))
    ops = useful_flops(cfg, gen.requests, t0, t1)
    late = gen.lateness
    if late:
        ctx.note("loadgen", sent=len(late),
                 late_p50_ms=1e3 * loadgen.percentile(late, 50),
                 late_max_ms=1e3 * max(late))
    if gen.probes:
        # what each request found as it was sent: the queue and the lanes
        n = len(gen.probes)
        ctx.note("at_each_send", sends=n,
                 found_a_queue_share=sum(1 for d, _ in gen.probes if d) / n,
                 found_no_free_lane_share=sum(
                     1 for _, f in gen.probes if not f) / n,
                 mean_busy_lanes=int(mix["lanes"]) - sum(
                     f for _, f in gen.probes) / n)
    del prog
    gc.collect()

    checked = check(cfg, seed, sample)
    ctx.note("check", requests=len(sample), **checked)
    window_s = t1 - t0
    e2e = {"serve_tokens_per_s": seen["tokens"] / window_s,
           "token_gap_p95_ms": 1e3 * loadgen.percentile(seen["gaps"], 95)
           if seen["gaps"] else float("nan")}
    return harness.Outcome(
        attempted=len(seen["mine"]), failed=len(seen["failed"]),
        end_to_end=e2e,
        checks=checks_of(checked), limits=mix["limits"],
        facts={"window_s": window_s, "useful_flops": ops,
               "slots": int(mix["lanes"]) + 1,
               "kv_capacity": int(mix["kv_capacity"])})


def readings(ctx, seeds, control=None, fault=None):
    """``{seed: {who: checks}}`` on several seeds in one process: one
    runner, other weights for each seed, a short window at the cell's
    own load, then the reference.  With ``control`` also the same
    prompts and tokens with, at each position, the token the lower
    precision puts first in the served token's place."""
    if fault is not None:
        raise ValueError(f"generate: unknown fault {fault!r}")
    cfg, mix = ctx.cfg, ctx.mix
    prog = Program(ctx, seeds[0])
    samples = {}
    for seed in seeds:
        prog.reload(seed)
        _, seen = serve_window(ctx, prog, seed, ctx.seconds)
        samples[seed] = sample_for_check(
            seen["mine"], seed, int(mix["check"]["requests"]))
        ctx.note("readings", seed=seed, attempted=len(seen["mine"]),
                 failed=len(seen["failed"]), sampled=len(samples[seed]),
                 tokens_per_s=seen["tokens"] / ctx.seconds,
                 gap_p95_ms=1e3 * loadgen.percentile(seen["gaps"], 95)
                 if seen["gaps"] else None)
    prog.close()
    del prog
    gc.collect()
    out = {}
    for seed in seeds:
        out[seed] = {"program": checks_of(check(cfg, seed, samples[seed]))}
        if control is not None:
            out[seed]["control:" + control] = checks_of(
                check(cfg, seed, samples[seed], cast=control))
    return out
