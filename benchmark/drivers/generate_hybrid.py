"""Driver of ``"kind": "generate_hybrid"`` cells: a hybrid state-space /
attention decoder, streamed generation, served.

The window, the load generator, the sample held against the reference
and the numbers ``correct`` compares are ``drivers/generate.py``'s; what
differs is the program (``mxtpu.models.hybrid.HybridDecoderModel`` behind
a ``GenerateRunner`` with a state spec: a bfloat16 KV table beside
float32 recurrent state, bfloat16 weights under ``amp``), the weights
(``weights_granite``), the reference (``reference_granite``) and the
counts (``flops_granite``).  The model's graph is traced anew in every
run (symbolically: no weight is initialised for it) and nothing is kept
in the checkout.

No model code lives here: a program that lacks the model fails at this
driver's first import of it, with no result line.  And the driver never
hands back a line it cannot stand behind: with no request due and
finished inside the window, a NaN among the numbers compared or
reported, or (traced) a metric of the cell that reads no value, it exits
non-zero with a message on standard error.
"""
import gc
import math
import os
import sys
import time

from .. import flops_granite, harness, weights_granite
from .generate import (_OnDevice, checks_of, sample_for_check,
                       serve_window)


def _stop(why):
    sys.exit(f"bench: generate_hybrid: no result line: {why}")


class Program:
    """The system under test: runner, server, and the way in."""

    NAME = "gen"

    def __init__(self, ctx, seed):
        os.environ["MXTPU_GEN_MAX_LANES"] = str(int(ctx.mix["lanes"]))
        import jax
        from mxtpu import symbol as sym_mod
        from mxtpu.models.hybrid import HybridDecoderModel
        from mxtpu.serving import GenerateRunner, InferenceServer
        cfg, mix = ctx.cfg, ctx.mix
        self.cfg, self.mix = cfg, mix
        marks = [("imports", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        net = HybridDecoderModel.from_config(cfg)
        out = net(*[sym_mod.var(f"data{i}") for i in range(6)])
        leaves = net.named_leaves()
        # program parameter name -> the benchmark's leaf it is given
        self.leaf_of = {p.name: leaf for leaf, p in leaves.items()}
        if {leaf: tuple(p.shape) for leaf, p in leaves.items()} \
                != weights_granite.leaf_shapes(cfg):
            raise RuntimeError("generate_hybrid: the program's leaves are "
                               "not the reference's")
        mark("model_and_graph")
        self.amp = cfg.get("param_dtype") == "bfloat16"
        rungs = [int(b) for b in mix["warm_batch_rungs"]]
        self.runner = GenerateRunner(
            sym_mod.Group(list(out)), self._params(seed),
            net.state_spec(int(mix["lanes"]), int(mix["kv_capacity"]),
                           kv_dtype=cfg.get("kv_cache_dtype", "float32")),
            prompt_buckets=tuple(mix["prompt_buckets"]),
            max_prefill_batch=max(rungs), amp=self.amp,
            device=jax.devices()[0])
        del net, out
        slots = self.runner.max_lanes + 1
        programs = [("prefill", (b, s)) for s in mix["prompt_buckets"]
                    for b in rungs] + [("decode", (slots,))]
        if sorted(rungs) != list(self.runner.batch_buckets):
            raise RuntimeError(
                f"generate_hybrid: the cell warms rungs {rungs}, the "
                f"runner's ladder is {self.runner.batch_buckets}")
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
                f"generate_hybrid: the server's batcher uses "
                f"{self.batcher.max_lanes} lanes, the cell states "
                f"{mix['lanes']}")
        ctx.wrap(self.runner, "decode", "decode")
        ctx.wrap(self.runner, "prefill", "prefill")
        ctx.wrap(self.batcher, "step", "batcher_step")
        from mxtpu import analysis
        ctx.note("programs", state_bytes=self.runner.state_bytes(),
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
        w = weights_granite.make(self.cfg, seed)
        if not self.amp:
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return {name: _OnDevice(w[leaf])
                for name, leaf in self.leaf_of.items()}

    def _run_each_once(self, programs):
        """A program's first run pays what a compile does not: pay it in
        set-up (padding rows only: scratch slot, nothing valid)."""
        import numpy as np
        r = self.runner
        kv = r.new_cache()
        for kind, shape in programs:
            if kind == "prefill":
                b, s = shape
                _, kv = r.prefill(np.ones((b, s), np.float32),
                                  np.zeros(b, np.float32),
                                  np.full(b, r.scratch_slot, np.float32), kv,
                                  np.zeros(b, np.float32))
            else:
                _, kv = r.decode(np.ones((shape[0], 1), np.float32),
                                 np.zeros(shape[0], np.float32), kv,
                                 np.zeros(shape[0], np.float32))
        del kv

    def reload(self, seed):
        """Other weights into the same runner (readings over many seeds
        in one process; a run never does this).  The old ones go first:
        two sets do not fit beside the tables."""
        self.runner._param_vals = ()
        gc.collect()
        vals = self._params(seed)
        self.runner._param_vals = tuple(
            vals[n].asnumpy() for n in self.runner._param_names)

    def submit(self, prompt, max_tokens, on_token):
        return self.server.submit_generate(
            self.NAME, prompt, max_tokens=max_tokens, top_k=1,
            on_token=on_token)

    def close(self):
        self.server.close()


def useful_flops(cfg, requests, t0, t1):
    """Forward operations the window's tokens needed: each prompt token
    of a request whose first token fell in the window, and each token
    decoded in it against the context it had."""
    ops = 0.0
    for r in requests:
        times, p = r.token_times, len(r.prompt)
        if times and t0 <= times[0] < t1:
            ops += p * flops_granite.forward_flops_per_token(cfg, p)
        for j, t in enumerate(times[1:], 1):
            if t0 <= t < t1:
                ops += flops_granite.decode_flops_per_token(cfg, p + j)
    return ops


def check(cfg, mix, seed, sample, casts=(None,)):
    """As ``generate.check``, against ``reference_granite``: ``{cast:
    numbers}``, the served tokens under ``None``, over one exact
    forward."""
    import numpy as np
    from .. import reference_granite
    w = weights_granite.make(cfg, seed)
    rows = [(r.prompt, r.tokens) for r in sample]
    by_cast = reference_granite.token_gaps_of(
        cfg, w, rows, casts, block=int(mix["check"].get("block", 8)),
        pad_to=int(mix["check"].get("pad_to", 256)))
    out = {}
    for cast, gaps in by_cast.items():
        worst, at = 0.0, None
        for r, g in zip(sample, gaps):
            j = int(np.argmax(g))
            if float(g[j]) >= worst:
                worst, at = float(g[j]), f"request {r.index} token {j}"
        flat = np.concatenate(gaps).astype(np.float64)
        out[cast] = {"worst": worst, "at": at, "mean": float(flat.mean()),
                     "mean_sq": float(np.mean(flat ** 2)),
                     "tokens": int(flat.size),
                     "not_first": int((flat > 0).sum())}
    return out


def _whole(checks):
    for name, (value, _) in checks.items():
        if not isinstance(value, float) or not math.isfinite(value):
            _stop(f"{name} = {value!r} is no number to compare")


def _read_every_metric(ctx, outcome):
    """Traced runs: every per-layer metric of the cell must read a
    value.  The trace is read here once and kept for ``run.py``, which
    asks ``trace_reduce.load`` for the same file next and is handed the
    same object, readers' memo and all."""
    import jax
    from .. import run as run_mod
    from .. import trace_reduce
    path = trace_reduce.find_xplane(ctx.trace_dir)
    trace = trace_reduce.load(path)
    load = trace_reduce.load
    trace_reduce.load = lambda p: trace if p == path else load(p)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    name = ctx.cell["name"]
    reported = {m["name"] for m in run_mod.metrics_of(bench, "end_to_end",
                                                      name)}
    reading = run_mod.Reading(
        ctx, outcome, trace,
        harness.peaks_for(jax.devices()[0].device_kind))
    silent = {}
    for m in run_mod.metrics_of(bench, "per_layer", name, reported):
        value = run_mod.read_metric(m["name"], reading)
        if value is None or not math.isfinite(value):
            silent[m["name"]] = value
    if silent:
        _stop(f"traced run: metrics of the cell with no value: {silent}")


def run(ctx, tamper=None):
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    prog = Program(ctx, seed)
    gen, seen = serve_window(ctx, prog, seed, ctx.seconds, tamper)
    t0, t1 = ctx.window
    ctx.read_memory()
    hlo_text = prog.runner.program_artifact()[0] if ctx.trace else None
    prog.close()
    sample = sample_for_check(seen["mine"], seed,
                              int(mix["check"]["requests"]))
    ops = useful_flops(cfg, gen.requests, t0, t1)
    ctx.note("window", tokens_per_s=seen["tokens"] / (t1 - t0),
             attempted=len(seen["mine"]), failed=len(seen["failed"]),
             memory_peak_bytes=ctx.memory_peak_bytes)
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

    checked = check(cfg, mix, seed, sample)[None]
    ctx.note("check", requests=len(sample), **checked)
    checks = checks_of(checked)
    _whole(checks)
    window_s = t1 - t0
    e2e = {"serve_tokens_per_s": seen["tokens"] / window_s}
    if not seen["tokens"]:
        _stop("no token was streamed inside the window")
    outcome = harness.Outcome(
        attempted=len(seen["mine"]), failed=len(seen["failed"]),
        end_to_end=e2e, checks=checks, limits=mix["limits"],
        facts={"window_s": window_s, "useful_flops": ops,
               "slots": int(mix["lanes"]) + 1,
               "kv_capacity": int(mix["kv_capacity"]),
               "hlo_text": hlo_text})
    if ctx.trace:
        _read_every_metric(ctx, outcome)
    return outcome


def readings(ctx, seeds, control=None, fault=None):
    """``{seed: {who: checks}}`` on several seeds in one process, as
    ``generate.readings``; ``control`` may name several controls, joined
    by commas (``ssm_bfloat16,fp8``)."""
    if fault is not None:
        raise ValueError(f"generate_hybrid: unknown fault {fault!r}")
    cfg, mix = ctx.cfg, ctx.mix
    prog = Program(ctx, seeds[0])
    samples = {}
    for i, seed in enumerate(seeds):
        if i:
            prog.reload(seed)
        # the generator holds the program's way in: it is not kept
        seen = serve_window(ctx, prog, seed, ctx.seconds)[1]
        samples[seed] = sample_for_check(
            seen["mine"], seed, int(mix["check"]["requests"]))
        ctx.note("readings", seed=seed, attempted=len(seen["mine"]),
                 failed=len(seen["failed"]), sampled=len(samples[seed]),
                 tokens_per_s=seen["tokens"] / ctx.seconds)
        if not samples[seed]:
            _stop(f"seed {seed}: no request due in the window finished")
    prog.close()
    del prog
    gc.collect()
    casts = (None,) + tuple(control.split(",") if control else ())
    out = {}
    for seed in seeds:
        got = check(cfg, mix, seed, samples[seed], casts)
        out[seed] = {"program" if cast is None else "control:" + cast:
                     checks_of(numbers) for cast, numbers in got.items()}
    return out
