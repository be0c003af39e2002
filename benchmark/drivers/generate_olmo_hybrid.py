"""Driver of ``"kind": "generate_olmo_hybrid"`` cells: a hybrid decoder
of Gated DeltaNet and full-attention layers, streamed generation,
served.

The window, the load generator, the sample held against the reference,
the numbers ``correct`` compares and the rule of ``generate_hybrid`` —
never a result line the driver cannot stand behind: with no request due
and finished inside the window, a NaN among the numbers compared, or
(traced) a metric of the cell that reads no value, it exits non-zero
with a message on standard error — are ``drivers/generate.py``'s and
``drivers/generate_hybrid.py``'s; what differs is the model's
configuration (``model_type: olmo_hybrid``), the weights
(``weights_olmo_hybrid``), the reference (``reference_olmo_hybrid``) and
the counts (``flops_olmo_hybrid``).

No model code lives here.  The first import below is of a name that
only a program with the Gated DeltaNet mixer has: on a program without
it the driver fails there, at once, with no result line.
"""
from mxtpu.models.hybrid import GatedDeltaNetMixer  # noqa: F401, I001

import gc
import os
import time

from .. import flops_olmo_hybrid, harness, weights_olmo_hybrid
from . import generate_hybrid
from .generate import _OnDevice, checks_of, sample_for_check, serve_window
from .generate_hybrid import _read_every_metric, _stop, _whole


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
        out = net(*[sym_mod.var(f"data{i}") for i in range(6)])
        leaves = net.named_leaves()
        # program parameter name -> the benchmark's leaf it is given
        self.leaf_of = {p.name: leaf for leaf, p in leaves.items()}
        if {leaf: tuple(p.shape) for leaf, p in leaves.items()} \
                != weights_olmo_hybrid.leaf_shapes(cfg):
            raise RuntimeError("generate_olmo_hybrid: the program's leaves "
                               "are not the reference's")
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
                f"generate_olmo_hybrid: the cell warms rungs {rungs}, the "
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
                f"generate_olmo_hybrid: the server's batcher uses "
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
        w = weights_olmo_hybrid.make(self.cfg, seed)
        if not self.amp:
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return {name: _OnDevice(w[leaf])
                for name, leaf in self.leaf_of.items()}


def useful_flops(cfg, requests, t0, t1):
    """Forward operations the window's tokens needed: each prompt token
    of a request whose first token fell in the window, and each token
    decoded in it against the context it had."""
    ops = 0.0
    for r in requests:
        times, p = r.token_times, len(r.prompt)
        if times and t0 <= times[0] < t1:
            ops += p * flops_olmo_hybrid.forward_flops_per_token(cfg, p)
        for j, t in enumerate(times[1:], 1):
            if t0 <= t < t1:
                ops += flops_olmo_hybrid.decode_flops_per_token(cfg, p + j)
    return ops


def check(ctx, seed, sample, casts=(None,)):
    """As ``generate_hybrid.check``, against ``reference_olmo_hybrid``:
    ``{cast: numbers}``, the served tokens under ``None``, over one
    exact forward; and on standard error the share of the longest
    sampled request's positions at which ``beta > 1``."""
    import numpy as np
    from .. import reference_olmo_hybrid as reference
    cfg, mix = ctx.cfg, ctx.mix
    w = weights_olmo_hybrid.make(cfg, seed)
    rows = [(r.prompt, r.tokens) for r in sample]
    by_cast = reference.token_gaps_of(
        cfg, w, rows, casts, block=int(mix["check"].get("block", 4)),
        pad_to=int(mix["check"].get("pad_to", 256)))
    longest = np.asarray(list(rows[0][0]) + list(rows[0][1]), np.int32)
    ctx.note("beta", positions=int(longest.size),
             share_above_one=reference.beta_share_above_one(
                 cfg, w, longest[None]))
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
    # window and drain; the program's own counter): a rung that stays at
    # 0 run after run is one a later PR can stop warming
    from mxtpu import obs
    took = {(int(v["labels"]["rows"]), int(v["labels"]["bucket"])):
            int(v["value"]) for v in obs.snapshot().get(
                "mxtpu_gen_prefill_rung_total", {}).get("series", [])}
    ctx.note("prefill_groups_by_rung", **{
        f"{b}x{s}": took.get((b, s), 0)
        for s in mix["prompt_buckets"] for b in mix["warm_batch_rungs"]})
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
    if not seen["tokens"]:
        _stop("no token was streamed inside the window")

    checked = check(ctx, seed, sample)[None]
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
    joined by commas (``delta_bfloat16,fp8``)."""
    if fault is not None:
        raise ValueError(f"generate_olmo_hybrid: unknown fault {fault!r}")
    mix = ctx.mix
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
        got = check(ctx, seed, samples[seed], casts)
        out[seed] = {"program" if cast is None else "control:" + cast:
                     checks_of(numbers) for cast, numbers in got.items()}
    return out
