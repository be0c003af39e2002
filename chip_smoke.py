"""chip_smoke.py — the quickest proof that mxtpu still starts on the chip.

    python chip_smoke.py              # one TPU chip: train + serve
    python chip_smoke.py --chips 4    # four chips: ZeRO-1 + fleet, nothing else

One process, no platform default set here: JAX picks the device, and
anything but a TPU is an error (non-zero exit, no result line).  Every
phase drives the entry points a user would call at the full published
width of BERT-Large (24 layers, 1024 hidden, 16 heads, 4096 FFN, vocab
30522, 512 positions) with weights and tokens made from ``--seed``:

* train — ``bert_large`` through ``parallel.build_train_step`` (adam,
  bf16 compute) at batch 8 x seq 512: single steps, then ``run_steps``
  scans.  Losses finite and falling, parameters on the chip, and the
  compiled program's own text holds the Pallas custom calls of flash
  attention and of the fused residual-LayerNorm epilogue.
* serve — a causal decoder of the same widths exported, loaded by
  ``GenerateRunner.from_export``, warmed, and driven through
  ``GenerateBatcher.submit``: concurrent greedy requests of different
  prompt lengths, streamed.  Every request completes; the per-step
  logits of the KV-cache path agree with the repo's
  re-prefill-every-token baseline within ``LOGIT_RTOL``, and every
  greedy token is the baseline's argmax or within twice that tolerance
  of it (random weights at the chip's default matmul precision can flip
  a near-tie, which is not a fault; a wrong token sits far below).
* --chips 4 only: the same BERT-Large under ZeRO-1 on a dp=4 mesh at
  global batch 32 x seq 512 (optimizer state and parameters spread
  over the four devices, reduce-scatter + all-gather in the program),
  then at global batch 8 against a one-device step from the same seed
  (losses within ``LOSS_RTOL``, optimizer state per device ~1/4 — one
  device cannot hold 32 x 512), and a four-replica ``FleetRouter``
  with one ``ModelRunner`` per chip.

A phase that fails raises, so the exit code is non-zero and the result
line is never printed.  The LAST line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``; everything
else worth knowing is on the lines before it.  This is a smoke run: its
seconds say the system runs, they are not a benchmark.

The phases take their sizes as a ``Sizes`` value so that
``tests/test_chip_smoke.py`` can rehearse the same code at a toy size
on the CPU; ``main()`` only ever passes ``FULL``.
"""
import argparse
import collections
import gc
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

# Logits of the KV-cache path vs the re-prefill baseline: allowed
# |difference| as a share of the largest |logit| of the baseline.  The
# two paths run differently shaped programs at the chip's default
# matmul precision (bf16 passes for f32 operands).
LOGIT_RTOL = 2e-2
# ZeRO-1 on four devices vs one device, same seed, no dropout: relative
# difference of each step's loss (bf16 compute; the gradient sum is
# taken in a different order).
LOSS_RTOL = 1e-2

Sizes = collections.namedtuple("Sizes", [
    "vocab", "units", "hidden", "layers", "heads", "max_length",
    "batch", "seq", "steps", "scan_steps",          # train
    "compare_batch",        # ZeRO-1 vs one device: their global batch
    "lanes", "prompt_buckets", "prompt_lens", "max_tokens",   # serve
    "fleet_seq", "fleet_batch", "fleet_requests",   # fleet replicas
])

# BERT-Large, no cut in width or depth.  lanes=7: the decode step then
# runs over 8 slots (7 lanes + the scratch slot), a whole sublane tile,
# so its LayerNorm epilogues are the Pallas kernel and not the lax
# composite that a slot count off the tile falls back to.
FULL = Sizes(vocab=30522, units=1024, hidden=4096, layers=24, heads=16,
             max_length=512, batch=8, seq=512, steps=5, scan_steps=4,
             compare_batch=8, lanes=7, prompt_buckets=(16, 64),
             prompt_lens=(6, 13, 21, 37), max_tokens=24,
             fleet_seq=128, fleet_batch=2, fleet_requests=16)


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, **facts):
    print(f"chip_smoke: {phase}: " + " ".join(
        f"{k}={v}" for k, v in facts.items()), flush=True)


def pallas_calls(hlo_text):
    """``{kernel file: count}`` of the TPU Pallas custom calls in a
    compiled program, attributed by the source site each call was
    traced from — read from the program text, not from the dispatch
    predicate that was supposed to pick them."""
    from mxtpu.analysis import hlo
    from mxtpu.analysis.dtypeflow import instr_site
    prog = hlo.parse_hlo(hlo.inline_source_sites(hlo_text))
    counts = collections.Counter()
    for instr in prog.all_instructions():
        if instr.target == "tpu_custom_call":
            site = instr_site(instr)[1]
            counts[os.path.basename(site.split(":")[0]) or "?"] += 1
    return dict(counts)


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _bert(sizes, dropout, **kw):
    from mxtpu.models.transformer import BERTModel, bert_large
    if sizes is FULL and not kw:
        return bert_large(vocab_size=sizes.vocab,
                          max_length=sizes.max_length, dropout=dropout)
    return BERTModel(sizes.vocab, sizes.units, sizes.hidden,
                     sizes.layers, sizes.heads,
                     max_length=sizes.max_length, dropout=dropout, **kw)


def _mlm_step(sizes, net, **kw):
    """The training step exactly as bench.py's BERT rows build it."""
    from mxtpu import parallel
    from mxtpu.gluon import loss as gloss

    def mlm_loss(pred, y):
        return gloss.SoftmaxCrossEntropyLoss()(
            pred.reshape((-1, sizes.vocab)), y.reshape((-1,)))

    # cast_batch=False: token ids must not be rounded through bf16
    return parallel.build_train_step(
        net, mlm_loss, "adam", {"learning_rate": 1e-4},
        compute_dtype="bfloat16", cast_batch=False, **kw)


def _tokens(sizes, seed, batch):
    from mxtpu import nd
    rng = np.random.RandomState(seed)
    return nd.array(rng.randint(0, sizes.vocab, (batch, sizes.seq))
                    .astype(np.float32))


# ----------------------------------------------------------------------
# train
# ----------------------------------------------------------------------
def train_phase(sizes, seed, device):
    """BERT through the compiled train step on ``device``; returns the
    facts ``main`` holds the chip run to."""
    import jax
    import mxtpu as mx

    batch = sizes.batch
    while True:
        mx.random.seed(seed)
        net = _bert(sizes, dropout=0.1)
        net.initialize(init="xavier")
        step = _mlm_step(sizes, net)
        toks = _tokens(sizes, seed, batch)
        try:
            t0 = time.perf_counter()
            text = step.hlo_text(toks, toks)      # builds THE executable
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            first = float(step(toks, toks).asnumpy())
            first_s = time.perf_counter() - t0
            break
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e) or batch == 1:
                raise
            say("train", lowered_batch=f"{batch}->{batch // 2}",
                why="step did not fit device memory")
            del net, step, toks
            gc.collect()
            batch //= 2
    say("train", batch=batch, seq=sizes.seq, compile_seconds=round(
        compile_s, 2), first_step_seconds=round(first_s, 3))

    losses, step_s = [first], []
    for _ in range(sizes.steps - 1):
        t0 = time.perf_counter()
        losses.append(float(step(toks, toks).asnumpy()))  # blocks
        step_s.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    scan = step.run_steps(toks, toks, sizes.scan_steps,
                          reuse_batch=True).asnumpy()
    scan_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    scan2 = step.run_steps(toks, toks, sizes.scan_steps,
                           reuse_batch=True).asnumpy()
    scan_s = time.perf_counter() - t0
    losses += [float(v) for v in scan] + [float(v) for v in scan2]
    check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall: {losses}")

    platforms = {d.platform for p in net.collect_params().values()
                 for d in p.data().data.devices()}
    mem = step.memory_analysis(toks, toks)
    facts = {
        "batch": batch, "losses": [round(v, 4) for v in losses],
        "param_platforms": sorted(platforms),
        "pallas_calls": pallas_calls(text),
    }
    say("train", step_seconds=round(statistics.median(step_s), 4),
        scan_compile_and_run_seconds=round(scan_cold_s, 2),
        scan_step_seconds=round(scan_s / sizes.scan_steps, 4),
        tokens_per_step=batch * sizes.seq)
    say("train", losses=facts["losses"])
    say("train", pallas_calls=facts["pallas_calls"],
        param_platforms=facts["param_platforms"],
        program_hbm_peak_bytes=mem.get("hbm_peak"),
        opt_state_bytes=mem.get("opt_state_bytes"),
        peak_bytes_in_use=peak_bytes(device))
    return facts


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------
def _export_causal(sizes, seed, workdir):
    """Trace the incremental (tokens, step, cache) signature of a
    causal BERT and export it, as bench.py's generation row does."""
    import mxtpu as mx
    from mxtpu import nd
    mx.random.seed(seed)
    net = _bert(sizes, dropout=0.0, use_token_type=False, causal=True)
    net.initialize(init="xavier")
    net.hybridize()
    rng = np.random.RandomState(seed)
    tokens = nd.array(rng.randint(0, sizes.vocab, (2, 3))
                      .astype(np.float32))
    net(tokens, nd.array(np.zeros(2, np.float32)),
        nd.array(np.zeros(net.kv_cache_spec(2), np.float32)))
    return net, net.export(os.path.join(workdir, "genbert"))


def _kv_path_logits(runner, prompt, stream):
    """Per-step logits of the KV-cache path for one request, fed its
    own stream: one prefill of the prompt into lane 0, then one decode
    step per emitted token."""
    slots = runner.max_lanes + 1
    kv = runner.new_cache()
    b = runner.batch_rung_for(1)
    tok = np.zeros((b, runner.prompt_bucket_for(len(prompt))),
                   np.float32)
    tok[0, :len(prompt)] = prompt
    lanes = np.full(b, runner.scratch_slot, np.float32)
    lanes[0] = 0
    length = np.zeros(b, np.float32)
    length[0] = len(prompt)
    logits, kv = runner.prefill(tok, np.zeros(b, np.float32), lanes, kv,
                                length)
    out = [np.asarray(logits[0, 0])]
    for t, last in enumerate(stream[:-1]):
        tokens = np.zeros((slots, 1), np.float32)
        steps = np.zeros(slots, np.float32)
        tokens[0, 0] = last
        steps[0] = len(prompt) + t
        logits, kv = runner.decode(tokens, steps, kv)
        out.append(np.asarray(logits)[0, 0])
    return np.stack(out)


def _reprefill_logits(runner, prompt, stream):
    """The repo's naive baseline (bench.py ``serving_generate``): a
    full prefill of the growing sequence for every token, into the
    scratch slot of a cache that is never read."""
    kv = runner.new_cache()
    b = runner.batch_rung_for(1)
    out = []
    for t in range(len(stream)):
        seq = list(prompt) + list(stream[:t])
        tok = np.zeros((b, runner.prompt_bucket_for(len(seq))),
                       np.float32)
        tok[0, :len(seq)] = seq
        length = np.zeros(b, np.float32)
        length[0] = len(seq)
        logits, kv = runner.prefill(
            tok, np.zeros(b, np.float32),
            np.full(b, runner.scratch_slot, np.float32), kv, length)
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


def serve_phase(sizes, seed, device, workdir):
    from mxtpu.serving import GenerateBatcher, GenerateRunner

    t0 = time.perf_counter()
    net, (sym_file, param_file) = _export_causal(sizes, seed, workdir)
    kv_spec = net.kv_cache_spec(sizes.lanes, sizes.max_length)
    del net
    gc.collect()
    runner = GenerateRunner.from_export(
        sym_file, param_file, kv_spec,
        prompt_buckets=sizes.prompt_buckets, device=device)
    export_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    runner.warmup()
    warmup_s = time.perf_counter() - t0
    say("serve", export_and_load_seconds=round(export_s, 2),
        warmup_seconds=round(warmup_s, 2),
        programs=runner.num_compiled(), lanes=sizes.lanes,
        kv_table_shape=kv_spec)

    rng = np.random.RandomState(seed + 1)
    prompts = [[int(t) for t in rng.randint(1, sizes.vocab, n)]
               for n in sizes.prompt_lens]
    batcher = GenerateBatcher(runner)
    streamed = [[] for _ in prompts]
    marks = [[] for _ in prompts]
    t_submit = time.perf_counter()
    reqs = [batcher.submit(
        p, max_tokens=sizes.max_tokens,
        on_token=lambda tok, idx, i=i: (
            streamed[i].append((idx, tok)),
            marks[i].append(time.perf_counter())))
        for i, p in enumerate(prompts)]
    n_steps = 0
    while not batcher.drain():
        batcher.step()
        n_steps += 1
        check(n_steps <= 4 * sizes.max_tokens * len(prompts),
              "serve: batcher did not drain")
    elapsed = time.perf_counter() - t_submit
    batcher.close()

    streams = []
    for i, r in enumerate(reqs):
        got = r.result(0)
        check(len(got) == sizes.max_tokens and
              r.finish_reason == "length",
              f"serve: request {i} ended {r.finish_reason!r} after "
              f"{len(got)} tokens")
        check(streamed[i] == list(enumerate(got)),
              f"serve: request {i} streamed {streamed[i]} but "
              f"returned {got}")
        streams.append(got)
    gaps = [b - a for m in marks for a, b in zip(m, m[1:])]
    say("serve", requests=len(reqs), prompt_lens=sizes.prompt_lens,
        new_tokens_each=sizes.max_tokens, batcher_steps=n_steps,
        seconds=round(elapsed, 3),
        first_token_seconds=[round(m[0] - t_submit, 3) for m in marks],
        median_token_gap_seconds=round(statistics.median(gaps), 4))

    worst, exact, total = 0.0, 0, 0
    for i, (prompt, stream) in enumerate(zip(prompts, streams)):
        kv_logits = _kv_path_logits(runner, prompt, stream)
        ref = _reprefill_logits(runner, prompt, stream)
        check(np.isfinite(kv_logits).all() and np.isfinite(ref).all(),
              f"serve: non-finite logits for request {i}")
        tol = LOGIT_RTOL * max(1.0, float(np.abs(ref).max()))
        diff = float(np.abs(kv_logits - ref).max())
        worst = max(worst, diff / tol)
        check(diff <= tol,
              f"serve: request {i}: KV-path logits differ from the "
              f"re-prefill baseline by {diff:.4g} > {tol:.4g}")
        for t, tok in enumerate(stream):
            # logits within tol of each other can only swap two
            # tokens whose baseline logits are within 2 tol: the
            # served token must be the baseline's best or that close
            # to it (a wrong token sits far below, so this is never
            # vacuous however many near-ties random weights give)
            best = int(np.argmax(ref[t]))
            short = float(ref[t, best] - ref[t, tok])
            check(short <= 2 * tol,
                  f"serve: request {i} token {t}: served {tok}, whose "
                  f"baseline logit is {short:.4g} below that of "
                  f"{best} (> {2 * tol:.4g})")
            exact += tok == best
            total += 1
    text, mem = runner.program_artifact()         # THE decode step
    facts = {"streams": streams, "pallas_calls": pallas_calls(text),
             "weight_platforms": sorted(
                 {d.platform for w in runner.weight_buffers()
                  for d in w.devices()})}
    say("serve", logit_rtol=LOGIT_RTOL,
        worst_logit_diff_over_tol=round(worst, 4),
        tokens_checked=total, equal_to_baseline_argmax=exact)
    say("serve", decode_pallas_calls=facts["pallas_calls"],
        weight_platforms=facts["weight_platforms"],
        decode_hbm_peak_bytes=(mem or {}).get("hbm_peak"),
        peak_bytes_in_use=peak_bytes(device))
    return facts


# ----------------------------------------------------------------------
# --chips 4: ZeRO-1 over a dp mesh, and one serving replica per chip
# ----------------------------------------------------------------------
def _zero_run(sizes, seed, batch, steps, **kw):
    """A few steps of the dropout-free model from ``seed`` at global
    ``batch``; everything the checks need is read out before the step
    and its buffers are dropped."""
    import jax
    import mxtpu as mx
    mx.random.seed(seed)
    net = _bert(sizes, dropout=0.0)
    net.initialize(init="xavier")
    step = _mlm_step(sizes, net, **kw)
    toks = _tokens(sizes, seed, batch)
    t0 = time.perf_counter()
    summary = step.program_summary(toks, toks)        # compiles
    compile_s = time.perf_counter() - t0
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(toks, toks).asnumpy()))   # blocks
        secs.append(time.perf_counter() - t0)
    return {
        "losses": losses, "compile_seconds": round(compile_s, 2),
        "step_seconds": round(statistics.median(secs[1:] or secs), 4),
        "opt_state_bytes": step.opt_state_bytes(),
        "collectives": {k: v["count"]
                        for k, v in summary["collectives"].items()},
        "state_device_sets": sorted(
            {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(step._opt_state)}),
        "param_device_sets": sorted(
            {len(p.data().data.sharding.device_set)
             for p in net.collect_params().values()}),
    }


def zero_phase(sizes, seed, devices):
    """ZeRO-1 data parallelism over ``devices``: first at the full
    global batch (``sizes.batch`` per device), then against a
    one-device step from the same seed on the same, smaller global
    batch ``sizes.compare_batch`` — one device cannot hold the full
    one.  Dropout is off throughout, because under ZeRO every shard
    draws its own dropout stream and the losses would differ by
    design."""
    from mxtpu import parallel

    dp = len(devices)
    mesh = parallel.make_mesh({"dp": dp}, devices=list(devices))
    full = _zero_run(sizes, seed, sizes.batch * dp, sizes.steps,
                     mesh=mesh, zero=1)
    say("zero", dp=dp, global_batch=sizes.batch * dp, seq=sizes.seq,
        **full, peak_bytes_in_use=[peak_bytes(d) for d in devices])
    check(all(np.isfinite(full["losses"])) and
          full["losses"][-1] < full["losses"][0],
          f"zero: losses not finite and falling: {full['losses']}")
    check(full["collectives"].get("reduce-scatter") and
          full["collectives"].get("all-gather"),
          f"zero: no reduce-scatter/all-gather pair in the program: "
          f"{full['collectives']}")
    check(full["state_device_sets"] == [dp] and
          full["param_device_sets"] == [dp],
          f"zero: optimizer state spans {full['state_device_sets']} "
          f"and parameters span {full['param_device_sets']} devices, "
          f"want {dp}")
    gc.collect()

    say("zero", compare_at_global_batch=sizes.compare_batch,
        why=f"one device cannot hold batch {sizes.batch * dp} x "
            f"seq {sizes.seq} (for BERT-Large at 32 x 512 the TPU "
            f"compiler asks 17.8 GB of a v5e chip's 15.75 GB)")
    zero = _zero_run(sizes, seed, sizes.compare_batch, sizes.steps,
                     mesh=mesh, zero=1)
    gc.collect()
    one = _zero_run(sizes, seed, sizes.compare_batch, sizes.steps)
    ratio = zero["opt_state_bytes"] / one["opt_state_bytes"]
    say("zero", zero1=zero)
    say("zero", one_device=one)
    say("zero", loss_rtol=LOSS_RTOL, opt_state_ratio=round(ratio, 4))
    check(all(np.isfinite(zero["losses"] + one["losses"])),
          f"zero: non-finite loss {zero['losses']} {one['losses']}")
    check(np.allclose(zero["losses"], one["losses"], rtol=LOSS_RTOL,
                      atol=0),
          f"zero: ZeRO-1 losses {zero['losses']} differ from the "
          f"one-device step's {one['losses']} by more than rtol "
          f"{LOSS_RTOL}")
    check(one["param_device_sets"] == [1],
          f"zero: the one-device step spans "
          f"{one['param_device_sets']} devices")
    # a shard is padded up to a multiple of dp, so a little over 1/dp
    check(1.0 / dp <= ratio <= 1.05 / dp and
          full["opt_state_bytes"] == zero["opt_state_bytes"],
          f"zero: optimizer state per device is {ratio:.3f} of the "
          f"one-device state, want ~1/{dp}")
    return {"full": full, "zero1": zero, "one_device": one,
            "opt_state_ratio": ratio}


def fleet_phase(sizes, seed, devices, workdir):
    """One ``ModelRunner`` per device behind a ``FleetRouter``, each
    built with its ``device=`` passed explicitly; every replica must
    answer, from buffers on its own device, what a direct forward of
    the exported net answers."""
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.serving import FleetRouter, FleetWorker, ModelRunner

    mx.random.seed(seed)
    net = _bert(sizes, dropout=0.0, use_token_type=False)
    net.initialize(init="xavier")
    net.hybridize()
    rng = np.random.RandomState(seed + 2)
    # whole-bucket sequences: the encoder has no padding mask, so a
    # padded row would not be comparable with the direct forward
    rows = [rng.randint(0, sizes.vocab, (sizes.fleet_seq,))
            .astype(np.float32) for _ in range(sizes.fleet_requests)]
    want = [net(nd.array(r[None, :])).asnumpy()[0] for r in rows[:4]]
    sym_file, param_file = net.export(os.path.join(workdir, "bert"))
    del net
    gc.collect()

    t0 = time.perf_counter()
    runners = [ModelRunner.from_export(
        sym_file, param_file, input_specs={"data": (None,)},
        seq_buckets=[sizes.fleet_seq],
        max_batch_size=sizes.fleet_batch, device=d) for d in devices]
    for r in runners:
        r.warmup()
    say("fleet", replicas=len(runners), load_and_warmup_seconds=round(
        time.perf_counter() - t0, 2),
        programs_each=runners[0].num_compiled())
    for r, d in zip(runners, devices):
        homes = {dev for w in r.weight_buffers() for dev in w.devices()}
        check(homes == {d},
              f"fleet: replica for {d} keeps weights on {homes}")

    with FleetRouter(threaded=True, canary=None) as router:
        for i, r in enumerate(runners):
            router.add_worker(FleetWorker(r, f"w{i}",
                                          max_queue_delay_us=2000.0))
        t0 = time.perf_counter()
        reqs = [router.submit({"data": row}, seq_len=len(row),
                              timeout_s=120.0) for row in rows]
        outs = [r.result(timeout=120.0)[0] for r in reqs]
        elapsed = time.perf_counter() - t0
        # a worker counts a completion after it has delivered the
        # result, so the counters may trail the futures for a moment
        deadline = time.perf_counter() + 10.0
        while True:
            served = {n: w["completed"] for n, w in
                      router.fleet_stats()["workers"].items()}
            if sum(served.values()) >= len(rows) or \
                    time.perf_counter() > deadline:
                break
            time.sleep(0.01)
    worst = 0.0
    for got, ref in zip(outs, want):
        tol = LOGIT_RTOL * max(1.0, float(np.abs(ref).max()))
        diff = float(np.abs(np.asarray(got) - ref).max())
        worst = max(worst, diff / tol)
        check(diff <= tol, f"fleet: a replica's logits differ from the "
                           f"direct forward by {diff:.4g} > {tol:.4g}")
    check(all(np.isfinite(np.asarray(o)).all() for o in outs),
          "fleet: non-finite output")
    check(len(served) == len(devices) and all(served.values()),
          f"fleet: a replica answered nothing: {served}")
    say("fleet", requests=len(rows), seconds=round(elapsed, 3),
        completed_per_replica=served,
        replica_devices=[str(d) for d in devices],
        worst_logit_diff_over_tol=round(worst, 4))
    return {"served": served}


# ----------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the four-chip path (ZeRO-1 dp=4 "
                         "and one serving replica per chip)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import mxtpu  # noqa: F401 — places JAX's compile cache (mxtpu/__init__)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} {dev.device_kind} device(s)")
    from mxtpu import kernels
    check(not kernels.interpret_mode(),
          "Pallas interpreter mode is on with a TPU backend")
    say("start", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), jax=jax.__version__, seed=args.seed,
        compile_cache_dir=jax.config.jax_compilation_cache_dir)

    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 1:
            train = train_phase(FULL, args.seed, dev)
            check(train["param_platforms"] == ["tpu"],
                  f"train: parameters live on "
                  f"{train['param_platforms']}, not the TPU")
            calls = train["pallas_calls"]
            check(calls.get("flash_attention.py", 0) >= FULL.layers
                  and calls.get("layer_norm.py", 0) >= 2 * FULL.layers,
                  f"train: the compiled step lacks the Pallas calls "
                  f"(flash attention per layer, LN epilogues): {calls}")
            gc.collect()
            serve = serve_phase(FULL, args.seed, dev, workdir)
            check(serve["weight_platforms"] == ["tpu"],
                  f"serve: weights live on {serve['weight_platforms']}")
            check(serve["pallas_calls"].get("layer_norm.py", 0)
                  >= 2 * FULL.layers,
                  f"serve: the compiled decode step lacks the Pallas "
                  f"LN epilogues: {serve['pallas_calls']}")
        else:
            zero_phase(FULL, args.seed, devices[:args.chips])
            gc.collect()
            fleet_phase(FULL, args.seed, devices[:args.chips], workdir)
    say("done", seconds=round(time.perf_counter() - t_all, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
