"""Traffic for generation cells, from a data file and a seed.

One generator serves every mix.  A mix is a JSON object:

    {"kind": "generate",
     "loop": "closed", "clients": 62, "ramp_s": 6.0,        # or
     "loop": "open", "arrival": "poisson", "rate_per_s": 5.0,
     "prompt_len": {"dist": "loguniform", "lo": 16, "hi": 256},
     "output_len": {"dist": "loguniform", "lo": 32, "hi": 192},
     "pool": 512}

Every seed gets the SAME multiset of sizes and inter-arrival gaps (the
``pool`` quantiles of each distribution), in another order, so that a
seed changes which request meets which and not how much work a run
holds.  Token ids come from the seed too.  Nothing here touches JAX.
"""
import math
import threading
import time

import numpy as np

# a little over 2**31 seeds must work: fold the seed through SeedSequence
# and never hand it to a 32-bit API as it is


def rng_for(seed, stream):
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _quantiles(spec, n):
    """The n mid-point quantiles of a distribution, as floats."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "loguniform":
        lo, hi = float(spec["lo"]), float(spec["hi"])
        return np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    if dist == "uniform":
        return float(spec["lo"]) + u * (float(spec["hi"]) - float(spec["lo"]))
    if dist == "fixed":
        return np.full(n, float(spec["value"]))
    if dist == "exponential":          # mean 1; scaled by the caller
        return -np.log1p(-u)
    raise ValueError(f"loadgen: unknown distribution {dist!r}")


def lengths(mix, seed):
    """``pool`` (prompt_len, output_len) pairs: fixed multisets, paired
    and ordered by the seed."""
    n = int(mix.get("pool", 512))
    p = np.rint(_quantiles(mix["prompt_len"], n)).astype(int)
    o = np.rint(_quantiles(mix["output_len"], n)).astype(int)
    rng = rng_for(seed, 1)
    return list(zip(rng.permutation(p).tolist(), rng.permutation(o).tolist()))


def arrivals(mix, seed, horizon_s):
    """Due times (seconds from the window's start) of an open loop up
    to ``horizon_s``.  Poisson: exponential gaps, a fixed multiset in a
    seeded order, repeated in fresh orders until the horizon."""
    rate = float(mix["rate_per_s"])
    kind = mix.get("arrival", "poisson")
    n = int(mix.get("pool", 512))
    if kind == "poisson":
        base = _quantiles({"dist": "exponential"}, n) / rate
    elif kind == "uniform":
        base = np.full(n, 1.0 / rate)
    else:
        raise ValueError(f"loadgen: unknown arrival process {kind!r}")
    rng = rng_for(seed, 2)
    out, t = [], 0.0
    while True:
        for g in rng.permutation(base):
            t += float(g)
            if t >= horizon_s:
                return out
            out.append(t)


def prompt_ids(seed, index, n, vocab):
    """Token ids of request ``index``: from the seed, never id 0."""
    return rng_for(seed, 1000 + index).integers(1, vocab, n).tolist()


class Request:
    """What the generator knows of one request; the token clock is the
    generator's own (``time.perf_counter`` at each streamed token)."""
    __slots__ = ("index", "prompt", "max_tokens", "due", "sent",
                 "token_times", "tokens", "error", "done")

    def __init__(self, index, prompt, max_tokens, due):
        self.index = index
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.due = due              # absolute perf_counter time
        self.sent = None
        self.token_times = []
        self.tokens = []
        self.error = None
        self.done = threading.Event()


class LoadGen:
    """Drives ``submit(prompt, max_tokens, on_token)`` from the
    calling thread.  Closed loop: ``clients`` requests in flight, the
    next one sent when one finishes.  Open loop: each request sent when
    it is due, whatever the server does.  ``run`` returns when the
    window has closed; ``drain`` waits for what is still in flight.  A
    request belongs to the window when its ``due`` time lies in it."""

    def __init__(self, mix, seed, vocab, submit, probe=None):
        self._probe = probe         # what the server looked like at a send
        self.probes = []
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self._submit = submit
        self._lengths = lengths(mix, seed)
        self._next = 0
        self.requests = []
        self._finished = []         # appended from the server's thread
        self._wake = threading.Condition()
        self.lateness = []          # open loop: sent - due, seconds

    # -- one request -----------------------------------------------------
    def _make(self, due):
        i = self._next
        self._next += 1
        plen, olen = self._lengths[i % len(self._lengths)]
        r = Request(i, prompt_ids(self.seed, i, plen, self.vocab), olen, due)
        self.requests.append(r)
        return r

    def _send(self, r):
        def on_token(tok, idx, r=r):
            r.token_times.append(time.perf_counter())
            r.tokens.append(int(tok))
            if idx + 1 >= r.max_tokens:
                r.done.set()
                with self._wake:
                    self._finished.append(r)
                    self._wake.notify()
        if self._probe is not None:
            self.probes.append(self._probe())
        r.sent = time.perf_counter()
        try:
            self._submit(r.prompt, r.max_tokens, on_token)
        except Exception as e:  # noqa: BLE001 — refused (ServerBusy) or closed
            r.error = repr(e)
            r.done.set()
            with self._wake:
                self._finished.append(r)
                self._wake.notify()

    # -- loops -----------------------------------------------------------
    def _turn_over(self, until, last):
        """Closed loop: answer each finished request with a new one
        until ``until``; in the ``last`` stretch a request that finishes
        after ``until`` is not answered."""
        while True:
            with self._wake:
                left = until - time.perf_counter()
                if not self._finished and left > 0:
                    self._wake.wait(left)
                done, self._finished = self._finished, []
            now = time.perf_counter()
            if now >= until and last:
                return
            for _ in done:
                self._send(self._make(now))
            if now >= until:
                return

    def _arrive(self, t0, dues):
        for due in dues:
            r = self._make(t0 + due)
            wait = r.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._send(r)
            self.lateness.append(r.sent - r.due)

    def run(self, seconds, on_open=lambda: None):
        """Bring the load up over ``ramp_s`` (not measured), call
        ``on_open()``, offer load for ``seconds``; returns the window
        ``(t0, t1)`` on the perf_counter clock.  Closed loop: the
        clients start one at a time over the ramp, so that no step
        admits more than a few.  Open loop: the ramp is a stretch of
        the same arrival process before the window opens."""
        ramp_s = float(self.mix.get("ramp_s", 0.0))
        if self.mix["loop"] == "closed":
            n = int(self.mix["clients"])
            for _ in range(n):
                self._send(self._make(time.perf_counter()))
                self._turn_over(time.perf_counter() + ramp_s / n, False)
            on_open()
            t0 = time.perf_counter()
            self._turn_over(t0 + seconds, True)
            return t0, t0 + seconds
        dues = arrivals(self.mix, self.seed, ramp_s + seconds)
        start = time.perf_counter()
        t0 = start + ramp_s
        self._arrive(start, [d for d in dues if d < ramp_s])
        wait = t0 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        on_open()
        self._arrive(start, [d for d in dues if d >= ramp_s])
        wait = t0 + seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        return t0, t0 + seconds

    def drain(self, timeout_s):
        """Wait for every request sent; one that never finishes is
        marked failed."""
        deadline = time.perf_counter() + timeout_s
        for r in self.requests:
            if not r.done.wait(max(0.0, deadline - time.perf_counter())):
                r.error = r.error or "never finished"
        return [r for r in self.requests if r.error]


def percentile(values, q):
    """Linear-interpolated percentile of a non-empty list."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)
