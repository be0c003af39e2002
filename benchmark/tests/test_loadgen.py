"""loadgen.py: the same seed gives the same schedule and lengths, every
seed the same multiset, and the generator knows how late it ran."""
import threading
import time

from benchmark import loadgen

MIX = {"loop": "open", "arrival": "poisson", "rate_per_s": 50.0,
       "ramp_s": 0.0,
       "prompt_len": {"dist": "loguniform", "lo": 16, "hi": 256},
       "output_len": {"dist": "loguniform", "lo": 32, "hi": 192},
       "pool": 128}
BIG = 2 ** 31 + 12345


def test_same_seed_same_traffic():
    assert loadgen.lengths(MIX, BIG) == loadgen.lengths(MIX, BIG)
    assert loadgen.arrivals(MIX, BIG, 5.0) == loadgen.arrivals(MIX, BIG, 5.0)
    assert loadgen.prompt_ids(BIG, 3, 40, 50358) == \
        loadgen.prompt_ids(BIG, 3, 40, 50358)


def test_every_seed_the_same_sizes_in_another_order():
    a, b = loadgen.lengths(MIX, 1), loadgen.lengths(MIX, BIG)
    assert a != b
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert min(p for p, _ in a) >= 16 and max(p for p, _ in a) <= 256
    assert min(o for _, o in a) >= 32 and max(o for _, o in a) <= 192
    ga = [y - x for x, y in zip([0.0] + loadgen.arrivals(MIX, 1, 2.0),
                                loadgen.arrivals(MIX, 1, 2.0))]
    gb = [y - x for x, y in zip([0.0] + loadgen.arrivals(MIX, 2, 2.0),
                                loadgen.arrivals(MIX, 2, 2.0))]
    assert ga != gb
    rate = len(loadgen.arrivals(MIX, 1, 20.0)) / 20.0
    assert 45.0 < rate < 55.0


def test_prompt_ids_in_vocabulary_and_never_zero():
    ids = loadgen.prompt_ids(BIG, 0, 5000, 97)
    assert min(ids) >= 1 and max(ids) <= 96


class _Server:
    """Finishes a request's tokens from its own thread after a delay."""

    def __init__(self, submit_delay=0.0, token_delay=0.001):
        self.submit_delay, self.token_delay = submit_delay, token_delay
        self.in_flight, self.peak = 0, 0
        self.lock = threading.Lock()

    def submit(self, prompt, max_tokens, on_token):
        time.sleep(self.submit_delay)
        with self.lock:
            self.in_flight += 1
            self.peak = max(self.peak, self.in_flight)

        def work():
            for i in range(max_tokens):
                time.sleep(self.token_delay)
                if i + 1 == max_tokens:
                    with self.lock:
                        self.in_flight -= 1
                on_token(7, i)
        threading.Thread(target=work, daemon=True).start()
        return None


def test_open_loop_is_late_aware():
    mix = dict(MIX, rate_per_s=100.0,
               output_len={"dist": "fixed", "value": 2})
    slow = _Server(submit_delay=0.03)        # 30 ms a submit, 10 ms due
    gen = loadgen.LoadGen(mix, 5, 97, slow.submit)
    t0, t1 = gen.run(0.5)
    assert not gen.drain(5.0)
    # the schedule does not slow down with the server: due times stay on
    # the arrival process, and the generator reports how late it ran
    assert max(gen.lateness) > 0.1
    due = [r.due - t0 for r in gen.requests]
    assert due == sorted(due) and due[-1] < 0.5
    assert all(r.sent >= r.due for r in gen.requests)


def test_closed_loop_keeps_its_clients_in_flight():
    mix = dict(MIX, loop="closed", clients=5, ramp_s=0.05,
               output_len={"dist": "fixed", "value": 3})
    srv = _Server()
    gen = loadgen.LoadGen(mix, 5, 97, srv.submit)
    opened = []
    t0, t1 = gen.run(0.3, on_open=lambda: opened.append(time.perf_counter()))
    assert not gen.drain(5.0)
    assert srv.peak == 5 and len(opened) == 1 and opened[0] <= t0
    assert len(gen.requests) > 20
    assert all(len(r.tokens) == 3 for r in gen.requests)
    assert max(r.due for r in gen.requests) < t1


def test_percentile():
    assert loadgen.percentile([1, 2, 3, 4, 5], 50) == 3
    assert loadgen.percentile(list(range(101)), 95) == 95
    assert loadgen.percentile([], 95) is None
