"""The open-loop driver on a scheduler stand-in: tokens are logged at the
return of the poll that showed them, requests are timed from when they
were due, and a request due while the loop sat inside a long poll still
counts as attempted."""
import os
import sys
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]

import harness  # noqa: E402
from gen import driver  # noqa: E402


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeSched:
    """Admits one request per poll; an admitting poll takes 3 s and shows
    the first token, a decode poll takes 1 s and shows one more."""

    def __init__(self, clock):
        self.clock, self.queue, self.slot_req = clock, [], [None]

    @property
    def has_work(self):
        return bool(self.queue) or self.slot_req[0] is not None

    def submit(self, r):
        self.queue.append(r)

    def poll(self):
        rep = SimpleNamespace(prefill_chunks=0, prefill_tokens=0,
                              decode_stepped=False, n_active=0,
                              completed=[])
        r = self.slot_req[0]
        if r is None:
            r = self.slot_req[0] = self.queue.pop(0)
            rep.prefill_chunks, self.clock.t = 1, self.clock.t + 3.0
        else:
            rep.decode_stepped, rep.n_active = True, 1
            self.clock.t += 1.0
        r.out_tokens.append(7)
        if len(r.out_tokens) == r.max_new:
            r.done, self.slot_req[0] = True, None
            rep.completed = [r]
        return rep


def test_window_log_and_unsent_requests(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(driver.time, "sleep",
                        lambda s: setattr(clock, "t", clock.t + s))
    sched = FakeSched(clock)
    reqs = [{"due": d, "tokens": [1, 2], "max_new": 2}
            for d in (0.0, 8.0, 9.5, 20.0)]
    log = driver.run_window(
        sched, reqs, 10.0, clock=clock,
        make_request=lambda i, r: SimpleNamespace(
            req_id=i, out_tokens=[], max_new=r["max_new"], done=False))
    # request 0: admitted at 100 (first token 103), decoded by 104;
    # request 1: due at 108, its admitting poll runs past the end (110);
    # request 2: due at 109.5, while the loop sat in that poll
    assert log.visible == {0: [103.0, 104.0], 1: [111.0], -3: []}
    assert log.due == {0: 100.0, 1: 108.0, -3: 109.5}
    assert log.summary()["due"] == 3 and log.summary()["sent"] == 2
    assert log.lateness == [0.0, 0.0]
    ctx = harness.Context(log=log, setup_s=0.0, model={}, cell={},
                          n_slots=1, peaks=None)
    assert harness.load_reader("output_tok_per_s").read(ctx) == 0.2
