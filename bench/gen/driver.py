"""The open-loop driver: submit each request when it is due, poll the
scheduler while it has work, and log when every token became visible.

Adapted from ``repro.launch.serve._drive_open_loop``: that loop ran until
every request completed and timed completions; this one runs for a fixed
window and records, at the return of every ``poll()``, which tokens a
streaming client would now see.  A request is timed from when it was due,
not from when the loop got round to submitting it, so a poll that stalls
the loop shows in every request that fell due meanwhile.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Poll:
    start: float                      # host clock, seconds
    end: float
    prefill_chunks: int
    prefill_tokens: int
    decode_stepped: bool
    n_active: int


@dataclasses.dataclass
class WindowLog:
    t0: float                         # window start (host clock)
    seconds: float                    # window length
    due: Dict[int, float]             # req_id -> absolute due time (ids
                                      # < 0: due but never submitted)
    visible: Dict[int, List[float]]   # req_id -> time each token showed
    requests: Dict[int, object]       # req_id -> scheduler Request
    polls: List[Poll]
    lateness: List[float]             # submit time - due time, per request
    traced: Optional[tuple] = None    # (start, end) of the traced part

    @property
    def end(self) -> float:
        return self.t0 + self.seconds

    def summary(self) -> Dict:
        late = np.asarray(self.lateness) if self.lateness else np.zeros(1)
        done = sum(1 for r in self.requests.values()
                   if r.done and self.visible[r.req_id]
                   and self.visible[r.req_id][-1] <= self.end)
        return {"due": len(self.due), "sent": len(self.lateness),
                "completed": done, "in_flight": len(self.lateness) - done,
                "late_p50_s": float(np.percentile(late, 50)),
                "late_max_s": float(late.max())}


def run_window(sched, reqs: List[Dict], seconds: float, *,
               make_request: Callable,
               trace_start: Optional[Callable] = None,
               trace_stop: Optional[Callable] = None,
               trace_seconds: float = 0.0,
               clock=time.perf_counter) -> WindowLog:
    """Drive ``sched`` open loop for ``seconds``.  ``reqs`` are the
    generator's dicts (due, tokens, max_new), sorted by due time;
    ``make_request(i, r)`` turns one into a scheduler ``Request``.  With
    ``trace_start``/``trace_stop`` the profiler covers the first
    ``trace_seconds`` of the window (the first poll that ends past it
    closes the trace)."""
    log = WindowLog(t0=0.0, seconds=seconds, due={}, visible={},
                    requests={}, polls=[], lateness=[])
    seen: Dict[int, int] = {}
    if trace_start is not None:
        trace_start()
    tracing = trace_start is not None
    t0 = log.t0 = clock()
    end = t0 + seconds
    i, n = 0, len(reqs)
    while True:
        now = clock()
        if now >= end:
            break
        while i < n and t0 + reqs[i]["due"] <= now:
            r = make_request(i, reqs[i])
            sched.submit(r)
            log.due[r.req_id] = t0 + reqs[i]["due"]
            log.visible[r.req_id] = []
            log.requests[r.req_id] = r
            log.lateness.append(now - log.due[r.req_id])
            seen[r.req_id] = 0
            i += 1
        if sched.has_work:
            with jax.profiler.TraceAnnotation("bench.poll"):
                ta = clock()
                rep = sched.poll()
                tb = clock()
            log.polls.append(Poll(ta, tb, rep.prefill_chunks,
                                  rep.prefill_tokens, rep.decode_stepped,
                                  rep.n_active))
            for r in [q for q in sched.slot_req if q is not None] \
                    + rep.completed:
                got = len(r.out_tokens)
                if got > seen[r.req_id]:
                    log.visible[r.req_id] += [tb] * (got - seen[r.req_id])
                    seen[r.req_id] = got
            if tracing and tb - t0 >= trace_seconds:
                trace_stop()
                log.traced = (t0, tb)
                tracing = False
        elif i < n:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(min(0.002, max(0.0, t0 + reqs[i]["due"] - now)))
        else:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(min(0.002, max(0.0, end - now)))
    if tracing:
        trace_stop()
        log.traced = (t0, clock())
    # due in the window but never submitted (the loop was inside a long
    # poll): still attempted, with no token shown
    for j in range(i, n):
        if reqs[j]["due"] < seconds:
            log.due[-1 - j] = t0 + reqs[j]["due"]
            log.visible[-1 - j] = []
    return log
