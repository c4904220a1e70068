"""The open-loop driver: submit each request when it falls due, step the
engine, and stamp every token with the host clock.

One thread does all of it, as a serving loop over ``ServingEngine``
would: a request that falls due while a step runs is submitted when the
step returns, and that lateness is recorded (``gen_lag``).  A token is
stamped when the step that produced it returns, which is when a server
built on the engine could first send it.  Latencies run from the due
time, so a stall also counts against the requests it delays.
"""
from __future__ import annotations

import dataclasses
import time

LIVE = ("queued", "running", "deferred")


@dataclasses.dataclass
class Entry:
    """What the host saw of one request."""
    due: object                        # traffic.Due
    rid: int
    submit: float                      # seconds after the traffic started
    times: list = dataclasses.field(default_factory=list)
    status: str = "queued"
    tokens: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Record:
    entries: list                      # [Entry], in order of due time
    steps: list                        # [(start, end, active slots)]
    end: float                         # when the driver stopped
    t0: float                          # the clock when traffic started


def drive(eng, plan, prompt_of, *, stop_at, must_finish, hard_end,
          on_time=(), annotate=None, clock=time.perf_counter):
    """Serve ``plan`` ([traffic.Due]) through ``eng``.

    Runs until ``stop_at`` seconds have passed and every request that
    ``must_finish(due)`` selects has its first token or has ended, and
    never past ``hard_end``.  ``on_time`` holds (seconds, callback) pairs,
    each called once at the first step boundary at or after its time.
    ``annotate(step_index)``, where given, returns a context manager that
    wraps one engine step (a profiler span).  Times in the record are
    seconds after the traffic started; each entry keeps its request's
    tokens, so the record outlives the engine."""
    t0 = clock()
    nxt, entries, live, steps = 0, [], {}, []
    hooks = sorted(on_time, key=lambda h: h[0])
    waiting = set()

    while True:
        now = clock() - t0
        while hooks and hooks[0][0] <= now:
            hooks.pop(0)[1]()
        while nxt < len(plan) and plan[nxt].due <= now and now < hard_end:
            d = plan[nxt]
            rid = eng.submit(prompt_of(d), d.max_new_tokens)
            e = Entry(d, rid, clock() - t0)
            entries.append(e)
            live[rid] = e
            if must_finish(d):
                waiting.add(rid)
            nxt += 1
        if now >= hard_end or (now >= stop_at and not hooks
                               and not waiting):
            break
        if eng.sched.has_work():
            t_s = clock() - t0
            if annotate is not None:
                with annotate(len(steps)):
                    active = eng.step()
            else:
                active = eng.step()
            t_e = clock() - t0
            steps.append((t_s, t_e, active))
            for rid, e in list(live.items()):
                req = eng.requests[rid]
                n = len(req.tokens)
                if n > len(e.times):
                    e.times.extend([t_e] * (n - len(e.times)))
                e.status = req.status
                if e.times or e.status not in LIVE:
                    waiting.discard(rid)
                if e.status not in LIVE:
                    del live[rid]
        else:
            until = plan[nxt].due if nxt < len(plan) else hard_end
            if hooks:
                until = min(until, hooks[0][0])
            time.sleep(min(max(until - (clock() - t0), 0.0), 0.01))
    for e in entries:
        e.tokens = list(eng.requests[e.rid].tokens)
    return Record(entries, steps, clock() - t0, t0)
