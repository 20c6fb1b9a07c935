"""Drive the paged ``Engine`` with a traffic mix, through its public methods.

One scheduler iteration per ``Engine.run(max_steps=eng.steps + 1)`` call;
requests are handed over with ``Engine.submit`` when they fall due. The
load is an open loop: Poisson (or bursty) arrivals at a rate fixed in the
cell, started before the window (the lead-in, which counts as set-up).
After the window the feeder keeps serving, arrivals continuing, until every
request due in the window has finished or the drain limit passes, so that
the requests the output check samples are served at the window's load.

Every request is timed from its due time, not from when ``submit`` ran.
The feeder counts what the window did from public request state: the model
FLOPs of prompt tokens written into the pool (``prefilled``, its high-water
mark, so a recompute resume is not counted again) and of tokens streamed
(``tokens``), as the architecture's ``Shape`` counts them
(``bench/archs/<arch>.py``), and preemptions (``preemptions``).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Iterator, List, Optional

import numpy as np


@dataclass
class Tracked:
    req: object              # the engine's EngineRequest
    due: float               # absolute time.monotonic() the request fell due
    prompt_len: int
    written: int = 0         # prompt tokens written into the pool (high-water)
    prefilled: int = 0       # the request's ``prefilled`` when last looked at
    streamed: int = 0        # tokens streamed so far
    preemptions: int = 0


@dataclass
class Iteration:
    start: float
    end: float
    decode_lengths: List[int]    # KV length the paged kernel sees, per decode row
    prompt_tokens: int = 0       # context tokens the chunk pass wrote (resumes too)
    preemptions: int = 0
    flops: int = 0


@dataclass
class Window:
    start: float
    end: float
    drained_at: Optional[float] = None

    def inside(self, t: float) -> bool:
        return self.start <= t < self.end


class Feeder:
    def __init__(self, eng, arrivals: Iterator, shape, rate: float,
                 annotate=None):
        self.eng = eng
        self.arrivals = arrivals
        self.shape = shape
        self.rate = rate
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.live: List[Tracked] = []
        self.all: List[Tracked] = []
        self.lateness: List[tuple] = []          # (due, submit - due)
        self.iterations: List[Iteration] = []
        self._next = None
        self._next_due = None
        self._clock = None
        self.origin = None

    # -- arrivals ------------------------------------------------------
    def _pull(self):
        """The next request, due when the previous one's gap has passed
        (the first at the origin)."""
        self._next = next(self.arrivals)
        self._next_due = self._clock
        self._clock += self._next.gap_s / self.rate

    def _submit(self, item, due: float):
        r = self.eng.submit(item.prompt, item.max_new)
        now = time.monotonic()
        t = Tracked(r, due, len(item.prompt))
        self.live.append(t)
        self.all.append(t)
        self.lateness.append((due, now - due))

    def _admit_due(self, now: float):
        with self.annotate("bench.arrivals"):
            while self._next_due <= now:
                self._submit(self._next, self._next_due)
                self._pull()

    # -- one iteration -------------------------------------------------
    def _decode_lengths(self) -> List[int]:
        out = []
        for r in self.eng.active:
            if (r is not None and r.ctx is not None
                    and r.prefilled >= len(r.ctx)):
                out.append(len(r.prompt) + len(r.tokens))
        return out

    def _account(self, it: Iteration):
        with self.annotate("bench.accounting"):
            keep = []
            for t in self.live:
                r = t.req
                if r.prefilled > t.prefilled:
                    it.prompt_tokens += r.prefilled - t.prefilled
                t.prefilled = r.prefilled
                hw = min(r.prefilled, t.prompt_len)
                if hw > t.written:
                    it.flops += self.shape.span_flops(t.written, hw)
                    t.written = hw
                n = len(r.tokens)
                for k in range(t.streamed, n):
                    it.flops += (self.shape.head_flops() if k == 0 else
                                 self.shape.token_flops(t.prompt_len + k - 1,
                                                        True))
                t.streamed = n
                if r.preemptions > t.preemptions:
                    it.preemptions += r.preemptions - t.preemptions
                    t.preemptions = r.preemptions
                if r.state != "done":
                    keep.append(t)
            self.live = keep

    def step(self) -> bool:
        """Submit what is due, then run one scheduler iteration. Returns
        False when the engine had nothing to do (the caller may wait)."""
        now = time.monotonic()
        self._admit_due(now)
        eng = self.eng
        if not eng.waiting and not any(a is not None for a in eng.active):
            return False
        lengths = self._decode_lengths()
        start = time.monotonic()
        with self.annotate("bench.engine_iter"):
            eng.run(max_steps=eng.steps + 1)
        it = Iteration(start, time.monotonic(), lengths)
        self._account(it)
        self.iterations.append(it)
        return True

    def idle_until(self, deadline: float):
        """Nothing to serve: sleep until the next arrival or ``deadline``."""
        with self.annotate("bench.idle_wait"):
            delay = min(self._next_due, deadline) - time.monotonic()
            if delay > 0:
                time.sleep(delay)

    # -- phases ----------------------------------------------------------
    def start(self, origin: float):
        self.origin = origin
        self._clock = origin
        self._pull()

    def serve_until(self, deadline: float):
        while time.monotonic() < deadline:
            if not self.step():
                self.idle_until(deadline)

    def drain(self, window: Window, limit_s: float):
        """Keep serving, arrivals continuing, until every request due in the
        window has finished, or ``limit_s`` after the window's end."""
        due = self.due_in(window)
        stop = window.end + limit_s
        while time.monotonic() < stop:
            if all(t.req.state == "done" for t in due):
                break
            if not self.step():
                self.idle_until(stop)
        window.drained_at = time.monotonic()

    def due_in(self, window: Window) -> List[Tracked]:
        return [t for t in self.all if window.inside(t.due)]


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by numpy's linear rule; inf sorts last."""
    return float(np.percentile(np.asarray(values, np.float64), q))
