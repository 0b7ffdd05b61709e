"""Open-loop serving: send each request when it is due, whatever the
system does, and time it from then.

Three threads besides the caller's: the generator sends requests at
their due times (``submit(i)`` returns a ticket with ``result(timeout)``),
the collector waits on the tickets in the order they were sent and
stamps each as it comes back, and the ticker advances the replica's
version on its own schedule.  A request's latency runs from its due
time, so a stall that holds up the generator (a full queue blocks
``submit``) shows in every later request; the generator's own lateness
is kept beside it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time

import numpy as np

START_DELAY_S = 0.05
#: how long past the window's close answers are awaited; one that never
#: comes counts as unanswered
ANSWER_WAIT_S = 60.0


class Replica:
    """The replica's clock at each tick; the ticker moves ``version``."""

    def __init__(self, clocks: list):
        self.clocks = clocks
        self.version = 0

    def current(self):
        return self.clocks[self.version]


@dataclasses.dataclass
class Served:
    t0: float                 # window start (perf_counter seconds)
    t_close: float            # window end
    due: np.ndarray           # absolute due times
    sent: np.ndarray          # NaN where never sent
    done: np.ndarray          # NaN where never answered
    ver_sent: np.ndarray      # replica version when sent
    ver_done: np.ndarray      # replica version when the answer was seen
    results: list             # what each ticket returned (None: nothing)

    @property
    def n_sent(self) -> int:
        return int(np.isfinite(self.sent).sum())

    @property
    def n_answered(self) -> int:
        return int(np.isfinite(self.done).sum())

    def latency_s(self) -> np.ndarray:
        """Due-to-answer seconds of every answered request."""
        ok = np.isfinite(self.done)
        return self.done[ok] - self.due[ok]

    def lag_s(self) -> np.ndarray:
        """How late the generator sent each request."""
        ok = np.isfinite(self.sent)
        return self.sent[ok] - self.due[ok]

    def completed_in_window(self) -> int:
        return int(((self.done >= self.t0) & (self.done <= self.t_close))
                   .sum())


def annotator(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def serve(due_s: np.ndarray, seconds: float, submit, replica: Replica,
          tick_every_s: float, *, trace: bool = False) -> Served:
    """Run one open-loop window: every request is sent, however late, and
    its answer awaited up to ``ANSWER_WAIT_S`` past the close."""
    annotate = annotator(trace)
    n = len(due_s)
    t0 = time.perf_counter() + START_DELAY_S
    t_close = t0 + seconds
    due = t0 + np.asarray(due_s, np.float64)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ver_sent = np.zeros(n, np.int32)
    ver_done = np.zeros(n, np.int32)
    results: list = [None] * n
    handoff: queue.SimpleQueue = queue.SimpleQueue()
    closed = threading.Event()
    errors: list = []

    def generator():
        try:
            for i in range(n):
                delay = due[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                ver_sent[i] = replica.version
                with annotate("bench.submit"):
                    sent[i] = time.perf_counter()
                    ticket = submit(i)
                handoff.put((i, ticket))
        except BaseException as e:      # surfaced by the caller
            errors.append(e)
        finally:
            handoff.put(None)

    def collector():
        deadline = t_close + ANSWER_WAIT_S
        while (item := handoff.get()) is not None:
            i, ticket = item
            try:
                res = ticket.result(
                    timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                continue
            done[i] = time.perf_counter()
            ver_done[i] = replica.version
            results[i] = res

    def ticker():
        t = 1
        while t < len(replica.clocks):
            if closed.wait(max(0.0, t0 + t * tick_every_s
                               - time.perf_counter())):
                return
            replica.version = t
            t += 1

    threads = [threading.Thread(target=f, name=f"bench-{f.__name__}",
                                daemon=True)
               for f in (generator, collector, ticker)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    with annotate("bench.window"):
        time.sleep(max(0.0, t_close - time.perf_counter()))
    closed.set()
    for th in threads:
        th.join(timeout=seconds + ANSWER_WAIT_S + 30.0)
        if th.is_alive():
            raise RuntimeError(f"{th.name} did not finish")
    if errors:
        raise RuntimeError("load generator failed") from errors[0]
    return Served(t0=t0, t_close=t_close, due=due, sent=sent, done=done,
                  ver_sent=ver_sent, ver_done=ver_done, results=results)
