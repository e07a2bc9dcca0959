"""Open-loop query arrivals and the pacer that offers them to the batcher.

Copied from ``serving/batcher.run_open_loop`` and extended:

  * arrivals are a Poisson process at a fixed rate, drawn as a fixed
    count ``round(rate * seconds)`` of arrival times uniform over the
    window (a Poisson process conditioned on its count), so every seed
    offers the same amount of work in another order;
  * cameras are spread evenly: each gets the same number of queries, in a
    shuffled order;
  * every ticket is stamped with its scheduled arrival, so its latency
    counts any slip of the pacer, and the pacer records how late it
    submitted each query (``lag``) and every launch it drove;
  * each answer is copied out of its ticket into preallocated arrays as
    its launch returns, and no ticket is kept: tens of thousands of kept
    tickets would grow the heap the garbage collector walks, and its full
    collections would stall the window.

One thread: the loop submits every query that has come due, runs one
batcher step while anything is pending, and sleeps to the next arrival
when nothing is. A query that comes due while a launch runs is submitted
when the launch returns, so its lag includes that wait.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np


def _annotated_sleep(secs: float):
    import jax
    with jax.profiler.TraceAnnotation("pacer.sleep"):
        time.sleep(secs)


@dataclasses.dataclass
class Schedule:
    times: np.ndarray        # (n,) seconds after the window opens, sorted
    clients: np.ndarray      # (n,) camera of each arrival

    def split(self, at: float):
        """(arrivals before ``at``, arrivals from ``at`` re-based to 0)."""
        i = int(np.searchsorted(self.times, at))
        return (Schedule(self.times[:i], self.clients[:i]),
                Schedule(self.times[i:] - at, self.clients[i:]))


def poisson_schedule(seed: int, rate: float, seconds: float,
                     n_clients: int) -> Schedule:
    rng = np.random.default_rng([seed, 0x9ACE])
    n = int(round(rate * seconds))
    n -= n % n_clients
    times = np.sort(rng.uniform(0.0, seconds, n))
    clients = rng.permutation(np.repeat(np.arange(n_clients), n // n_clients))
    return Schedule(times, clients)


@dataclasses.dataclass
class Launch:
    t_launch: float
    t_done: float
    slots: int


@dataclasses.dataclass
class OpenLoopRun:
    """One row per arrival, in schedule order (times on ``perf_counter``)."""
    t_submit: np.ndarray     # (n,) scheduled arrival
    t_launch: np.ndarray     # (n,) start of the launch that answered it
    t_done: np.ndarray       # (n,) its answer
    ids: np.ndarray          # (n, k) top-k gallery ids
    dists: np.ndarray        # (n, k) their squared distances
    lag: np.ndarray          # (n,) submit time minus scheduled arrival
    launches: List[Launch]
    t0: float                # window start
    t_end: float             # t0 + the schedule's length

    @property
    def latency(self) -> np.ndarray:
        return self.t_done - self.t_submit

    @property
    def queue_s(self) -> np.ndarray:
        return self.t_launch - self.t_submit

    def answered_by(self, t: float) -> int:
        return int(np.count_nonzero(self.t_done <= t))


def run_open_loop(batcher, schedule: Schedule, protos: np.ndarray,
                  seconds: float, annotate: bool = False) -> OpenLoopRun:
    """Offer ``schedule`` to ``batcher`` in real time and run it until
    every query is answered. ``protos[i]`` is arrival i's prototype.
    ``annotate`` marks the pacer's sleeps (``pacer.sleep``) in a profile."""
    sleep = _annotated_sleep if annotate else time.sleep
    n = len(schedule.times)
    due = schedule.times
    lag = np.zeros((n,))
    t_launch, t_done = np.full((n,), np.nan), np.full((n,), np.nan)
    ids = dists = None
    launches: List[Launch] = []
    t0 = time.perf_counter()
    i = 0
    while i < n or batcher.pending:
        now = time.perf_counter()
        while i < n and t0 + due[i] <= now:
            batcher.submit(int(schedule.clients[i]), protos[i], qid=i,
                           now=t0 + due[i])
            lag[i] = now - (t0 + due[i])
            i += 1
        if batcher.pending:
            done = batcher.step()
            if done:
                launches.append(Launch(done[0].t_launch, done[0].t_done,
                                       len(done)))
                if ids is None:
                    ids = np.zeros((n,) + done[0].ids.shape, done[0].ids.dtype)
                    dists = np.zeros((n,) + done[0].dists.shape,
                                     done[0].dists.dtype)
                for tk in done:
                    t_launch[tk.qid], t_done[tk.qid] = tk.t_launch, tk.t_done
                    ids[tk.qid], dists[tk.qid] = tk.ids, tk.dists
        elif i < n:
            sleep(max(0.0, t0 + due[i] - time.perf_counter()))
    return OpenLoopRun(t0 + due, t_launch, t_done, ids, dists, lag, launches,
                       t0, t0 + seconds)
