"""The timing loop, the same in every cell, and the log of what JAX compiles.

Both are the benchmark's own copies of the idea in `chip_smoke.py`
(`timed_steps`, `CompileLog`), kept here so that no later PR to the program
can change how a step is timed.
"""

from __future__ import annotations

import math
import statistics
import time
import traceback
from dataclasses import dataclass, field

import jax


class CompileLog:
    """Counts what JAX compiles, from its own monitoring events: every
    compile request (a new trace reaching the backend, cached or not) and the
    persistent cache's hits and misses. A miss is counted when an entry is
    written, so a second run in the same checkout must show none."""

    def __init__(self) -> None:
        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclass
class Window:
    """What the loop saw: one completion stamp and one loss per step."""
    stamps: list = field(default_factory=list)   # host clock, seconds
    losses: list = field(default_factory=list)
    dispatched: int = 0
    raised: int = 0

    @property
    def failed(self) -> int:
        return self.raised + sum(not math.isfinite(x) for x in self.losses)

    @property
    def step_seconds(self) -> list:
        return [b - a for a, b in zip(self.stamps, self.stamps[1:])]

    def median_step_seconds(self) -> float:
        """Median of the differences between completion stamps: the first
        completion only opens the first interval, so whatever the queue held
        at the start is not counted."""
        if len(self.stamps) < 2:
            raise ValueError("a step time needs two completed steps")
        return statistics.median(self.step_seconds)

    def steps_per_second(self) -> float:
        """The rate a steady loop runs at: one step per median step time.

        Not steps 2..n over (t_n - t_1): where the host sets the pace, a
        handful of stalls of the shared machine (hundreds of ms each, a
        different handful every run) moved that mean by 20% between two
        runs of the same code on the v5e while the median moved by 0.3%
        (PERF.md, Findings). What the stalls cost is `stall_share`."""
        return 1.0 / self.median_step_seconds()

    def stall_share(self) -> float:
        """The share of (t_n - t_1) that the steps took beyond a median
        step each: 0 for a steady loop."""
        whole = self.stamps[-1] - self.stamps[0]
        return max(0.0, 1.0 - (len(self.stamps) - 1)
                   * self.median_step_seconds() / whole)


def run_steps(dispatch, done, block=float, clock=time.perf_counter) -> Window:
    """Drive `dispatch()` as a user's loop does: step i+1 is dispatched
    before the host blocks on step i's loss (`block(loss)`), and each
    completion is stamped. `done(window)` is asked after every completion;
    the step already in flight then completes and is stamped too.

    A step that raises ends the loop: the state it donated is gone."""
    w = Window()

    def next_step():
        try:
            loss = dispatch()
        except Exception:  # counted as a failed step; the run reports it
            traceback.print_exc()
            w.raised += 1
            return None
        w.dispatched += 1
        return loss

    def complete(loss) -> bool:
        try:
            w.losses.append(block(loss))
        except Exception:  # a step's failure surfaces where it is awaited
            traceback.print_exc()
            w.raised += 1
            return False
        w.stamps.append(clock())
        return True

    pending = next_step()
    while pending is not None:
        following = next_step()
        if not complete(pending):
            break
        pending = following
        if pending is not None and done(w):
            complete(pending)
            break
    return w


def for_seconds(seconds: float, clock=time.perf_counter):
    """A `done` that ends the loop once `seconds` have passed since it was
    made (make it right before the loop)."""
    start = clock()
    return lambda w: clock() - start >= seconds


def for_steps(n: int):
    """A `done` that ends the loop with exactly `n` completed steps."""
    return lambda w: len(w.stamps) >= n - 1
