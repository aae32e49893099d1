"""The measured window, as the worker's loop sees it: when it starts, when it
is over, which steps were dispatched and which completed inside it, and the
loop's own host spans. Plain Python and an injected clock, so that it is
tested without a device."""

from __future__ import annotations

import contextlib
import math
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

TRACE_STEPS = 8          # steady steps the profiler sees, in the middle of the window
TRACE_AFTER = 0.4        # of the window
GROUP_STEPS = 8          # positions the rate is read at: the fed mix pulls a 64-row block every 8 steps


class WindowClock:
    """Usage, in a loop file:

        clock.start()                       # after block_until_ready
        while not clock.expired():
            with clock.step():
                with clock.span("dispatch"): ...; clock.dispatched()
                with clock.span("sync"): loss = float(...); clock.completed(loss)
        clock.stop()                        # after block_until_ready

    Every rank of a gang must leave the loop at the same step, so "time is
    up" is put to a vote: `dispatched()` casts this rank's (`begin_vote`, one
    tiny jitted sum queued behind the step it follows, so the device never
    waits for it) and the next `expired()` reads the gang's (`end_vote`).
    Alone, the vote is the flag. `tracer` starts and stops the profiler and
    wraps steps and spans in annotations while it runs.
    """

    def __init__(self, seconds: float, tokens_per_step: int, loss_band, *,
                 group_steps: int = GROUP_STEPS,
                 now: Callable[[], float] = time.perf_counter,
                 begin_vote: Callable[[bool], Any] = bool,
                 end_vote: Callable[[Any], bool] = bool,
                 tracer: Optional[Any] = None):
        self.seconds = seconds
        self.tokens_per_step = tokens_per_step
        self.loss_band = loss_band
        self.group_steps = group_steps
        self._now = now
        self._begin_vote = begin_vote
        self._end_vote = end_vote
        self._vote: Any = False
        self._tracer = tracer
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self.wall0: Optional[float] = None
        self.attempted = 0
        self.completed_steps = 0
        self.failed = 0
        self.losses: List[float] = []
        self.completed_at: List[float] = []
        self.spans: Dict[str, List[float]] = {}
        self._steps_begun = 0
        self._traced = 0
        self._tracing = False
        self.traced_steps: Optional[Tuple[int, int]] = None  # first and last index

    # ---- the window
    def start(self) -> None:
        self.t0 = self._now()
        self.wall0 = time.time()

    def expired(self) -> bool:
        """True once `seconds` had passed, on any rank, when the last step was
        dispatched: the window runs at most one step past its length."""
        return self._end_vote(self._vote)

    def stop(self) -> None:
        self.t1 = self._now()

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def window_tokens_per_s(self) -> float:
        """Tokens of the steps that completed inside the window, per second of it."""
        return self.completed_steps * self.tokens_per_step / self.window_s

    @property
    def step_medians(self) -> List[float]:
        """For each of the `group_steps` positions in a group of consecutive
        steps, the median over the window of the time between the completion
        of a step at that position and of the step before it."""
        gaps = [b - a for a, b in zip(self.completed_at, self.completed_at[1:])]
        if len(gaps) < self.group_steps:
            return []
        return [_median(gaps[p::self.group_steps]) for p in range(self.group_steps)]

    @property
    def tokens_per_s(self) -> float:
        """Tokens of a group of steps over the sum of `step_medians`. What
        every step costs, and what comes round with the group (a block pull
        every eighth step), is in it; a stall now and then (a neighbour on the
        host's cores: 20-100 ms in a few steps of a hundred) is not, and shows
        as `stall_share` and in `window_tokens_per_s`. A window too short for
        one group gives the window's rate."""
        medians = self.step_medians
        if not medians:
            return self.window_tokens_per_s
        return self.group_steps * self.tokens_per_step / sum(medians)

    def stall_share(self, margin: int = 4) -> Optional[float]:
        """The share of the time between completions that a rate read the way
        `tokens_per_s` is leaves out, over the steps at least `margin` away
        from the traced ones (the profiler's own start and stop stall the
        loop). Nothing where that leaves a position under three readings."""
        lo, hi = self.traced_steps or (len(self.completed_at), -1)
        clear: List[List[float]] = [[] for _ in range(self.group_steps)]
        for i, (a, b) in enumerate(zip(self.completed_at, self.completed_at[1:])):
            if not lo - margin <= i + 1 <= hi + margin:
                clear[i % self.group_steps].append(b - a)
        if min(map(len, clear)) < 3:
            return None
        return 1.0 - sum(_median(gaps) * len(gaps) for gaps in clear) / sum(map(sum, clear))

    # ---- steps
    def dispatched(self) -> None:
        self.attempted += 1
        self._vote = self._begin_vote(self._now() - self.t0 >= self.seconds)

    def completed(self, loss: float) -> None:
        """The host has the loss of one more step: it ran to its end."""
        self.completed_steps += 1
        self.completed_at.append(self._now() - self.t0)
        self.losses.append(loss)
        lo, hi = self.loss_band
        if not (math.isfinite(loss) and lo <= loss <= hi):
            self.failed += 1

    @contextlib.contextmanager
    def step(self, drain: Optional[Callable[[], None]] = None):
        """One iteration of the loop. With a tracer, the profiler starts at the
        first step after `TRACE_AFTER` of the window and stops after
        `TRACE_STEPS` more, once `drain()` has waited for their device work."""
        tr = self._tracer
        if tr is not None and not self._tracing and not self._traced \
                and self._now() - self.t0 >= TRACE_AFTER * self.seconds:
            tr.start()
            self._tracing = True
        index = self._steps_begun
        self._steps_begun += 1
        if self._tracing:
            with tr.step_annotation(index):
                yield
            self._traced += 1
            self.traced_steps = (index - self._traced + 1, index)
            if self._traced == TRACE_STEPS:
                if drain is not None:
                    drain()
                tr.stop()
                self._tracing = False
        else:
            yield

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the loop: always timed, and an annotation in the
        profiler's trace while it runs."""
        ctx = self._tracer.annotation("bench." + name) if self._tracing else contextlib.nullcontext()
        t = self._now()
        with ctx:
            yield
        self.spans.setdefault(name, []).append(self._now() - t)

    def close_tracer(self) -> None:
        """A window that ended before the traced steps did."""
        if self._tracing:
            self._tracer.stop()
            self._tracing = False

    def span_ms_per_step(self, name: str) -> Optional[float]:
        """Median milliseconds of the span over the window's steps."""
        values = self.spans.get(name)
        return _median(values) * 1e3 if values else None


def _median(values: List[float]) -> float:
    values = sorted(values)
    mid = len(values) // 2
    return values[mid] if len(values) % 2 else (values[mid - 1] + values[mid]) / 2
