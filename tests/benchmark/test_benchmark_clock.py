"""The window clock, on a clock of the test's own."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.clock import TRACE_STEPS, WindowClock  # noqa: E402


class FakeTime:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeTracer:
    def __init__(self, log):
        self.log = log

    def start(self):
        self.log.append("start")

    def stop(self):
        self.log.append("stop")

    def step_annotation(self, index):
        import contextlib

        self.log.append(("step", index))
        return contextlib.nullcontext()

    def annotation(self, name):
        import contextlib

        self.log.append(name)
        return contextlib.nullcontext()


def test_only_completed_steps_count_and_the_window_is_block_to_block():
    now = FakeTime()
    clock = WindowClock(10.0, 8192, (10.5, 11.2), now=now)
    clock.start()
    for _ in range(5):  # five dispatched, three completed when time is up
        assert not clock.expired()
        now.t += 2.0
        clock.dispatched()
    for loss in (10.9, 10.95, 11.0):
        clock.completed(loss)
    assert clock.expired()  # time was up when the fifth step was dispatched
    now.t += 2.5  # the wait for the device at the window's end counts
    clock.stop()
    assert (clock.attempted, clock.completed_steps, clock.failed) == (5, 3, 0)
    assert clock.window_s == 12.5
    assert clock.window_tokens_per_s == 3 * 8192 / 12.5
    assert clock.tokens_per_s == 3 * 8192 / 12.5  # too short for one group: the window's rate


def _window_of_steps(intervals, group_steps):
    now = FakeTime()
    clock = WindowClock(1e9, 1000, (0, 20), group_steps=group_steps, now=now)
    clock.start()
    for dt in intervals:
        now.t += dt
        clock.dispatched()
        clock.completed(11.0)
    clock.stop()
    return clock


def test_the_rate_is_read_from_median_steps_and_a_rare_stall_is_not_in_it():
    # 1 + 20 steps of 0.25 s; three of them stall, two at the same position.
    intervals = [0.25] * 21
    for i, stall in ((3, 0.1), (10, 2.0), (14, 0.08)):
        intervals[i] += stall
    clock = _window_of_steps(intervals, group_steps=4)
    assert clock.step_medians == pytest.approx([0.25] * 4)
    assert clock.tokens_per_s == pytest.approx(4000.0)
    assert clock.window_tokens_per_s == pytest.approx(21 * 1000 / 7.43)  # the stalls show here
    assert abs(clock.stall_share() - 2.18 / 7.18) < 1e-9  # and here, from the first completion on


def test_a_cost_that_comes_round_with_the_group_is_in_the_rate():
    # Every fourth step pulls a block for 0.1 s more, wherever the groups begin.
    for offset in range(4):
        intervals = [0.25 + (0.1 if (i + offset) % 4 == 0 else 0.0) for i in range(25)]
        clock = _window_of_steps(intervals, group_steps=4)
        assert sorted(clock.step_medians) == pytest.approx([0.25, 0.25, 0.25, 0.35])
        assert abs(clock.tokens_per_s - 4000 / 1.1) < 1e-6
        assert abs(clock.stall_share()) < 1e-9
    # too few steps for one group: the window's rate, and no stall share
    short = _window_of_steps([0.25] * 4, group_steps=4)
    assert short.step_medians == [] and short.stall_share() is None
    assert short.tokens_per_s == short.window_tokens_per_s == 4000.0


def test_the_stall_share_leaves_out_the_steps_round_the_traced_ones():
    now, log = FakeTime(), []
    clock = WindowClock(5.0, 1000, (0, 20), group_steps=4, now=now, tracer=FakeTracer(log))
    clock.start()
    for i in range(40):
        with clock.step():
            now.t += 2.25 if i in (8, 16) else 0.25  # the profiler starts before step 8, stops after 15
            clock.dispatched()
            clock.completed(11.0)
    clock.stop()
    assert clock.traced_steps == (8, 15)
    assert clock.tokens_per_s == 4000.0
    assert abs(clock.stall_share()) < 1e-9
    assert clock.stall_share(margin=0) > 0.2  # step 16 waits for the profiler to stop


def test_a_loss_that_is_not_finite_or_leaves_the_band_is_a_failed_step():
    clock = WindowClock(1.0, 1, (10.5, 11.2), now=FakeTime())
    clock.start()
    for loss in (10.9, float("nan"), float("inf"), 3.0, 11.3):
        clock.completed(loss)
    assert (clock.completed_steps, clock.failed) == (5, 4)


def test_the_gang_votes_on_the_end_behind_each_dispatched_step():
    now, cast = FakeTime(), []
    clock = WindowClock(10.0, 1, (0, 1), now=now,
                        begin_vote=lambda flag: cast.append(flag) or ("handle", len(cast)),
                        end_vote=lambda handle: handle == ("handle", 2))
    clock.start()
    assert not clock.expired()  # nothing dispatched, nothing voted
    clock.dispatched()
    assert not clock.expired() and cast == [False]
    now.t += 11.0
    clock.dispatched()  # this rank's time is up: it says so with this step
    assert cast == [False, True] and clock.expired()  # and the gang's answer is read after it


def test_the_profiler_sees_eight_steps_after_two_fifths_of_the_window():
    now, log = FakeTime(), []
    clock = WindowClock(10.0, 1, (0, 20), now=now, tracer=FakeTracer(log))
    clock.start()
    for i in range(20):
        with clock.step(lambda: log.append("drain")):
            with clock.span("dispatch"):
                now.t += 0.5
    steps = [e for e in log if isinstance(e, tuple)]
    assert log[0] == "start" and steps == [("step", i) for i in range(8, 8 + TRACE_STEPS)]
    assert log[-2:] == ["drain", "stop"]  # the device's work is waited for, then the trace ends
    assert log.count("bench.dispatch") == TRACE_STEPS and log.count("start") == 1
    assert len(clock.spans["dispatch"]) == 20 and clock.span_ms_per_step("dispatch") == 500.0
    clock.close_tracer()
    assert log.count("stop") == 1


def test_a_window_that_ends_inside_the_traced_steps_still_stops_the_profiler():
    now, log = FakeTime(), []
    clock = WindowClock(10.0, 1, (0, 20), now=now, tracer=FakeTracer(log))
    clock.start()
    now.t += 5.0
    with clock.step():
        pass
    clock.close_tracer()
    assert log == ["start", ("step", 0), "stop"]
