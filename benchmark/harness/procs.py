"""The parent's view of its processes: which it started, a watchdog that kills
them all on a hang (after `chip_smoke.py`'s), and the CPU they used."""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Dict, List, Tuple

_TICK = os.sysconf("SC_CLK_TCK")


def _stat(pid) -> List[str]:
    with open(f"/proc/{pid}/stat") as fh:
        return fh.read().rsplit(")", 1)[1].split()


def process_start_wall() -> float:
    """When this process was started, on the wall clock."""
    with open("/proc/uptime") as fh:
        uptime = float(fh.read().split()[0])
    return time.time() - (uptime - int(_stat(os.getpid())[19]) / _TICK)


def descendants() -> List[int]:
    """Live processes this one started, children of children included
    (zombies have no command line and are left out)."""
    parent_of = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            ppid = int(_stat(pid)[1])
            with open(f"/proc/{pid}/cmdline", "rb") as fh:
                if fh.read():
                    parent_of[int(pid)] = ppid
        except (OSError, ValueError, IndexError):
            continue
    found, frontier = [], {os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent_of.items() if pp in frontier and p not in found}
        found.extend(frontier)
    return found


def kill_descendants() -> None:
    for pid in descendants():
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


class Watchdog:
    """A phase that hangs (a worker waiting for a chip, a gang that never
    joins) must not sit on the chip until someone else's time limit."""

    def __init__(self):
        self._timer = None

    def arm(self, phase: str, seconds: float) -> None:
        self.disarm()

        def fire():
            print(f"BENCHMARK FAILED: phase {phase!r} still running after {seconds:.0f}s; "
                  "killing every process this run started", flush=True)
            kill_descendants()
            os._exit(1)

        self._timer = threading.Timer(seconds, fire)
        self._timer.daemon = True
        self._timer.start()

    def disarm(self) -> None:
        if self._timer is not None:
            self._timer.cancel()


class CpuSampler:
    """User + system CPU seconds of this process and every descendant, sampled
    on a thread; a process that has exited keeps what it was last seen with."""

    def __init__(self, period_s: float = 0.25):
        self._period = period_s
        self._last: Dict[int, float] = {}
        self.samples: List[Tuple[float, float]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _sample(self) -> None:
        for pid in [os.getpid()] + descendants():
            try:
                f = _stat(pid)
                self._last[pid] = (int(f[11]) + int(f[12])) / _TICK
            except (OSError, ValueError, IndexError):
                continue
        self.samples.append((time.time(), sum(self._last.values())))

    def _run(self) -> None:
        while not self._stop.wait(self._period):
            self._sample()

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)

    def cpu_seconds_between(self, wall0: float, wall1: float) -> float:
        """CPU seconds used between two wall times, by linear interpolation."""

        def at(t: float) -> float:
            before = [s for s in self.samples if s[0] <= t]
            after = [s for s in self.samples if s[0] > t]
            if not before or not after:
                return (before or after)[-1 if before else 0][1]
            (t0, c0), (t1, c1) = before[-1], after[0]
            return c0 + (c1 - c0) * (t - t0) / (t1 - t0)

        return at(wall1) - at(wall0) if self.samples else 0.0
