"""What both drivers share: compile counting, the profiler session,
percentiles, and the list of numbers compared beside their limits."""
from __future__ import annotations

import os
import shutil
import time

import jax

from . import spec

TRACE_DIR = os.path.join(os.path.dirname(spec.ROOT), ".chipbench_trace")


# Look-ups of the persistent compile cache since the process began: a
# warm run's set-up has no miss.
CACHE = {"hits": 0, "misses": 0}


def _on_cache_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        CACHE["misses"] += 1


jax.monitoring.register_event_listener(_on_cache_event)


class CompileCounter:
    """Counts the executables XLA is asked for from now on, whether it
    compiles them or finds them in the persistent cache. A window in
    which one is asked for was not warmed up."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _counters: list = []
    _installed = False

    def __init__(self):
        self.count = 0
        cls = CompileCounter
        if not cls._installed:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._installed = True
        cls._counters.append(self)

    @classmethod
    def _on(cls, event, _seconds, **_):
        if event == cls._EVENT:
            for c in cls._counters:
                c.count += 1

    def close(self) -> int:
        CompileCounter._counters.remove(self)
        return self.count


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between the
    order statistics, as numpy's default."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class TraceSession:
    """A profiler trace of a slice of the window: started once the
    window is under way, stopped `seconds` later. The python tracer is
    off: it slows the host and the readers want only device operations
    and `TraceAnnotation` spans."""

    def __init__(self, seconds: float, after: float):
        self.seconds, self.after = float(seconds), float(after)
        self.started = self.stopped = None
        # seconds the host has spent starting and stopping the profiler:
        # nothing is dispatched meanwhile, so a traced run's rates are
        # taken over the window less what of this fell inside it, and
        # its tails over the requests that these stalls did not touch
        self.overhead = 0.0
        self.stalls = []        # (from, to) on time.perf_counter()

    def tick(self, elapsed: float) -> None:
        """Call between steps with the seconds since the window began."""
        if self.started is None and elapsed >= self.after:
            t0 = time.perf_counter()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            self.started = time.perf_counter()
            self.overhead += self.started - t0
            self.stalls.append((t0, self.started))
        elif (self.started is not None and self.stopped is None
              and time.perf_counter() - self.started >= self.seconds):
            self.stop()

    def stop(self) -> None:
        if self.started is not None and self.stopped is None:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stopped = time.perf_counter()
            self.overhead += self.stopped - t0
            self.stalls.append((t0, self.stopped))

    def path(self):
        """The `.xplane.pb` the session wrote, or None."""
        for root, _dirs, files in os.walk(TRACE_DIR):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        return None


class Checks:
    """The numbers `correct` compares, each beside its limit. A check
    holds when its value is at most its limit (`most`) or, for counts
    that must be met exactly, equal to it."""

    def __init__(self):
        self.rows = []
        self.notes = {}

    def note(self, name: str, value) -> None:
        """A number that is read and printed but not compared: PERF.md
        says why it has no limit."""
        self.notes[name] = float(value)

    def most(self, name: str, value, limit) -> None:
        self.rows.append((name, float(value), float(limit),
                          bool(value <= limit)))

    def equal(self, name: str, value, limit) -> None:
        self.rows.append((name, float(value), float(limit),
                          bool(value == limit)))

    @property
    def ok(self) -> bool:
        return all(r[3] for r in self.rows)

    def as_dict(self) -> dict:
        return {name: {"value": value, "limit": limit, "ok": ok}
                for name, value, limit, ok in self.rows}


def stand_in_line(checks: Checks) -> dict:
    """A control's or a planted fault's readings as `chipbench.control`
    prints them: every number read, `ok` as a run's `correct` would be,
    and the numbers that failed."""
    rows = checks.as_dict()
    return {**checks.notes, **{k: v["value"] for k, v in rows.items()},
            "ok": checks.ok,
            "failed": sorted(k for k, v in rows.items() if not v["ok"])}
