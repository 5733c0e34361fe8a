"""Where set-up time goes: the seconds JAX spends building each executable
of the serving engine and the ZeRO trainer, and whether the persistent
compile cache held it, counted by the family of executable that asked.

JAX reports three phases of every build through `jax.monitoring`, each
with a marker at its start (a scalar) and its duration at its end:
tracing to a jaxpr, lowering to a module, and the backend's compile
(which is a load where the persistent cache holds the executable; the
cache's hit or miss fires inside it). `install()`, called by every
`ServingEngine` and `ZeroTrainStep` built, registers one listener of
each kind, which turn those events into two counters of the
process-wide `global_registry()` (the jit caches are shared by every
engine over a model, so the count is the process's):

- `jit_compile_seconds_total{family, phase}`, `phase` one of `trace`,
  `lower`, `backend`;
- `jit_executables_total{family, cache}`, one a backend compile,
  `cache` `hit`, `miss`, or `off` where the persistent cache neither
  held the executable nor kept it.

A build belongs to the family of the innermost `span()` open on its
thread: the engine opens `serving.compile` around the dispatch of a new
input shape, the trainer `train.compile` around its first step of one.
With no span open, a build made while the engine's or the trainer's own
code is on the stack (an eager op on a new shape, a small helper jit, a
collective probe) counts as `host_ops`, and any other (a caller's own
jit, a reference) is not counted. Every inner jit and jitted `jnp`
function traced inside another's trace reports its own trace, within
the outer one's seconds: only the outermost event of a phase is counted.
Nothing here runs unless JAX is building something.
"""
from __future__ import annotations

import contextlib
import sys
import threading

import jax

from ..profiler import RecordEvent
from . import global_registry

__all__ = ["install", "span", "BUILT", "HOST_OPS", "SECONDS",
           "EXECUTABLES"]

SECONDS = "jit_compile_seconds_total"
EXECUTABLES = "jit_executables_total"
HOST_OPS = "host_ops"

# jax._src.dispatch's event names, one a phase
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}
# modules whose frames on the stack make a build with no span open the
# program's own
_OWNERS = frozenset({"paddle_tpu.serving.engine", "paddle_tpu.parallel.zero"})


class _Thread(threading.local):
    def __init__(self):
        self.families = []                  # the open spans' families
        self.depth = dict.fromkeys(_PHASES.values(), 0)
        self.cache = "off"                  # of the backend compile open


_STATE = _Thread()


def _family():
    """The family the build on this thread belongs to, or None."""
    if _STATE.families:
        return _STATE.families[-1]
    frame = sys._getframe(2)
    while frame is not None:
        if frame.f_globals.get("__name__") in _OWNERS:
            return HOST_OPS
        frame = frame.f_back
    return None


def _on_start(event, _value, **_):
    phase = _PHASES.get(event)
    if phase is not None:
        _STATE.depth[phase] += 1


def _on_end(event, seconds, **_):
    phase = _PHASES.get(event)
    if phase is None:
        return
    depth = _STATE.depth[phase] = max(_STATE.depth[phase] - 1, 0)
    if depth:
        return
    if phase == "backend":
        cache, _STATE.cache = _STATE.cache, "off"
    family = _family()
    if family is None:
        return
    registry = global_registry()
    registry.counter(SECONDS, "seconds JAX spent building executables",
                     labels={"family": family, "phase": phase}).inc(seconds)
    if phase == "backend":
        registry.counter(EXECUTABLES, "executables built; cache says "
                         "whether the persistent compile cache held them",
                         labels={"family": family, "cache": cache}).inc()


def _on_cache(event, **_):
    found = _CACHE.get(event)
    if found is not None:
        _STATE.cache = found


_LISTENERS = (
    (jax.monitoring.register_scalar_listener,
     jax.monitoring.unregister_scalar_listener, _on_start),
    (jax.monitoring.register_event_duration_secs_listener,
     jax.monitoring.unregister_event_duration_listener, _on_end),
    (jax.monitoring.register_event_listener,
     jax.monitoring.unregister_event_listener, _on_cache),
)
_LOCK = threading.Lock()


def install() -> None:
    """Register the three listeners, each once. Called again, it puts
    back any that `jax.monitoring.clear_event_listeners()` dropped (it
    drops the duration and event listeners, not the scalar ones), and
    forgets the phases this thread saw start and not end meanwhile.
    Never called while JAX builds."""
    with _LOCK:
        for register, unregister, listener in _LISTENERS:
            try:
                unregister(listener)
            except (AssertionError, ValueError):     # was not registered
                pass
            register(listener)
        _STATE.depth = dict.fromkeys(_PHASES.values(), 0)
        _STATE.cache = "off"


# what a dispatch holds whose executable is built already
BUILT = contextlib.nullcontext()


class span:
    """`with span("serving.compile", "decode", key=16):` charges what JAX
    builds inside it to the family `decode`, under a `RecordEvent` of
    that name whose attributes are the family and `attrs`."""

    __slots__ = ("family", "_event")

    def __init__(self, name: str, family: str, **attrs):
        self.family = family
        self._event = RecordEvent(name, family=family, **attrs)

    def __enter__(self):
        _STATE.families.append(self.family)
        self._event.begin()
        return self

    def __exit__(self, *exc):
        self._event.end()
        _STATE.families.pop()
        return False
