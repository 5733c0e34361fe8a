"""RNG state management.

Paddle has a global generator (`paddle.seed`) plus Fleet's RNGStatesTracker for
parallel-consistent dropout (ref: fleet/meta_parallel/parallel_layers/random.py,
upstream layout, unverified — mount empty).

TPU-native design: threefry counter keys. Two modes:
  * eager: a global mutable key, split on every draw;
  * traced (inside jit): a `rng_guard(key)` context supplies a base key that is
    split deterministically per draw, so the same program always consumes keys
    functionally — no hidden state inside compiled code.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import numpy as np


def _key_impl() -> Optional[str]:
    """RNG implementation for framework keys. Default: threefry (jax's
    default — reproducible across backends). PADDLE_TPU_RNG_IMPL=rbg swaps
    in XLA's RngBitGenerator, which lowers to the TPU's hardware PRNG —
    which spares threefry's 20 u32 rounds a dropout mask (no cell of
    `chipbench` draws a mask yet: `PERF.md` section 7). Masks
    are then not bit-reproducible across backends, which Paddle's dropout
    contract does not promise."""
    return os.environ.get("PADDLE_TPU_RNG_IMPL") or None


class Generator:
    """Mutable RNG stream over a threefry key."""

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        # LAZY key creation: jax.random.key() is a computation that would
        # initialize the XLA backend at `import paddle_tpu` time — which
        # breaks jax.distributed.initialize (must run before backend init)
        # in real multi-process jobs
        self._key = None
        # trace-mode stack: (base_key, counter_list)
        self._trace_stack = []

    def _ensure_key(self):
        if self._key is None:
            impl = _key_impl()
            self._key = (jax.random.key(self._seed, impl=impl) if impl
                         else jax.random.key(self._seed))
        return self._key

    def manual_seed(self, seed: int):
        # stays lazy like __init__: paddle.seed() before fleet.init() must
        # not initialize the XLA backend (breaks jax.distributed.initialize)
        self._seed = int(seed)
        self._key = None
        return self

    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        if self._trace_stack:
            base, counter = self._trace_stack[-1]
            counter[0] += 1
            return jax.random.fold_in(base, counter[0])
        self._key, sub = jax.random.split(self._ensure_key())
        return sub

    def get_state(self):
        return jax.random.key_data(self._ensure_key())

    def set_state(self, state):
        data = np.asarray(state, dtype=np.uint32)
        # the impl is recoverable from the data shape (threefry2x32 keys
        # are (2,) u32, rbg/unsafe_rbg (4,)), so state saved under one
        # PADDLE_TPU_RNG_IMPL setting restores under any other
        if data.shape and data.shape[-1] == 4:
            impl = _key_impl()
            if impl not in ("rbg", "unsafe_rbg"):
                impl = "rbg"
            self._key = jax.random.wrap_key_data(data, impl=impl)
        else:
            self._key = jax.random.wrap_key_data(data)

    @contextlib.contextmanager
    def trace_mode(self, base_key):
        """Within jit tracing: draw keys functionally from `base_key`."""
        self._trace_stack.append((base_key, [0]))
        try:
            yield
        finally:
            self._trace_stack.pop()


_DEFAULT_GENERATOR = Generator(0)


def default_generator() -> Generator:
    return _DEFAULT_GENERATOR


def seed(s: int) -> Generator:
    """paddle.seed"""
    _DEFAULT_GENERATOR.manual_seed(s)
    return _DEFAULT_GENERATOR


def next_key():
    return _DEFAULT_GENERATOR.next_key()


@contextlib.contextmanager
def rng_guard(base_key):
    """Supply the base key for a traced region (used by jitted train steps)."""
    with _DEFAULT_GENERATOR.trace_mode(base_key):
        yield


def get_rng_state():
    return _DEFAULT_GENERATOR.get_state()


def set_rng_state(state):
    _DEFAULT_GENERATOR.set_state(state)


class RNGStatesTracker:
    """Named RNG streams — Fleet's tracker for TP-consistent dropout.

    Model-parallel regions register a stream whose seed is offset by the mp
    rank so dropout masks differ across tensor-parallel shards while the
    default stream stays identical (Megatron semantics).
    """

    def __init__(self):
        self._states = {}

    def reset(self):
        self._states.clear()

    def add(self, name: str, seed_: int):
        if name in self._states:
            raise ValueError(f"rng state {name!r} already added")
        self._states[name] = Generator(seed_)

    def get_generator(self, name: str) -> Generator:
        if name not in self._states:
            raise KeyError(f"rng state {name!r} not found")
        return self._states[name]

    @contextlib.contextmanager
    def rng_state(self, name: str = "global_seed"):
        """Temporarily make the named stream the default generator."""
        global _DEFAULT_GENERATOR
        if name not in self._states:
            raise KeyError(f"rng state {name!r} not found; call add() first")
        prev = _DEFAULT_GENERATOR
        _DEFAULT_GENERATOR = self._states[name]
        try:
            yield
        finally:
            _DEFAULT_GENERATOR = prev


_MODEL_PARALLEL_TRACKER = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _MODEL_PARALLEL_TRACKER


def model_parallel_random_seed(seed_: int, mp_rank: int = 0):
    """Fleet parity: distinct 'local_seed' per mp rank, shared 'global_seed'."""
    tracker = get_rng_state_tracker()
    tracker.reset()
    tracker.add("global_seed", seed_)
    tracker.add("local_seed", seed_ + 1024 + mp_rank)
