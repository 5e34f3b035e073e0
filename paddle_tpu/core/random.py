"""Stateful RNG facade over jax PRNG keys.

The reference exposes a global stateful generator (`paddle/phi/core/generator.h`,
`paddle.seed`). JAX is functional, so we keep a stack of RNG states: the base
state is a concrete key advanced by splitting; `functional_rng(key)` pushes a
state bound to a traced key so random layers (dropout etc.) stay correct inside
`jax.jit`-traced training steps — the caller supplies a fresh key per step.

Also provides the TP rng-state tracker capability
(`python/paddle/distributed/fleet/meta_parallel/parallel_layers/random.py`:
``get_rng_state_tracker`` — named local/global seeds so e.g. dropout masks are
replicated or varied across model-parallel ranks as required).
"""
from __future__ import annotations

import contextlib
import os

import jax
import numpy as np


def _use_rbg() -> bool:
    """TPU default: the hardware RngBitGenerator PRNG ('rbg') instead of
    threefry. Threefry is a software counter-based PRNG that costs real
    compute on TPU (measured 11.3 ms/step of a 65 ms BERT-base AMP
    train step just for dropout masks); rbg lowers to the on-chip RNG
    and is effectively free. Same design choice as T5X/MaxText.
    Opt out: PADDLE_TPU_RBG_RANDOM=0. Off-TPU keeps threefry (bitwise
    reproducibility of existing CPU tests)."""
    if os.environ.get("PADDLE_TPU_RBG_RANDOM", "1") != "1":
        return False
    from .place import on_tpu_backend
    return on_tpu_backend()


def make_key(s: int):
    """Seed -> PRNG key with the platform-appropriate implementation."""
    if _use_rbg():
        return jax.random.key(int(s), impl="rbg")
    return jax.random.PRNGKey(int(s))


class RNGState:
    def __init__(self, key):
        self.key = key

    def next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub


class _SeededState(RNGState):
    """Base state that builds its key from the seed on first use.
    Building a key initialises the jax backend, which claims the chip:
    `import paddle_tpu` and `paddle.seed()` must not do that, or a
    supervisor that only imports the package starves the worker it
    spawns."""

    def __init__(self, s: int):
        self._seed = int(s)
        self._key = None

    @property
    def key(self):
        if self._key is None:
            self._key = make_key(self._seed)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_stack = [_SeededState(0)]


def seed(s: int):
    """paddle.seed parity."""
    _stack[0] = _SeededState(s)
    return _stack[0]


def next_key():
    return _stack[-1].next_key()


def get_rng_state():
    return _stack[-1].key


def set_rng_state(key):
    _stack[-1].key = key


@contextlib.contextmanager
def functional_rng(key):
    """Bind the RNG to a (possibly traced) key for the duration of a trace."""
    _stack.append(RNGState(key))
    try:
        yield
    finally:
        _stack.pop()


class RNGStatesTracker:
    """Named rng states for tensor parallelism.

    Parity: fleet's ``RNGStatesTracker``
    (meta_parallel/parallel_layers/random.py) — 'global_seed' states are
    identical on all mp ranks, 'local_seed' states differ per rank so dropout
    inside column/row-parallel regions decorrelates.
    """

    def __init__(self):
        self.states_ = {}

    def add(self, name, s):
        if name in self.states_:
            raise ValueError(f"state {name} already exists")
        self.states_[name] = _SeededState(s)

    def reset(self):
        self.states_ = {}

    @contextlib.contextmanager
    def rng_state(self, name="model_parallel_rng"):
        if name not in self.states_:
            raise ValueError(f"state {name} not added")
        _stack.append(self.states_[name])
        try:
            yield
        finally:
            _stack.pop()


_tracker = RNGStatesTracker()


def get_rng_state_tracker():
    return _tracker


def model_parallel_random_seed(seed_: int, mp_rank: int = 0):
    global_seed = 100003 + seed_
    local_seed = seed_ + 2718 + mp_rank * 1024
    _tracker.reset()
    _tracker.add("global_seed", global_seed)
    _tracker.add("local_seed", local_seed)


def np_rng() -> np.random.Generator:
    """Host-side numpy generator for data pipelines."""
    return np.random.default_rng()
