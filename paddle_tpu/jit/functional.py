"""Functionalisation: run a stateful Layer as a pure jax function.

This is the TPU-native replacement for the reference's dygraph-to-static
bridge (`python/paddle/fluid/dygraph/dygraph_to_static/program_translator.py`
+ `partial_program.py`): instead of AST-transforming python into a static
Program run by InterpreterCore, we temporarily bind traced arrays into the
layer's Parameters/buffers and trace the ordinary eager forward under
`jax.jit` — XLA is the static executor (SURVEY.md §7.5).
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax
import jax.numpy as jnp

from ..core import autograd
from ..core import random as rng_mod
from ..core.tensor import Tensor
from ..profiler import metrics as _metrics


# jax records this duration once for every executable it builds — from
# the compiler or from the persistent compilation cache — around
# `compile_or_get_cached`, in the thread that made the call, tagged
# `fun_name="jit(<name of the jitted function>)"`
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileCounter:
    """Counts XLA compilations per thread through the public
    `jax.monitoring` duration listener.

    `instrumented_jit` brackets each call with `begin(name)` / `end()`;
    `end()` returns how many executables jax built FOR THAT FUNCTION
    inside the bracket. Matching on the name matters: a call can also
    build small executables that are not the function's own (resharding
    an argument onto a mesh compiles `_multi_slice`; an eager op on a
    closed-over constant compiles itself), and those are not
    recompiles of the step. The listener is registered on first use
    and then PROVED on a throwaway jit: if this jax no longer emits
    the event, or names it differently, the counter raises instead of
    reporting "no compiles" for ever."""

    def __init__(self):
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._ready = False

    def _on_duration(self, event, duration, fun_name=None, **kwargs):
        del duration, kwargs
        if event == _COMPILE_EVENT:
            frames = getattr(self._tls, "frames", None)
            if frames and fun_name == frames[-1][0]:
                frames[-1][1] += 1

    def _prove(self):
        with self._lock:
            if self._ready:
                return
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)

            def compile_counter_probe(x):
                return x + 1

            self._tls.frames = [["jit(compile_counter_probe)", 0]]
            try:
                jax.jit(compile_counter_probe).lower(
                    jax.ShapeDtypeStruct((), jnp.int32)).compile()
                heard = self._tls.frames[-1][1]
            finally:
                self._tls.frames = []
            if heard != 1:
                raise RuntimeError(
                    f"jax {jax.__version__} recorded {heard} "
                    f"{_COMPILE_EVENT!r} events named "
                    "'jit(compile_counter_probe)' for one fresh "
                    "compile: the compile counter behind "
                    "instrumented_jit and the one-compile watchdog "
                    "cannot count on this installation")
            self._ready = True

    def begin(self, name):
        if not self._ready:
            self._prove()
        frames = getattr(self._tls, "frames", None)
        if frames is None:
            frames = self._tls.frames = []
        frames.append([f"jit({name})", 0])

    def end(self) -> int:
        return self._tls.frames.pop()[1]


_compile_counter = _CompileCounter()


def instrumented_jit(fn, name, **jit_kwargs):
    """`jax.jit` with compile accounting. The function is jitted UNDER
    `name` — so the HLO module, profiler traces and jax's own compile
    events all carry the entry point's stable name instead of whatever
    the closure happened to be called — and every call is bracketed by
    the process's `_CompileCounter`, which knows how many executables
    jax built for it: the running total is `call.compile_count()`.
    Fresh compiles also increment paddle_tpu_jit_compiles_total{fn=name}
    (and add their wall time to
    paddle_tpu_jit_compile_seconds_total{fn=name}) when profiler
    metrics are enabled, and are reported to every active
    `analysis.guards` compile-count watchdog keyed by (name, THIS
    wrapper) — so per-instance one-compile budgets hold even with
    metrics off."""
    from ..analysis import guards as _guards

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)
    named.__name__ = named.__qualname__ = name
    jitted = jax.jit(named, **jit_kwargs)
    instance = _guards.next_instance_id()
    total = 0

    @functools.wraps(fn)
    def call(*args, **kwargs):
        nonlocal total
        timed = _metrics._enabled
        t0 = time.perf_counter() if timed else 0.0
        _compile_counter.begin(name)
        try:
            out = jitted(*args, **kwargs)
        finally:
            compiled = _compile_counter.end()
        if compiled > 0:
            total += compiled
            if timed:
                _metrics.JIT_COMPILES.labels(name).inc(compiled)
                # dt spans trace+compile+first execution — the honest
                # cost of hitting an uncompiled signature
                _metrics.JIT_COMPILE_SECONDS.labels(name).inc(
                    time.perf_counter() - t0)
            _guards.notify_compile(name, instance, compiled)
        return out

    def rejit():
        """A NEW `jax.jit` of the same function under the same name and
        options. jax keys its in-process caches by the function object,
        so what is lowered and compiled through it is built anew even
        when `jitted` already holds an executable (what
        `core.compile_cache.compile_fresh` needs)."""
        @functools.wraps(named)
        def again(*args, **kwargs):
            return fn(*args, **kwargs)
        return jax.jit(again, **jit_kwargs)

    call._jitted = jitted
    call.rejit = rejit
    call._watchdog_instance = instance
    call.compile_count = lambda: total
    return call


@contextlib.contextmanager
def bind_arrays(tensors, arrays):
    old = [t._data for t in tensors]
    for t, a in zip(tensors, arrays):
        t._data = a
    try:
        yield
    finally:
        for t, o in zip(tensors, old):
            t._data = o


def split_state(layer):
    """(param_names, param_tensors, buffer_names, buffer_tensors)."""
    p_names, p_tensors = [], []
    for n, p in layer.named_parameters():
        p_names.append(n)
        p_tensors.append(p)
    b_names, b_tensors = [], []
    for n, b in layer.named_buffers():
        b_names.append(n)
        b_tensors.append(b)
    return p_names, p_tensors, b_names, b_tensors


def call_functional(layer, param_tensors, buffer_tensors, param_arrays,
                    buffer_arrays, args, rng_key, grad_params=True):
    """Run layer(*args) with the given arrays bound in; returns
    (outputs_arrays, new_buffer_arrays). Tape is disabled — gradients come
    from jax AD over this function."""
    wrapped = [a if isinstance(a, Tensor) else Tensor(a) for a in args]
    with bind_arrays(param_tensors, param_arrays), \
            bind_arrays(buffer_tensors, buffer_arrays), \
            rng_mod.functional_rng(rng_key), autograd.no_grad():
        out = layer(*wrapped)
        new_buffers = [b._data for b in buffer_tensors]
    return out, new_buffers


def tree_arrays(x):
    """Extract raw arrays from Tensor/list/tuple/dict structures."""
    if isinstance(x, Tensor):
        return x._data
    if isinstance(x, (list, tuple)):
        return type(x)(tree_arrays(v) for v in x)
    if isinstance(x, dict):
        return {k: tree_arrays(v) for k, v in x.items()}
    return x
