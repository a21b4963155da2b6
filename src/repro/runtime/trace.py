"""Named host spans on the profiler's clock, and the compile counter.

Spans are off by default.  :func:`enable` switches them on; :func:`span`
then enters a ``jax.profiler.TraceAnnotation``, which lands on the
profiler's host plane, on the same clock as the device's ``XLA Ops``
line, so a trace of a step shows which span the host was in while the
device sat idle.  Spans nest on their thread: ``exec.compute`` inside
``exec.step``, ``optim.decode`` inside ``optim.update``.  Their arguments
name the unit of work they belong to (the optimizer's step count, the
serving request's user and arrival) and the layer an op ran.  With spans
off, :func:`span` reads one global and returns one shared no-op context
manager.

:data:`SPANS` lists every span name the package opens, by layer:

* planner — ``plan.compile`` (one ``compile_plan`` call) and
  ``plan.verify`` (the static verifier inside it);
* executor — ``exec.step`` (one replay of the op list through
  ``ExecutorBackend.run``), ``exec.compute`` (one ``Compute`` op),
  ``exec.block`` (one fused block of the ``jit_blocks`` backend),
  ``exec.swap_out`` / ``exec.swap_in`` (the transfer engine's copy of one
  ``SwapOut`` / ``Prefetch``, or of a late swap-in), ``exec.fence`` /
  ``exec.opt_fence`` (a device-stream wait on an in-flight activation or
  optimizer copy) and ``exec.drain`` (the fences at the end of a step);
* optimizer lane — ``optim.update`` (one ``offloaded_update``) and, per
  slot inside it, ``optim.decode`` (dequantize and log-decode the host
  copy), ``optim.unpack``, ``optim.adamw``, ``optim.pack`` and
  ``optim.encode`` (encode, quantize and error feedback);
* serving — ``serve.prepare`` (admission, bucket, plan cache),
  ``sched.open`` (a session's cursor), ``sched.advance`` (one phase of one
  session, the ``exec.*`` spans inside it), ``sched.finish`` (the result,
  its loss on the host and the replay proof) and ``serve.apply_update``.

Counts of transfers and bytes are ``SwapExecStats``'s, not spans'.

:func:`compile_counter` registers the process's one
:class:`CompileCounter` with ``jax.monitoring`` on its first call; from
then on it counts the programs JAX builds and the seconds it spends
building them.  It stays on, since it fires only when something
compiles; call it at process start to count every compile.
"""

from __future__ import annotations

import bisect
import contextlib
import threading
import time
from typing import List, Optional, Tuple

import jax

SPANS = (
    "plan.compile", "plan.verify",
    "exec.step", "exec.compute", "exec.block", "exec.swap_out",
    "exec.swap_in", "exec.fence", "exec.opt_fence", "exec.drain",
    "optim.update", "optim.decode", "optim.unpack", "optim.adamw",
    "optim.pack", "optim.encode",
    "serve.prepare", "sched.open", "sched.advance", "sched.finish",
    "serve.apply_update",
)
_NAMES = frozenset(SPANS)

_on = False
_OFF = contextlib.nullcontext()


def enable(on: bool = True) -> None:
    """Switch the spans on (or off again with ``on=False``)."""
    global _on
    _on = bool(on)


def span(name: str, **args):
    """A context manager around one unit of host work named ``name`` (one
    of :data:`SPANS`), with ``args`` recorded beside it."""
    if not _on:
        return _OFF
    if name not in _NAMES:
        raise ValueError(f"span {name!r} is not in trace.SPANS")
    return jax.profiler.TraceAnnotation(name, **args)


class CompileCounter:
    """Programs JAX builds and the host seconds spent building them.

    ``programs`` counts ``backend_compile_duration`` events: one per miss
    of JAX's in-memory executable cache, whether XLA compiled the program
    or loaded it from the persistent cache (``loads`` counts those).
    ``seconds`` adds tracing to a jaxpr, lowering to MLIR and the backend
    step; the backend step encloses the persistent cache's retrieval, so
    ``cache_s`` is a part of ``backend_s`` and is not added twice.
    """

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self, clock=time.perf_counter) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self.programs = 0
        self.loads = 0
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.backend_s = 0.0
        self.cache_s = 0.0
        # the host clock at each event's end, and (programs, seconds)
        # counted up to it
        self._ends: List[float] = []
        self._totals: List[Tuple[int, float]] = []

    @property
    def seconds(self) -> float:
        return self.trace_s + self.lower_s + self.backend_s

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        """The ``jax.monitoring`` duration listener."""
        with self._lock:
            if event == self.TRACE:
                self.trace_s += duration
            elif event == self.LOWER:
                self.lower_s += duration
            elif event == self.BACKEND:
                self.programs += 1
                self.backend_s += duration
            elif event == self.CACHE:
                self.loads += 1
                self.cache_s += duration
                return
            else:
                return
            self._ends.append(self._clock())
            self._totals.append((self.programs, self.seconds))

    def until(self, t: float) -> Tuple[int, float]:
        """``(programs, seconds)`` of the events that ended by ``t`` on the
        host clock (``time.perf_counter``)."""
        with self._lock:
            i = bisect.bisect_right(self._ends, t)
            return self._totals[i - 1] if i else (0, 0.0)


_COMPILES: Optional[CompileCounter] = None
_COMPILES_LOCK = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process's compile counter, registered on the first call."""
    global _COMPILES
    with _COMPILES_LOCK:
        if _COMPILES is None:
            _COMPILES = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(
                _COMPILES.on_duration)
        return _COMPILES
