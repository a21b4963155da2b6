"""Pluggable executor backends replaying the lowered ExecutionSchedule.

One interpreter, two realisations of its transfer ops:

* :class:`SimulatedBackend` (``"sim"``, the default) — synchronous host
  round trips through :class:`repro.core.exec.store.SyncHostEngine`;
  bit-for-bit the accounting the planner validation suite gates on;
* :class:`AsyncDeviceBackend` (``"async"``) — every ``SwapOut`` /
  ``Prefetch`` op is issued as a real ``jax.device_put`` against the
  device's ``pinned_host`` memory space, *dispatched* at its scheduled EO
  and fenced only when the consumer computes, so DMA overlaps the compute
  in between (the ROADMAP "async double-buffer on real device streams"
  item).  The backend measures
  ``inflight_high_water`` (achieved double-buffer occupancy) and the
  achieved-overlap fraction against the plan's
  ``peak_inflight_prefetch`` — see :meth:`AsyncDeviceBackend.report`.
* :class:`JitBlocksBackend` (``"jit_blocks"``) — async transfers plus
  jit-fused compute dispatch: the static dependence prover
  (:mod:`repro.core.verify.deps`) partitions the op list into
  fusion-legal ``Compute`` runs and each run replays as a *single*
  ``jax.jit`` call, collapsing the per-op Python dispatch loop.

The ``sim`` and ``async`` backends replay the compiled op list
*verbatim*: ``SwapExecStats.replayed_ops == lowered.ops`` is CI-gated per
backend, so a backend cannot silently skip or reorder a planned transfer.
``jit_blocks`` replays a *proven-equivalent permutation* instead — same
op multiset, every dependence edge preserved — and is admitted only after
:func:`repro.core.verify.schedules_equivalent` signs off on its fused
replay stream; CI gates that proof rather than positional equality.

Backends only replay *verified* schedules: a plan-backed schedule that has
not passed the static verifier (:mod:`repro.core.verify`) is verified on
admission and refused (``ScheduleVerificationError``) if unsound — the
runtime analogue of the ``compile_plan`` verify knob, so a schedule cannot
reach the device streams unchecked even when compile-time verification
was skipped.  A debug sanitizer mode (``sanitize=True`` on any backend
constructor, or ``REPRO_EXEC_SANITIZE=1``) additionally steps the
verifier's :class:`repro.core.verify.StaticResidencyModel` alongside the
real :class:`ActivationStore` and cross-checks device residency after
every replayed op.

Select a backend with ``MemoryPlanConfig(executor="sim" | "async")`` or by
passing ``executor=`` to :func:`swap_planned_loss_and_grads`; registry
lookups go through :func:`get_backend`.
"""

from __future__ import annotations

import dataclasses
import os
import weakref
from typing import Any, Dict, List, Optional, Protocol, Tuple, Union,\
    runtime_checkable

import jax
import numpy as np

from repro.core.exec.layers import (_needs_deriv, _param_owner,
                                    layer_calc_derivative,
                                    layer_calc_gradient, layer_forward,
                                    loss_derivative, loss_forward)
from repro.core.exec.store import (ActivationStore, DeviceStreamEngine,
                                   HbmTracker, SwapExecStats, SyncHostEngine,
                                   TransferEngine, commit)
from repro.core.execution_order import OrderedTensors, compute_execution_order
from repro.core.graph import LOSS_KINDS, WEIGHTED_KINDS, LayerGraph
from repro.core.offload import OffloadSchedule


@runtime_checkable
class ExecutorBackend(Protocol):
    """One way to execute a lowered :class:`ExecutionSchedule`.

    ``run`` performs one training iteration — replaying the op list
    verbatim — and returns ``(loss, grads, SwapExecStats)``; ``report``
    summarises what the last run did (transfer counts, high-water marks,
    and for real-stream backends the achieved overlap).
    """

    name: str

    def run(self, graph: LayerGraph, params, x, label, *,
            schedule: OffloadSchedule,
            ordered: Optional[OrderedTensors] = None,
            plan=None, lowered=None, mask=None
            ) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]],
                       SwapExecStats]: ...

    def report(self) -> Dict[str, Any]: ...


class _ComputeEnv:
    """The ``Compute``-op interpreter, decoupled from the ActivationStore.

    All layer math and backward-state threading (saved contexts, pending
    derivatives, gradient accumulation) lives here, parameterised over
    ``get``/``put`` activation accessors.  The per-op backends wire those
    to the live :class:`ActivationStore` (fencing on read); the
    ``jit_blocks`` backend wires them to a plain dict so a whole run of
    phases traces into one XLA computation.  One interpreter, both
    realisations — the two paths cannot drift apart semantically.
    """

    def __init__(self, graph: LayerGraph, params, label, mask, *, get, put):
        self.graph = graph
        self.params = params
        self.label = label
        self.mask = mask
        self.get = get          # (layer name) -> activation array
        self.put = put          # (layer name, array) -> None
        self.ctxs: Dict[str, Any] = {}
        self.derivs: Dict[str, jax.Array] = {}
        self.pending_dxs: Dict[str, List[Tuple[str, jax.Array]]] = {}
        self.pending_cd: Dict[str, Tuple[jax.Array, List[str]]] = {}
        self.grads: Dict[str, Dict[str, jax.Array]] = {}
        self.loss_val = None

    def resolve_ctx(self, ctx: Any) -> Any:
        return tuple(
            self.get(e[1])
            if isinstance(e, tuple) and len(e) == 2 and e[0] == "@act"
            else e
            for e in ctx
        )

    def read_names(self, op) -> List[str]:
        """Activation names this Compute may read — the consumer-fence set
        (its layer inputs plus its own output, which backward ctxs
        reference)."""
        return list(self.graph.layer(op.layer).inputs) + [op.layer]

    def step(self, op) -> None:
        """Execute one ``Compute`` op (kind "F" / "CG" / "CD")."""
        graph, params, label, mask = \
            self.graph, self.params, self.label, self.mask
        l = graph.layer(op.layer)
        lname, kind = op.layer, op.kind
        if kind == "F":
            if l.kind in LOSS_KINDS:
                self.loss_val = loss_forward(
                    l.kind, self.get(l.inputs[0]), label, mask)
            else:
                xs = [self.get(i) for i in l.inputs]
                p = params.get(_param_owner(graph, l))
                y, ctx = layer_forward(l, xs, p)
                self.put(lname, y)
                # keep saved activations by *reference* into the
                # store, so a swap moves the residual too (same
                # bytes in a real arena)
                sym = []
                for e in ctx:
                    hit = next(
                        (i for i, xi in enumerate(xs) if e is xi),
                        None)
                    if hit is not None:
                        sym.append(("@act", l.inputs[hit]))
                    elif e is y:
                        sym.append(("@act", lname))
                    else:
                        sym.append(e)
                self.ctxs[lname] = tuple(sym)
        elif kind == "CG":
            if l.kind in LOSS_KINDS:
                pred = l.inputs[0]
                self.derivs[pred] = loss_derivative(
                    l.kind, self.get(pred), label, mask)
            else:
                dy = self.derivs.pop(lname, None)
                if dy is not None:
                    if l.trainable and l.weight_shapes():
                        p = params.get(_param_owner(graph, l))
                        g = layer_calc_gradient(
                            l, self.resolve_ctx(self.ctxs[lname]), dy, p)
                        owner = _param_owner(graph, l)
                        if owner in self.grads:
                            self.grads[owner] = {
                                k: self.grads[owner][k] + g[k] for k in g}
                        else:
                            self.grads[owner] = g
                    upstream_needed = [
                        i for i in l.inputs
                        if i != "__input__" and _needs_deriv(graph, i)
                    ]
                    if not upstream_needed:
                        pass
                    elif l.kind in WEIGHTED_KINDS:
                        # A weighted layer's saved input has a F+CG
                        # lifespan — it is freed (or swapped) right
                        # after this phase — so its derivative is
                        # computed here, on the same resident
                        # context the CG just used, and *published*
                        # at the adjacent CD phase
                        # (EO_CD = EO_CG + 1).
                        p = params.get(_param_owner(graph, l))
                        dxs = layer_calc_derivative(
                            l, self.resolve_ctx(self.ctxs[lname]), dy, p)
                        self.pending_dxs[lname] = [
                            (inp, dx)
                            for inp, dx in zip(l.inputs, dxs)
                            if inp != "__input__"
                            and inp in upstream_needed
                        ]
                    else:
                        # In-place / pool / view layers have F+CD
                        # contexts (e.g. max-pool argmax source,
                        # activation output) — residency and
                        # prefetches target the CD phase.
                        self.pending_cd[lname] = (dy, upstream_needed)
        else:  # CD: compute deferred derivatives, publish D:<inp>
            dxs_out = self.pending_dxs.pop(lname, [])
            if lname in self.pending_cd:
                dy, upstream_needed = self.pending_cd.pop(lname)
                p = params.get(_param_owner(graph, l))
                dxs = layer_calc_derivative(
                    l, self.resolve_ctx(self.ctxs[lname]), dy, p)
                dxs_out = [
                    (inp, dx) for inp, dx in zip(l.inputs, dxs)
                    if inp != "__input__" and inp in upstream_needed
                ]
            for inp, dx in dxs_out:
                if inp in self.derivs:
                    self.derivs[inp] = self.derivs[inp] + dx
                else:
                    self.derivs[inp] = dx


def _check_opt_high_water(plan, stats: SwapExecStats) -> None:
    """Assert the replayed optimizer residency against the packed region
    (the optimizer-lane analogue of the activation residency-peak gate)."""
    optim = getattr(plan, "optim", None)
    if optim is not None \
            and stats.opt_device_high_water > optim.device_peak_bytes:
        raise AssertionError(
            f"optimizer working region exceeded the packed peak: "
            f"{stats.opt_device_high_water} > {optim.device_peak_bytes} "
            f"bytes")


class ScheduleCursor:
    """Resumable replay of one lowered schedule, preemptible at phase
    boundaries.

    Produced by :meth:`_ReplayBackend.start` (which runs the same verified
    admission as :meth:`run` — a cursor never exists for an unverified
    plan-backed schedule).  :meth:`advance` executes exactly one *phase*
    (every op sharing one EO: prefetches, the compute, swap-outs, frees)
    and returns True while phases remain; the phase boundary is the
    natural preemption point the serve-layer :class:`StepScheduler`
    round-robins sessions at, because all of this phase's DMA has been
    *issued* but need not be *fenced* until a later phase computes.

    After the last phase, :meth:`result` returns ``(loss, grads, stats)``
    with the same end-of-run drain, high-water assertions and stats
    finalisation ``run()`` has always performed.  :meth:`abort` abandons a
    step mid-flight: this cursor's in-flight transfers are fenced (so a
    shared engine holds no dangling references into the dead store) and
    every activation reference is dropped, making the session's arena
    share immediately reusable.

    ``stats.wall_time_s`` accumulates only the time spent *inside*
    ``advance``/``result`` — under interleaving, the wall-clock a session
    spends preempted is other tenants' compute, not this step's cost.
    """

    def __init__(self, backend: "_ReplayBackend", graph: LayerGraph,
                 params, x, label, *, schedule: OffloadSchedule,
                 ordered: OrderedTensors, plan, lowered, mask,
                 engine: TransferEngine, sanitizer, tag: str = ""):
        import time as _time

        self._clock = _time.perf_counter
        self.backend = backend
        self.graph = graph
        self.schedule = schedule
        self.ordered = ordered
        self.plan = plan
        self.lowered = lowered
        self.tag = tag
        self.engine = engine
        self.sanitizer = sanitizer
        self.stats = SwapExecStats(backend=backend.name)
        self.stats.inplace_prefetches = sum(
            1 for d in schedule.decisions if d.inplace)
        self.hbm = HbmTracker()
        self.store = ActivationStore(ordered, self.hbm, engine=engine)
        params, x, label, mask = commit((params, x, label, mask),
                                        getattr(engine, "device", None))
        self.store.device["__input__"] = x
        self.env = _ComputeEnv(graph, params, label, mask,
                               get=lambda n: self.store.get(n, self.stats),
                               put=self.store.put)
        self._replayed: List[Any] = []
        self._inflight = 0
        self._opt_resident = 0
        self._done_at: Dict[int, int] = {}
        self._opt_fence_at: Dict[int, List[str]] = {}
        self._retired_eo = -1
        # phase groups: runs of ops sharing one EO, in schedule order
        self._phases: List[List[Tuple[int, Any]]] = []
        cur_eo = None
        for i, op in enumerate(lowered.ops):
            if cur_eo is None or op.eo != cur_eo:
                self._phases.append([])
                cur_eo = op.eo
            self._phases[-1].append((i, op))
        self._next_phase = 0
        self._finished = False
        self.aborted = False
        self.last_advance_s = 0.0
        self._result: Optional[Tuple] = None

    # ------------------------------------------------------------ driving
    @property
    def phases_total(self) -> int:
        return len(self._phases)

    @property
    def phases_done(self) -> int:
        return self._next_phase

    @property
    def has_inflight_dma(self) -> bool:
        """True while this cursor has issued-but-unfenced transfers —
        the condition under which another session's compute hides them."""
        return bool(getattr(self.engine, "has_inflight", False)
                    or getattr(self.engine, "inflight_bytes", 0)
                    or getattr(self.engine, "opt_inflight_bytes", 0))

    def advance(self) -> bool:
        """Execute one phase; True while more phases remain."""
        if self._finished:
            return False
        t0 = self._clock()
        for op_index, op in self._phases[self._next_phase]:
            self._exec_op(op, op_index)
        self._next_phase += 1
        self.last_advance_s = self._clock() - t0
        self.stats.wall_time_s += self.last_advance_s
        if self._next_phase >= len(self._phases):
            self._finish()
            return False
        return True

    def result(self):
        """``(loss, grads, stats)`` — only after the cursor is exhausted."""
        if not self._finished or self._result is None:
            raise RuntimeError(
                "ScheduleCursor.result() before the cursor finished"
                + (" (aborted)" if self.aborted else ""))
        return self._result

    def abort(self) -> None:
        """Abandon the step at a phase boundary (mid-step kill): fence this
        session's in-flight transfers and release every activation
        reference.  The cursor yields no result."""
        if self._finished:
            return
        self.engine.drain(self.stats)
        self.store.device.clear()
        self.store.host.clear()
        self.store.alive.clear()
        self._finished = True
        self.aborted = True

    # ----------------------------------------------------------- op body
    def _exec_op(self, op, op_index: int) -> None:
        from repro.core.plan import (Compute, Free, OptPrefetch, OptSwapOut,
                                     Prefetch, SwapOut)

        stats, store = self.stats, self.store
        if isinstance(op, OptPrefetch):
            # optimizer working state lands in its own device region; the
            # numerical dance (dequantize, AdamW update, EF requantize)
            # runs in repro.core.optim_offload — the replay accounts
            # residency/bus traffic and, on real-stream engines, issues
            # the H2D of the compressed host copy *now* and fences it at
            # the first Compute of its read EO, so the opt DMA hides
            # behind the compute dispatched in between
            self._opt_resident += op.nbytes
            stats.opt_device_high_water = max(
                stats.opt_device_high_water, self._opt_resident)
            stats.opt_prefetches += 1
            stats.opt_dma_bytes += op.host_nbytes
            self.engine.opt_swap_in(op.tensor, op.nbytes, op.host_nbytes,
                                    stats)
            self._opt_fence_at.setdefault(op.read_eo, []).append(op.tensor)
            self._replayed.append(op)
        elif isinstance(op, OptSwapOut):
            self._opt_resident -= op.nbytes
            stats.opt_swap_outs += 1
            stats.opt_dma_bytes += op.nbytes
            stats.opt_compressed_bytes += op.host_nbytes
            self._replayed.append(op)
        elif isinstance(op, Prefetch):
            if op.tensor in store.alive:
                return  # late swap-in already brought it back
            store.swap_in(op.tensor, stats)
            self._inflight += op.nbytes
            self._done_at[op.read_eo] = \
                self._done_at.get(op.read_eo, 0) + op.nbytes
            stats.peak_inflight_prefetch = max(
                stats.peak_inflight_prefetch, self._inflight)
            self._replayed.append(op)
        elif isinstance(op, Compute):
            # prefetches issued at earlier phases complete by their read
            # EO: retire their double-buffer slots at the phase boundary,
            # and fence optimizer slots whose read EO has arrived
            if op.eo > self._retired_eo:
                for eo in list(self._done_at):
                    if eo <= op.eo:
                        self._inflight -= self._done_at.pop(eo)
                for eo in list(self._opt_fence_at):
                    if eo <= op.eo:
                        for owner in self._opt_fence_at.pop(eo):
                            self.engine.opt_fence(owner, stats)
                self._retired_eo = op.eo
            self.env.step(op)
            self._replayed.append(op)
        elif isinstance(op, SwapOut):
            if op.tensor in store.alive:
                store.swap_out(op.tensor, stats)
                self._replayed.append(op)
        elif isinstance(op, Free):
            store.free_owner(op.tensor)
            self._replayed.append(op)
        if self.sanitizer is not None:
            self.sanitizer.step(op)
            self.sanitizer.cross_check(store.alive, op_index)
            stats.sanitizer_checks += 1

    # ---------------------------------------------------------- finalise
    def _finish(self) -> None:
        t0 = self._clock()
        stats, plan = self.stats, self.plan
        self.engine.drain(stats)
        stats.wall_time_s += self._clock() - t0
        stats.hbm_high_water = self.hbm.high_water
        stats.host_high_water = self.store.host_pool.high_water
        stats.replayed_ops = tuple(self._replayed)
        stats.dispatch_calls = len(self._replayed)
        self.backend._finalize_stats(stats, self.engine)
        self.backend._last_stats = stats
        self.backend._planned_inflight = self.schedule.peak_inflight_prefetch
        if plan is not None:
            stats.planned_peak = plan.activation_residency_peak()
            stats.planned_host_pool = plan.host_pool_bytes
            if stats.hbm_high_water > stats.planned_peak:
                raise AssertionError(
                    f"swap executor exceeded the planned residency peak: "
                    f"{stats.hbm_high_water} > {stats.planned_peak} bytes")
            if stats.host_high_water > stats.planned_host_pool:
                raise AssertionError(
                    f"swap executor exceeded the packed host pool: "
                    f"{stats.host_high_water} > {stats.planned_host_pool} "
                    f"bytes")
        _check_opt_high_water(plan, stats)
        self._finished = True
        self._result = (self.env.loss_val, self.env.grads, stats)


class _ReplayBackend:
    """Shared interpreter: walk the compiled op list, account residency.

    Subclasses choose the :class:`TransferEngine` wired into the store;
    everything else — layer math dispatch, alias-group accounting,
    high-water assertions, replay-equality bookkeeping — is common, so the
    two backends cannot drift apart semantically.
    """

    name = "replay"

    def __init__(self, *, sanitize: Optional[bool] = None):
        if sanitize is None:
            sanitize = os.environ.get("REPRO_EXEC_SANITIZE",
                                      "") not in ("", "0")
        self.sanitize = bool(sanitize)
        self._last_stats: Optional[SwapExecStats] = None
        self._planned_inflight: Optional[int] = None

    def make_engine(self) -> TransferEngine:
        raise NotImplementedError

    # ---------------------------------------------------------------- start
    def start(self, graph: LayerGraph, params, x, label, *,
              schedule: OffloadSchedule,
              ordered: Optional[OrderedTensors] = None,
              plan=None, lowered=None, mask=None,
              engine: Optional[TransferEngine] = None,
              tag: str = "") -> ScheduleCursor:
        """Admit a schedule and return a resumable :class:`ScheduleCursor`.

        This is the preemptible entry point the phase-interleaved serve
        scheduler drives: the same verified admission as :meth:`run`, but
        the caller chooses when each phase executes (and may supply a
        shared ``engine`` — e.g. a session-scoped view over one
        :class:`DeviceStreamEngine` — so several cursors' DMAs interleave
        on one device stream).
        """
        from repro.core.plan import lower_schedule
        from repro.core.verify import (StaticResidencyModel, is_verified,
                                       mark_verified, verify_schedule)
        if ordered is None:
            ordered = compute_execution_order(graph, int(x.shape[0]))
        if lowered is None:
            lowered = lower_schedule(ordered, schedule, plan)
        # admission check: a plan-backed schedule must have passed static
        # verification before any transfer op reaches a device stream —
        # verify on the spot if compile-time verification was skipped
        if plan is not None and not is_verified(lowered):
            verify_schedule(ordered, schedule, plan,
                            lowered).raise_if_errors()
            mark_verified(lowered)
        sanitizer = StaticResidencyModel(ordered) if self.sanitize else None
        if engine is None:
            engine = self.make_engine()
        return ScheduleCursor(self, graph, params, x, label,
                              schedule=schedule, ordered=ordered, plan=plan,
                              lowered=lowered, mask=mask, engine=engine,
                              sanitizer=sanitizer, tag=tag)

    # ------------------------------------------------------------------ run
    def run(self, graph: LayerGraph, params, x, label, *,
            schedule: OffloadSchedule,
            ordered: Optional[OrderedTensors] = None,
            plan=None, lowered=None, mask=None,
            engine: Optional[TransferEngine] = None):
        cursor = self.start(graph, params, x, label, schedule=schedule,
                            ordered=ordered, plan=plan, lowered=lowered,
                            mask=mask, engine=engine)
        while cursor.advance():
            pass
        return cursor.result()

    def _finalize_stats(self, stats: SwapExecStats,
                        engine: TransferEngine) -> None:
        pass

    # --------------------------------------------------------------- report
    def report(self) -> Dict[str, Any]:
        """Summary of the last :meth:`run` (transfer counts + high waters)."""
        if self._last_stats is None:
            raise RuntimeError(
                f"{type(self).__name__}.report() needs a completed run()")
        s = self._last_stats
        return {
            "backend": s.backend,
            "swap_outs": s.swap_outs,
            "prefetches": s.prefetches,
            "dma_bytes": s.dma_bytes,
            "late_swap_ins": s.late_swap_ins,
            "hbm_high_water": s.hbm_high_water,
            "host_high_water": s.host_high_water,
            "peak_inflight_prefetch": s.peak_inflight_prefetch,
            "planned_peak_inflight_prefetch": self._planned_inflight,
            "sanitizer_checks": s.sanitizer_checks,
            "dispatch_calls": s.dispatch_calls,
            "replayed_op_count": len(s.replayed_ops),
            "wall_time_s": s.wall_time_s,
            "opt_swap_outs": s.opt_swap_outs,
            "opt_prefetches": s.opt_prefetches,
            "opt_dma_bytes": s.opt_dma_bytes,
            "opt_compressed_bytes": s.opt_compressed_bytes,
            "opt_device_high_water": s.opt_device_high_water,
        }


class SimulatedBackend(_ReplayBackend):
    """Today's synchronous replay — the default executor backend.

    Every transfer op blocks until its bytes land, so scheduling effects
    are fully deterministic and the measured stats are bit-for-bit the
    values the planner-validation tests have always asserted."""

    name = "sim"

    def make_engine(self) -> TransferEngine:
        return SyncHostEngine()


class AsyncDeviceBackend(_ReplayBackend):
    """Issue the compiled transfer ops on real device streams.

    ``SwapOut`` lowers to ``jax.device_put(arr, <pinned_host>)``
    dispatched (not awaited) during its scheduled phase; ``Prefetch``
    lowers to the host->device put issued ``prefetch_margin`` phases ahead
    of the read and fenced only when the consuming compute actually touches
    the tensor.  The device's ``pinned_host`` memory space is required (a
    device without one raises); on a TPU the copies are DMA against pinned
    memory.  ``report()`` carries the achieved overlap."""

    name = "async"

    def __init__(self, device=None, *, sanitize: Optional[bool] = None):
        super().__init__(sanitize=sanitize)
        self.device = device
        self._last_engine: Optional[DeviceStreamEngine] = None

    def make_engine(self) -> TransferEngine:
        self._last_engine = DeviceStreamEngine(self.device)
        return self._last_engine

    def _finalize_stats(self, stats: SwapExecStats,
                        engine: TransferEngine) -> None:
        # fences/stalled_fences accumulate per call on the stats record
        # (so a session-scoped view over a shared engine still yields
        # per-session numbers); the engine contributes its in-flight
        # high-water marks — a SessionScopedEngine reports per-session
        # marks, a raw DeviceStreamEngine the whole stream's
        stats.inflight_high_water = getattr(engine, "inflight_high_water", 0)
        stats.opt_inflight_high_water = getattr(
            engine, "opt_inflight_high_water", 0)
        stats.achieved_overlap = (
            (stats.fences - stats.stalled_fences) / stats.fences
            if stats.fences else None)

    def report(self) -> Dict[str, Any]:
        out = super().report()
        s = self._last_stats
        planned = self._planned_inflight
        out.update({
            "host_memory_kind": (self._last_engine.host_memory_kind
                                 if self._last_engine else None),
            "inflight_high_water": s.inflight_high_water,
            "fences": s.fences,
            "stalled_fences": s.stalled_fences,
            "achieved_overlap": s.achieved_overlap,
            # measured double-buffer occupancy vs what the plan budgeted —
            # <= 1.0 means the stream never held more than planned
            "inflight_vs_planned": (s.inflight_high_water / planned
                                    if planned else None),
            # measured bus-time split: seconds the activation DMAs ran
            # hidden under dispatched compute vs seconds consumer fences
            # actually blocked — and the same split for the optimizer
            # lane, whose OptPrefetch H2D now streams on the real engine
            "hidden_dma_s": s.hidden_dma_s,
            "exposed_dma_s": s.exposed_dma_s,
            "opt_hidden_dma_s": s.opt_hidden_dma_s,
            "opt_exposed_dma_s": s.opt_exposed_dma_s,
            "opt_fences": s.opt_fences,
            "opt_stalled_fences": s.opt_stalled_fences,
            "opt_inflight_high_water": s.opt_inflight_high_water,
            "cross_hidden_dma_s": s.cross_hidden_dma_s,
        })
        return out


# ---------------------------------------------------------------------------
# jit_blocks: dispatch proven-fusable Compute runs as single XLA calls
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ArraySlot:
    """Skeleton placeholder for one array leaf of a flattened state."""

    index: int


def _flatten_state(obj, leaves: List[Any]):
    """Split a nested interpreter state into (skeleton, array leaves).

    ``jax.tree_util`` cannot flatten this state — saved ctx tuples mix
    arrays with strings, shape tuples and ``("@act", name)`` references —
    so these walkers treat arrays (and tracers) as leaves and everything
    else as static skeleton.  The skeleton contains no arrays, so two
    skeletons compare with ``==`` safely (the jit-cache validity check)."""
    if isinstance(obj, (jax.Array, np.ndarray)) or hasattr(obj, "aval"):
        leaves.append(obj)
        return _ArraySlot(len(leaves) - 1)
    if isinstance(obj, dict):
        return {k: _flatten_state(v, leaves) for k, v in obj.items()}
    if isinstance(obj, tuple):
        return tuple(_flatten_state(v, leaves) for v in obj)
    if isinstance(obj, list):
        return [_flatten_state(v, leaves) for v in obj]
    return obj


def _unflatten_state(skel, leaves: List[Any]):
    if isinstance(skel, _ArraySlot):
        return leaves[skel.index]
    if isinstance(skel, dict):
        return {k: _unflatten_state(v, leaves) for k, v in skel.items()}
    if isinstance(skel, tuple):
        return tuple(_unflatten_state(v, leaves) for v in skel)
    if isinstance(skel, list):
        return [_unflatten_state(v, leaves) for v in skel]
    return skel


# Jitted block functions, keyed weakly by the lowered schedule (same
# lifetime discipline as the verifier's _VERIFIED registry): entry ->
# {(block index, mask is None): (jitted fn, input skeleton, out cell)}.
_FUSED_FN_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _make_block_fn(graph: LayerGraph, ops: Tuple[Any, ...],
                   compute_indices: Tuple[int, ...], in_skel,
                   out_cell: List[Any]):
    """Build the pure function tracing one fused block.

    Takes the flattened input state (device dict + backward state +
    params/label/mask), replays the block's ``Compute`` ops through
    :class:`_ComputeEnv` against a plain dict, and returns the flattened
    *delta*: newly produced device/ctx entries plus the whole (small)
    backward-state dicts.  The output skeleton is captured into
    ``out_cell`` at trace time."""

    def fn(leaves):
        state = _unflatten_state(in_skel, leaves)
        device = dict(state["device"])
        env = _ComputeEnv(graph, state["params"], state["label"],
                          state["mask"],
                          get=device.__getitem__, put=device.__setitem__)
        env.ctxs = dict(state["ctxs"])
        env.derivs = dict(state["derivs"])
        env.pending_dxs = dict(state["pending_dxs"])
        env.pending_cd = dict(state["pending_cd"])
        env.grads = dict(state["grads"])
        env.loss_val = state["loss"]
        before_dev, before_ctx = set(state["device"]), set(state["ctxs"])
        for ci in compute_indices:
            env.step(ops[ci])
        out = {
            "device": {k: v for k, v in device.items()
                       if k not in before_dev},
            "ctxs": {k: v for k, v in env.ctxs.items()
                     if k not in before_ctx},
            "derivs": env.derivs,
            "pending_dxs": env.pending_dxs,
            "pending_cd": env.pending_cd,
            "grads": env.grads,
            "loss": env.loss_val,
        }
        out_leaves: List[Any] = []
        out_cell.append(_flatten_state(out, out_leaves))
        return out_leaves

    return fn


class JitBlocksBackend(AsyncDeviceBackend):
    """Dispatch each proven-fusable Compute run as one jitted XLA call.

    On large graphs the per-op Python dispatch loop is the async
    backend's bottleneck and drowns the achieved-overlap measurement in
    interpreter noise (ROADMAP "Jit-fused compute dispatch").  This
    backend asks the static dependence prover
    (:mod:`repro.core.verify.deps`) for a :class:`FusionPlan` — maximal
    ``Compute`` runs crossing no transfer fence, no ``Free``-reuse hazard
    and no in-place-prefetch window — and replays each block as a single
    ``jax.jit`` call, giving every DMA a long XLA dispatch window to hide
    behind.

    Admission is strictly *prove-then-run*: beyond the base verifier
    gate, the fusion plan must pass :func:`verify_fusion` and the fused
    replay stream must pass :func:`schedules_equivalent` against the
    verified original — the backend never executes an op order the
    dependence DAG did not license.  Transfers and the ops between blocks
    stay eager (issue points unchanged), consumer fences run at block
    entry for every tensor the block reads, and the sanitizer
    cross-checks residency at block boundaries (op granularity inside a
    traced block does not exist at run time).  Jitted block functions are
    cached per lowered schedule (weak, like the verifier registry), so
    iteration 2+ pays one Python dispatch per block."""

    name = "jit_blocks"

    def run(self, graph: LayerGraph, params, x, label, *,
            schedule: OffloadSchedule,
            ordered: Optional[OrderedTensors] = None,
            plan=None, lowered=None, mask=None):
        import time as _time

        from repro.core.plan import (Compute, Free, OptPrefetch, OptSwapOut,
                                     Prefetch, SwapOut, lower_schedule)
        from repro.core.verify import (ScheduleVerificationError,
                                       StaticResidencyModel, is_verified,
                                       mark_verified, plan_fusion,
                                       replay_stream, schedules_equivalent,
                                       verify_fusion, verify_schedule)
        if ordered is None:
            ordered = compute_execution_order(graph, int(x.shape[0]))
        if lowered is None:
            lowered = lower_schedule(ordered, schedule, plan)
        if plan is not None and not is_verified(lowered):
            verify_schedule(ordered, schedule, plan,
                            lowered).raise_if_errors()
            mark_verified(lowered)
        # fusion admission: plan the blocks, re-prove them legal, and
        # prove the fused replay stream preserves every dependence edge
        # of the verified original — only then may a block dispatch
        fusion = plan_fusion(lowered, ordered, plan)
        fdiags = tuple(d for d in verify_fusion(fusion, lowered, ordered,
                                                plan)
                       if d.severity == "error")
        if fdiags:
            raise ScheduleVerificationError(fdiags)
        fused_stream = replay_stream(lowered, fusion)
        schedules_equivalent(lowered, fused_stream, ordered=ordered,
                             plan=plan).raise_if_errors()
        self._last_fusion = fusion

        sanitizer = StaticResidencyModel(ordered) if self.sanitize else None
        t_run0 = _time.perf_counter()
        stats = SwapExecStats(backend=self.name)
        stats.inplace_prefetches = sum(
            1 for d in schedule.decisions if d.inplace)
        engine = self.make_engine()
        hbm = HbmTracker()
        store = ActivationStore(ordered, hbm, engine=engine)
        params, x, label, mask = commit((params, x, label, mask),
                                        engine.device)
        store.device["__input__"] = x
        env = _ComputeEnv(graph, params, label, mask,
                          get=lambda n: store.get(n, stats),
                          put=store.put)
        ops = lowered.ops
        block_at: Dict[int, Any] = {min(b.op_indices): b
                                    for b in fusion.blocks}
        covered = {i for b in fusion.blocks for i in b.op_indices}
        cache = _FUSED_FN_CACHE.setdefault(lowered, {})

        replayed: List[Any] = []
        inflight = 0
        opt_resident = 0
        done_at: Dict[int, int] = {}
        retired_eo = -1

        def sanitize_step(op, op_index: int, *, cross: bool) -> None:
            if sanitizer is None:
                return
            sanitizer.step(op)
            if cross:
                sanitizer.cross_check(store.alive, op_index)
            stats.sanitizer_checks += 1

        for op_index, op in enumerate(ops):
            block = block_at.get(op_index)
            if block is not None:
                # retire double-buffer slots up to the block's last phase
                last_eo = ops[block.compute_indices[-1]].eo
                if last_eo > retired_eo:
                    for eo in list(done_at):
                        if eo <= last_eo:
                            inflight -= done_at.pop(eo)
                    retired_eo = last_eo
                self._exec_block(block, ops, graph, store, env, stats,
                                 params, label, mask, cache)
                stats.dispatch_calls += 1
                for ci in block.compute_indices:
                    replayed.append(ops[ci])
                    sanitize_step(ops[ci], ci, cross=False)
                for fi in block.free_indices:
                    store.free_owner(ops[fi].tensor)
                    replayed.append(ops[fi])
                    sanitize_step(ops[fi], fi,
                                  cross=fi == block.free_indices[-1])
                if sanitizer is not None and not block.free_indices:
                    sanitizer.cross_check(store.alive,
                                          block.compute_indices[-1])
                continue
            if op_index in covered:
                continue        # replayed as part of its block
            if isinstance(op, OptPrefetch):
                # optimizer ops never fuse (they are fences to the
                # dependence prover): eager accounting, one dispatch each
                opt_resident += op.nbytes
                stats.opt_device_high_water = max(
                    stats.opt_device_high_water, opt_resident)
                stats.opt_prefetches += 1
                stats.opt_dma_bytes += op.host_nbytes
                replayed.append(op)
                stats.dispatch_calls += 1
            elif isinstance(op, OptSwapOut):
                opt_resident -= op.nbytes
                stats.opt_swap_outs += 1
                stats.opt_dma_bytes += op.nbytes
                stats.opt_compressed_bytes += op.host_nbytes
                replayed.append(op)
                stats.dispatch_calls += 1
            elif isinstance(op, Prefetch):
                if op.tensor in store.alive:
                    continue
                store.swap_in(op.tensor, stats)
                inflight += op.nbytes
                done_at[op.read_eo] = done_at.get(op.read_eo, 0) + op.nbytes
                stats.peak_inflight_prefetch = max(
                    stats.peak_inflight_prefetch, inflight)
                replayed.append(op)
                stats.dispatch_calls += 1
            elif isinstance(op, Compute):
                if op.eo > retired_eo:
                    for eo in list(done_at):
                        if eo <= op.eo:
                            inflight -= done_at.pop(eo)
                    retired_eo = op.eo
                env.step(op)
                replayed.append(op)
                stats.dispatch_calls += 1
            elif isinstance(op, SwapOut):
                if op.tensor not in store.alive:
                    continue
                store.swap_out(op.tensor, stats)
                replayed.append(op)
                stats.dispatch_calls += 1
            elif isinstance(op, Free):
                store.free_owner(op.tensor)
                replayed.append(op)
                stats.dispatch_calls += 1
            sanitize_step(op, op_index, cross=True)

        engine.drain(stats)
        stats.wall_time_s = _time.perf_counter() - t_run0
        stats.hbm_high_water = hbm.high_water
        stats.host_high_water = store.host_pool.high_water
        stats.replayed_ops = tuple(replayed)
        self._finalize_stats(stats, engine)
        self._last_stats = stats
        self._planned_inflight = schedule.peak_inflight_prefetch
        if plan is not None:
            stats.planned_peak = plan.activation_residency_peak()
            stats.planned_host_pool = plan.host_pool_bytes
            if stats.hbm_high_water > stats.planned_peak:
                raise AssertionError(
                    f"swap executor exceeded the planned residency peak: "
                    f"{stats.hbm_high_water} > {stats.planned_peak} bytes")
            if stats.host_high_water > stats.planned_host_pool:
                raise AssertionError(
                    f"swap executor exceeded the packed host pool: "
                    f"{stats.host_high_water} > {stats.planned_host_pool} "
                    f"bytes")
        _check_opt_high_water(plan, stats)
        return env.loss_val, env.grads, stats

    def _exec_block(self, block, ops, graph, store, env, stats,
                    params, label, mask, cache) -> None:
        """Fence the block's inputs, then dispatch it as one jitted call
        and fold the produced state back into the live store."""
        # consumer fences: every tensor the block reads must have its
        # in-flight DMA fenced before the traced computation touches the
        # bytes.  Device-resident names only: read_names over-approximates
        # (a CG/CD lists all layer inputs even when its planned read is a
        # later phase), and fencing a host-resident name would late-swap
        # it in ahead of its scheduled Prefetch.  The verifier's
        # use_before_resident pass proves every tensor a block compute
        # actually reads was prefetched before the block (blocks contain
        # no transfers), i.e. is already in store.device here.
        for ci in block.compute_indices:
            for name in env.read_names(ops[ci]):
                if name in store.device:
                    store.get(name, stats)
        state = {
            "device": dict(store.device),
            "ctxs": env.ctxs,
            "derivs": env.derivs,
            "pending_dxs": env.pending_dxs,
            "pending_cd": env.pending_cd,
            "grads": env.grads,
            "loss": env.loss_val,
            "params": params,
            "label": label,
            "mask": mask,
        }
        leaves: List[Any] = []
        in_skel = _flatten_state(state, leaves)
        cache_key = (block.index, mask is None)
        entry = cache.get(cache_key)
        if entry is None or entry[1] != in_skel:
            out_cell: List[Any] = []
            fn = jax.jit(_make_block_fn(graph, ops,
                                        block.compute_indices, in_skel,
                                        out_cell))
            entry = (fn, in_skel, out_cell)
            cache[cache_key] = entry
        fn, _, out_cell = entry
        out_leaves = fn(leaves)
        out = _unflatten_state(out_cell[-1], list(out_leaves))
        for k, v in out["device"].items():
            store.put(k, v)
        env.ctxs.update(out["ctxs"])
        env.derivs = out["derivs"]
        env.pending_dxs = out["pending_dxs"]
        env.pending_cd = out["pending_cd"]
        env.grads = out["grads"]
        env.loss_val = out["loss"]

    def report(self) -> Dict[str, Any]:
        out = super().report()
        fusion = getattr(self, "_last_fusion", None)
        if fusion is not None:
            out["fusion"] = fusion.summary()
        return out


# Registry: MemoryPlanConfig.executor values -> backend factories.
BACKENDS = {
    SimulatedBackend.name: SimulatedBackend,
    AsyncDeviceBackend.name: AsyncDeviceBackend,
    JitBlocksBackend.name: JitBlocksBackend,
}


def get_backend(executor: Union[str, ExecutorBackend, None]
                ) -> ExecutorBackend:
    """Resolve an executor selection to a backend instance.

    ``None`` means the default (``"sim"``); a string is looked up in
    :data:`BACKENDS` (unknown names raise with the valid options); an
    :class:`ExecutorBackend` instance passes through untouched, the hook
    for custom backends."""
    if executor is None:
        executor = SimulatedBackend.name
    if isinstance(executor, str):
        cls = BACKENDS.get(executor)
        if cls is None:
            raise ValueError(
                f"unknown executor backend {executor!r}; "
                f"valid: {sorted(BACKENDS)}")
        return cls()
    if isinstance(executor, ExecutorBackend):
        return executor
    raise TypeError(
        f"executor must be a backend name {sorted(BACKENDS)} or an "
        f"ExecutorBackend instance, got {type(executor).__name__}")


def swap_planned_loss_and_grads(
    graph: LayerGraph,
    params: Dict[str, Dict[str, jax.Array]],
    x: jax.Array, label: jax.Array, *,
    schedule: OffloadSchedule,
    ordered: Optional[OrderedTensors] = None,
    plan: Optional["SwapAwarePlan"] = None,  # noqa: F821
    lowered: Optional["ExecutionSchedule"] = None,  # noqa: F821
    executor: Union[str, ExecutorBackend, None] = None,
    mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]], SwapExecStats]:
    """One layer-basis iteration replaying the compiled op list.

    Identical numerics to :func:`repro.core.exec.layers.planned_loss_and_grads`
    (arrays round-trip through host exactly), but walks the lowered
    :class:`repro.core.plan.ExecutionSchedule` directly: every ``Compute``,
    ``SwapOut``, ``Prefetch`` and ``Free`` was decided at compile time, so
    the executor holds no scheduling policy — it replays ops and accounts
    HBM / host-pool residency high-water marks.  When no ``lowered``
    schedule is supplied (hand-wired callers) it is derived here from
    ``schedule``/``plan``.  With a :class:`SwapAwarePlan`, asserts the
    measured high-water marks never exceed the planned residency peak and
    the packed host pool.  ``executor`` picks the backend ("sim" default,
    "async" for real device streams) — see :func:`get_backend`.
    """
    return get_backend(executor).run(
        graph, params, x, label, schedule=schedule, ordered=ordered,
        plan=plan, lowered=lowered, mask=mask)
