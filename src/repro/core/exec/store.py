"""Activation store, residency trackers and the transfer-engine seam.

The store owns *what lives where* (device tier, host tier, alias groups,
byte accounting); it holds no scheduling policy and no opinion about *how*
bytes move.  Data movement is delegated to a :class:`TransferEngine`:

* :class:`SyncHostEngine` — synchronous ``numpy`` round trips, the
  simulated-DMA behaviour the plan validation relies on;
* :class:`DeviceStreamEngine` — real ``jax.device_put`` copies between the
  device and its (pinned) host memory space, *dispatched* when the op is
  replayed and *fenced* only when a consumer reads the tensor, so the DMA
  overlaps the compute issued in between (NNTrainer §6's proactive swap on
  actual device streams).  The engine measures the overlap it achieved:
  how many fences found the transfer already complete, and the in-flight
  byte high-water mark to compare against the plan's
  ``peak_inflight_prefetch``.

Backends (:mod:`repro.core.exec.backends`) pick the engine; everything
else — alias groups, owner accounting, high-water marks — is shared.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Protocol, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execution_order import OrderedTensors
from repro.core.lifespan import CreateMode


@dataclasses.dataclass
class SwapExecStats:
    """What the swap executor actually did during one iteration."""
    swap_outs: int = 0
    prefetches: int = 0
    inplace_prefetches: int = 0    # re-residencies that needed no copy
    dma_bytes: int = 0             # device<->host bytes moved
    late_swap_ins: int = 0         # schedule misses: access before prefetch
    hbm_high_water: int = 0        # peak resident planned-activation bytes
    host_high_water: int = 0       # peak resident host-pool bytes
    planned_peak: Optional[int] = None   # SwapAwarePlan's residency bound
    planned_host_pool: Optional[int] = None  # packed host arena bound
    peak_inflight_prefetch: int = 0      # double-buffer occupancy peak
    # the ops actually executed, in order — equals the compiled
    # ExecutionSchedule.ops exactly when no schedule miss occurred (the
    # jit_blocks backend replays a proven-equivalent *fused* permutation
    # instead: computes of a block, then its deferred frees)
    replayed_ops: Tuple = ()
    # Python-level dispatches issued while replaying: one per op on the
    # per-op backends, one per fused block (plus one per unfused op) on
    # jit_blocks — the denominator of the dispatch-reduction claim
    dispatch_calls: int = 0
    # ---- backend-specific fields (defaults describe the simulated path) ----
    backend: str = "sim"
    # async engine: peak bytes issued on the device stream but not yet
    # fenced by a consumer — the measured double-buffer occupancy to hold
    # against the plan's ``peak_inflight_prefetch``
    inflight_high_water: int = 0
    fences: int = 0                # consumer-side waits on in-flight copies
    stalled_fences: int = 0        # fences that actually had to block
    # fraction of fences that found the transfer already complete (the DMA
    # fully overlapped compute); None when no real transfers were issued
    achieved_overlap: Optional[float] = None
    # debug sanitizer: per-op cross-checks of runtime residency against
    # the static verifier model (0 when the sanitizer is off)
    sanitizer_checks: int = 0
    # wall-clock seconds the backend spent replaying the op list — the
    # per-step timing the serving layer aggregates into per-session
    # steps/sec (0.0 until a run completes)
    wall_time_s: float = 0.0
    # ---- optimizer-state offload (repro.core.optim_offload) ----
    opt_swap_outs: int = 0         # OptSwapOut ops replayed
    opt_prefetches: int = 0        # OptPrefetch ops replayed
    # optimizer DMA: fp32 working state D2H + compressed host copy H2D
    opt_dma_bytes: int = 0
    opt_compressed_bytes: int = 0  # host-side bytes after quantization
    opt_device_high_water: int = 0 # peak resident optimizer working bytes
    # ---- measured bus-time split (device-stream engines only) ----
    # activation lane: seconds each prefetch spent in flight before its
    # consumer fence (hidden behind dispatched compute) vs seconds the
    # fence actually blocked (exposed on the critical path)
    hidden_dma_s: float = 0.0
    exposed_dma_s: float = 0.0
    # optimizer lane, same split: OptPrefetch H2D issued at its scheduled
    # EO and fenced at the first Compute of its read EO
    opt_hidden_dma_s: float = 0.0
    opt_exposed_dma_s: float = 0.0
    opt_fences: int = 0            # optimizer-lane consumer fences
    opt_stalled_fences: int = 0    # opt fences that actually had to block
    opt_inflight_high_water: int = 0  # peak issued-but-unfenced opt bytes
    # portion of hidden_dma_s that elapsed while *another* session held
    # the compute slot — credited by the phase-interleaved StepScheduler
    # (repro.serve.scheduler); 0.0 for single-session runs
    cross_hidden_dma_s: float = 0.0


class HbmTracker:
    """High-water-mark accounting over the planned activation bytes."""

    def __init__(self):
        self.current = 0
        self.high_water = 0

    def alloc(self, nbytes: int) -> None:
        self.current += nbytes
        self.high_water = max(self.high_water, self.current)

    def free(self, nbytes: int) -> None:
        self.current -= nbytes


class TransferEngine(Protocol):
    """How activation bytes move between the device and host tiers.

    ``swap_out``/``swap_in`` receive the member arrays of one owner group
    and return the handles of the destination tier; ``fence`` blocks until
    a previously issued ``swap_in`` of ``owner`` is complete (no-op for
    synchronous engines and for owners with nothing in flight); ``drain``
    fences everything still outstanding at the end of an iteration.
    """

    name: str

    def swap_out(self, owner: str, members: Dict[str, jax.Array],
                 nbytes: int) -> Dict[str, Any]: ...

    def swap_in(self, owner: str, members: Dict[str, Any],
                nbytes: int) -> Dict[str, jax.Array]: ...

    def fence(self, owner: str, stats: SwapExecStats) -> None: ...

    def drain(self, stats: SwapExecStats) -> None: ...

    # Optimizer-lane streaming (optional: engines without real streams
    # implement both as no-ops).  ``opt_swap_in`` issues the H2D copy of
    # one slot's compressed host bytes at its scheduled EO;
    # ``opt_fence`` blocks at the consuming Compute.
    def opt_swap_in(self, owner: str, nbytes: int, host_nbytes: int,
                    stats: SwapExecStats) -> None: ...

    def opt_fence(self, owner: str, stats: SwapExecStats) -> None: ...


class SyncHostEngine:
    """Synchronous host round trips (simulated DMA, bit-for-bit stable).

    ``np.asarray`` blocks until the device buffer is materialised on host;
    ``jnp.asarray`` blocks the other way.  Nothing is ever in flight, so
    fences are free and the measured overlap is undefined (None).

    ``bus_gbps`` (default None = off) applies the same emulated-bus model
    as :class:`DeviceStreamEngine`, but synchronously: a blocking engine
    occupies the bus for the transfer's full duration *at the transfer*,
    so every byte of bus time is exposed wall-clock.  This is the honest
    baseline cost the async engines exist to hide.  Numerics untouched.
    """

    name = "sync_host"

    def __init__(self, bus_gbps=None, bus_latency_s=0.0):
        import time as _time
        if bus_gbps is not None and bus_gbps <= 0:
            raise ValueError("bus_gbps must be positive (or None = off)")
        if bus_latency_s < 0:
            raise ValueError("bus_latency_s must be non-negative")
        self.bus_gbps = bus_gbps
        self.bus_latency_s = bus_latency_s
        self._sleep = _time.sleep

    def _bus_block(self, nbytes: int) -> None:
        # a blocking engine is queue-depth-1 storage I/O: every access
        # pays the full device latency, then the serial transfer
        if self.bus_gbps is not None and nbytes > 0:
            self._sleep(self.bus_latency_s
                        + nbytes / (self.bus_gbps * 1e9))

    def swap_out(self, owner: str, members: Dict[str, jax.Array],
                 nbytes: int) -> Dict[str, Any]:
        out = {m: np.asarray(a) for m, a in members.items()}
        self._bus_block(nbytes)
        return out

    def swap_in(self, owner: str, members: Dict[str, Any],
                nbytes: int) -> Dict[str, jax.Array]:
        arrays = {m: jnp.asarray(h) for m, h in members.items()}
        self._bus_block(nbytes)
        return arrays

    def fence(self, owner: str, stats: SwapExecStats) -> None:
        pass

    def drain(self, stats: SwapExecStats) -> None:
        pass

    def opt_swap_in(self, owner: str, nbytes: int, host_nbytes: int,
                    stats: SwapExecStats) -> None:
        # nothing is ever in flight, but the blocking bus still carries
        # the compressed optimizer image synchronously when paced
        self._bus_block(host_nbytes)

    def opt_fence(self, owner: str, stats: SwapExecStats) -> None:
        pass


def commit(tree, device=None):
    """``tree`` with every array committed to ``device`` (default: the
    first device); arrays already there are not copied.

    JAX keys its compile cache on whether each argument is committed to a
    device, and a ``device_put`` transfer always returns a committed
    array.  Params that are uncommitted in the first step (fresh from
    init) and committed in the next (produced from transferred arrays)
    would make every op compile again; committing the inputs of a step up
    front keeps later steps on the programs the first one compiled."""
    device = device if device is not None else jax.devices()[0]
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, device), tree)


def _host_memory_kind(device) -> str:
    """The device's pinned host memory space, where swapped activations
    live.  A device without one has no host tier to swap to, so this
    raises instead of handing back a "host" sharding that is really HBM."""
    kinds = {m.kind for m in device.addressable_memories()}
    if "pinned_host" not in kinds:
        raise RuntimeError(
            f"device {device} has no pinned_host memory space "
            f"(memory kinds: {sorted(kinds)}); swapped activations would "
            "never leave device memory")
    return "pinned_host"


class DeviceStreamEngine:
    """Async device-stream transfers via ``jax.device_put``.

    Swap-outs are dispatched as device->host copies into ``pinned_host``
    the moment their op is replayed.  The copies are not donated (JAX
    refuses donation across memory kinds); the store drops its reference
    to the device array, so the runtime frees the buffer once the copy
    has read it.  Prefetches are dispatched host->device at their
    scheduled EO and left *in flight*; the consumer's read fences them.
    JAX's runtime orders a prefetch after its own swap-out automatically
    (data dependency), so no manual event chaining is needed.

    Measured stats:

    * ``inflight_high_water`` — peak bytes issued-but-not-fenced, the
      achieved double-buffer occupancy (compare: the plan's
      ``peak_inflight_prefetch``);
    * ``ready_fences / fences`` — the achieved overlap fraction: a fence
      that finds its transfer complete means the DMA fully hid behind the
      compute dispatched since the issue EO.

    ``bus_gbps`` (default None = off) emulates the paper's narrow
    storage/host bus on hardware that has none (a CPU host, where
    ``device_put`` is a memcpy): every transfer occupies one serialized
    bus for ``nbytes / bus_gbps`` seconds from issue, and a fence that
    arrives before its transfer's completion time sleeps out the
    remainder (landing in ``exposed_dma_s``, exactly like a real stall).
    A fence that arrives *after* completion pays nothing — so compute
    dispatched between issue and fence, whether the session's own or
    another session's under the phase-interleaved scheduler, genuinely
    hides the bus time in wall-clock terms.  The sim/async numerics are
    untouched; only the clock is.
    """

    name = "device_stream"

    def __init__(self, device=None, bus_gbps=None, bus_latency_s=0.0):
        import time as _time
        self._clock = _time.perf_counter
        self._sleep = _time.sleep
        if bus_gbps is not None and bus_gbps <= 0:
            raise ValueError("bus_gbps must be positive (or None = off)")
        if bus_latency_s < 0:
            raise ValueError("bus_latency_s must be non-negative")
        self.bus_gbps = bus_gbps
        self.bus_latency_s = bus_latency_s
        self._bus_free_at = 0.0      # emulated serialized-bus availability
        self.device = device if device is not None else jax.devices()[0]
        kind = _host_memory_kind(self.device)
        Single = jax.sharding.SingleDeviceSharding
        self.device_sharding = Single(self.device)
        self.host_sharding = Single(self.device, memory_kind=kind)
        self.host_memory_kind = kind
        # owner -> (nbytes, arrays, issue timestamp, emulated ready time)
        self._inflight: Dict[str, Tuple[int, List[jax.Array], float,
                                        float]] = {}
        self.inflight_bytes = 0
        self.inflight_high_water = 0
        self.fences = 0
        self.ready_fences = 0
        self.stalled_fences = 0
        self.d2h_issued = 0
        self.h2d_issued = 0
        # optimizer lane: one reusable host-resident byte image per slot
        # (sized like the compressed copy the codec would store) so the
        # H2D prefetch moves real bus bytes at the scheduled EO
        self._opt_host: Dict[str, jax.Array] = {}
        self._opt_inflight: Dict[str, Tuple[int, jax.Array, float,
                                            float]] = {}
        self.opt_inflight_bytes = 0
        self.opt_inflight_high_water = 0

    def _bus_schedule(self, nbytes: int) -> float:
        """Reserve the emulated bus for ``nbytes``; returns the completion
        time (0.0 with pacing off).  The bus is serialized: a transfer
        starts when the previous one finishes, like one DMA queue.

        ``bus_latency_s`` models the storage access latency: a transfer
        issued to an *idle* bus pays it in full, but one queued behind
        an earlier transfer overlaps its access setup with that
        transfer's data movement — the amortization a deep DMA/NCQ queue
        buys and a blocking (queue-depth-1) engine never gets."""
        if self.bus_gbps is None:
            return 0.0
        start = max(self._clock() + self.bus_latency_s, self._bus_free_at)
        self._bus_free_at = start + nbytes / (self.bus_gbps * 1e9)
        return self._bus_free_at

    # ------------------------------------------------------------- issue
    def swap_out(self, owner: str, members: Dict[str, jax.Array],
                 nbytes: int) -> Dict[str, Any]:
        out = {}
        for m, a in members.items():
            out[m] = jax.device_put(a, self.host_sharding)
            self.d2h_issued += 1
        # the d2h copy occupies the emulated bus too; its cost surfaces
        # through the completion times of the transfers queued behind it
        self._bus_schedule(nbytes)
        return out

    def swap_in(self, owner: str, members: Dict[str, Any],
                nbytes: int) -> Dict[str, jax.Array]:
        arrays = {}
        for m, h in members.items():
            arrays[m] = jax.device_put(h, self.device_sharding)
            self.h2d_issued += 1
        if arrays:
            self._inflight[owner] = (nbytes, list(arrays.values()),
                                     self._clock(),
                                     self._bus_schedule(nbytes))
            self.inflight_bytes += nbytes
            self.inflight_high_water = max(self.inflight_high_water,
                                           self.inflight_bytes)
        return arrays

    def opt_swap_in(self, owner: str, nbytes: int, host_nbytes: int,
                    stats: SwapExecStats) -> None:
        if owner in self._opt_inflight:      # already streaming this slot
            return
        host = self._opt_host.get(owner)
        if host is None or host.nbytes != host_nbytes:
            host = jax.device_put(
                np.zeros(max(1, host_nbytes), np.uint8), self.host_sharding)
            self._opt_host[owner] = host
        arr = jax.device_put(host, self.device_sharding)
        self.h2d_issued += 1
        self._opt_inflight[owner] = (host_nbytes, arr, self._clock(),
                                     self._bus_schedule(host_nbytes))
        self.opt_inflight_bytes += host_nbytes
        self.opt_inflight_high_water = max(self.opt_inflight_high_water,
                                           self.opt_inflight_bytes)

    # ------------------------------------------------------------- fence
    def fence(self, owner: str, stats: SwapExecStats) -> None:
        entry = self._inflight.pop(owner, None)
        if entry is None:
            return
        nbytes, arrays, issued, ready_at = entry
        t0 = self._clock()
        ready = all(a.is_ready() for a in arrays
                    if hasattr(a, "is_ready")) and t0 >= ready_at
        jax.block_until_ready(arrays)
        if ready_at > 0.0:
            left = ready_at - self._clock()
            if left > 0:
                self._sleep(left)        # emulated bus stall -> exposed
        self.inflight_bytes -= nbytes
        self.fences += 1
        stats.fences += 1
        stats.hidden_dma_s += t0 - issued
        stats.exposed_dma_s += self._clock() - t0
        if ready:
            self.ready_fences += 1
        else:
            self.stalled_fences += 1
            stats.stalled_fences += 1

    def opt_fence(self, owner: str, stats: SwapExecStats) -> None:
        entry = self._opt_inflight.pop(owner, None)
        if entry is None:
            return
        host_nbytes, arr, issued, ready_at = entry
        t0 = self._clock()
        ready = (arr.is_ready() if hasattr(arr, "is_ready") else True) \
            and t0 >= ready_at
        jax.block_until_ready(arr)
        if ready_at > 0.0:
            left = ready_at - self._clock()
            if left > 0:
                self._sleep(left)
        self.opt_inflight_bytes -= host_nbytes
        stats.opt_fences += 1
        stats.opt_hidden_dma_s += t0 - issued
        stats.opt_exposed_dma_s += self._clock() - t0
        if not ready:
            stats.opt_stalled_fences += 1

    def drain(self, stats: SwapExecStats) -> None:
        for owner in list(self._inflight):
            self.fence(owner, stats)
        for owner in list(self._opt_inflight):
            self.opt_fence(owner, stats)


class SessionScopedEngine:
    """Per-session view over one shared :class:`DeviceStreamEngine`.

    The phase-interleaved scheduler (:mod:`repro.serve.scheduler`) runs N
    sessions' cursors through a *single* device-stream engine so one
    tenant's DMA can hide under another's compute.  Every session replays
    the same compiled plan, so owner names collide across sessions; this
    wrapper namespaces them with the session scope and tracks which
    transfers belong to this session, so ``drain`` (end of step, or an
    abort after a mid-step kill) fences only this session's in-flight
    copies and never another tenant's.

    Per-session ``inflight_bytes`` / high-water marks are kept here — the
    shared engine's counters aggregate the whole device, which is the
    wrong denominator for a per-session stats record.
    """

    name = "session_scoped"

    def __init__(self, inner: DeviceStreamEngine, scope: str):
        self.inner = inner
        self.scope = scope
        self.device = inner.device
        self.host_memory_kind = inner.host_memory_kind
        self._sizes: Dict[str, int] = {}       # outstanding owner -> bytes
        self._opt_sizes: Dict[str, int] = {}
        self.inflight_bytes = 0
        self.inflight_high_water = 0
        self.opt_inflight_bytes = 0
        self.opt_inflight_high_water = 0

    def _k(self, owner: str) -> str:
        return f"{self.scope}\x1f{owner}"

    def swap_out(self, owner: str, members: Dict[str, jax.Array],
                 nbytes: int) -> Dict[str, Any]:
        return self.inner.swap_out(self._k(owner), members, nbytes)

    def swap_in(self, owner: str, members: Dict[str, Any],
                nbytes: int) -> Dict[str, jax.Array]:
        arrays = self.inner.swap_in(self._k(owner), members, nbytes)
        if arrays:
            self._sizes[owner] = nbytes
            self.inflight_bytes += nbytes
            self.inflight_high_water = max(self.inflight_high_water,
                                           self.inflight_bytes)
        return arrays

    def fence(self, owner: str, stats: SwapExecStats) -> None:
        self.inner.fence(self._k(owner), stats)
        nbytes = self._sizes.pop(owner, None)
        if nbytes is not None:
            self.inflight_bytes -= nbytes

    def opt_swap_in(self, owner: str, nbytes: int, host_nbytes: int,
                    stats: SwapExecStats) -> None:
        if owner in self._opt_sizes:
            return
        self.inner.opt_swap_in(self._k(owner), nbytes, host_nbytes, stats)
        self._opt_sizes[owner] = host_nbytes
        self.opt_inflight_bytes += host_nbytes
        self.opt_inflight_high_water = max(self.opt_inflight_high_water,
                                           self.opt_inflight_bytes)

    def opt_fence(self, owner: str, stats: SwapExecStats) -> None:
        self.inner.opt_fence(self._k(owner), stats)
        host_nbytes = self._opt_sizes.pop(owner, None)
        if host_nbytes is not None:
            self.opt_inflight_bytes -= host_nbytes

    def drain(self, stats: SwapExecStats) -> None:
        """Fence everything *this session* still has in flight."""
        for owner in list(self._sizes):
            self.fence(owner, stats)
        for owner in list(self._opt_sizes):
            self.opt_fence(owner, stats)

    @property
    def has_inflight(self) -> bool:
        return bool(self._sizes or self._opt_sizes)

    @property
    def next_ready_at(self) -> float:
        """Emulated-bus completion time of this session's *oldest*
        in-flight transfer (0.0 when nothing is pacing): the scheduler's
        stall-risk signal.  Prefetches are issued and consumed in EO
        order, so the next fence this session hits is approximately its
        oldest outstanding transfer — if that one is complete, the next
        phase advance cannot stall, however deep the issue-ahead is."""
        oldest = float("inf")
        for owner in self._sizes:
            entry = self.inner._inflight.get(self._k(owner))
            if entry is not None:
                oldest = min(oldest, entry[3])
        for owner in self._opt_sizes:
            entry = self.inner._opt_inflight.get(self._k(owner))
            if entry is not None:
                oldest = min(oldest, entry[3])
        return 0.0 if oldest == float("inf") else oldest


class ActivationStore:
    """Layer-output store with device/host tiers and post-merge alias groups.

    Keys are layer names; bytes are accounted per *owner* tensor (the
    post-merge ``X:`` CREATE owner), so an in-place activation output that
    aliases its producer's storage is neither double-counted nor separately
    swapped — swapping an owner moves every alias with it, exactly like one
    arena region moving to host.  The store holds no scheduling logic: the
    executor drives it by replaying the compiled
    :class:`repro.core.plan.ExecutionSchedule` op by op, and the wired
    :class:`TransferEngine` decides whether the bytes move synchronously
    or on a real device stream.
    """

    def __init__(self, ordered: OrderedTensors, hbm: HbmTracker,
                 host_pool: Optional[HbmTracker] = None,
                 engine: Optional[TransferEngine] = None):
        self.ordered = ordered
        self.hbm = hbm
        self.host_pool = host_pool or HbmTracker()
        self.engine = engine or SyncHostEngine()
        self.device: Dict[str, jax.Array] = {}
        self.host: Dict[str, Any] = {}
        self.members: Dict[str, Set[str]] = {}     # owner -> layer names
        self.alive: Set[str] = set()               # owners holding HBM bytes
        self._owner_cache: Dict[str, Optional[str]] = {}

    def owner_of(self, lname: str) -> Optional[str]:
        """The planned X: owner accounting this output's bytes, if any."""
        if lname in self._owner_cache:
            return self._owner_cache[lname]
        owner = self.ordered.owner(f"X:{lname}")
        spec = self.ordered.tensors.get(owner)
        tracked = (spec is not None and spec.create_mode == CreateMode.CREATE
                   and spec.merged_into is None)
        self._owner_cache[lname] = owner if tracked else None
        return self._owner_cache[lname]

    def put(self, lname: str, y: jax.Array) -> None:
        self.device[lname] = y
        owner = self.owner_of(lname)
        if owner is None:
            return
        self.members.setdefault(owner, set()).add(lname)
        if owner not in self.alive:
            self.alive.add(owner)
            self.hbm.alloc(self.ordered.tensors[owner].nbytes)

    def get(self, lname: str, stats: SwapExecStats) -> jax.Array:
        if lname in self.device:
            owner = self.owner_of(lname)
            if owner is not None:
                # consumer read: fence any prefetch still in flight for
                # this alias group (no-op on the synchronous engine)
                self.engine.fence(owner, stats)
            return self.device[lname]
        owner = self.owner_of(lname)
        if owner is not None and lname in self.host:
            # The schedule was wrong (or margins too tight): blocking swap-in.
            stats.late_swap_ins += 1
            self.swap_in(owner, stats)
            self.engine.fence(owner, stats)
            return self.device[lname]
        raise KeyError(f"activation {lname!r} neither on device nor host")

    def swap_out(self, owner: str, stats: SwapExecStats) -> None:
        nbytes = self.ordered.tensors[owner].nbytes
        moved = {}
        for m in self.members.get(owner, ()):
            if m in self.device:
                moved[m] = self.device.pop(m)
        self.host.update(self.engine.swap_out(owner, moved, nbytes))
        self.alive.discard(owner)
        self.hbm.free(nbytes)
        self.host_pool.alloc(nbytes)
        stats.swap_outs += 1
        stats.dma_bytes += nbytes

    def swap_in(self, owner: str, stats: SwapExecStats) -> None:
        nbytes = self.ordered.tensors[owner].nbytes
        moved = {}
        for m in self.members.get(owner, ()):
            if m in self.host:
                moved[m] = self.host.pop(m)
        self.device.update(self.engine.swap_in(owner, moved, nbytes))
        self.alive.add(owner)
        self.hbm.alloc(nbytes)
        self.host_pool.free(nbytes)
        stats.prefetches += 1
        stats.dma_bytes += nbytes

    def free_owner(self, owner: str) -> None:
        on_host = False
        for m in self.members.get(owner, ()):
            self.device.pop(m, None)
            on_host |= self.host.pop(m, None) is not None
        if on_host:
            self.host_pool.free(self.ordered.tensors[owner].nbytes)
        if owner in self.alive:
            self.alive.discard(owner)
            self.hbm.free(self.ordered.tensors[owner].nbytes)


# Backwards-compatible private aliases (the pre-subsystem names).
_HbmTracker = HbmTracker
_ActivationStore = ActivationStore
