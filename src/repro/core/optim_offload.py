"""Optimizer-state offload: plan AdamW moments as first-class arena slots.

The paper's small-batch personalization regime makes Adam's optimizer
state — two fp32 moments, 2x the parameter bytes — the dominant device
tenant, not activations.  This module extends the memory plan to cover it,
in the mold of 8-bit Adam and the 256KB-tier on-device training line of
work (PAPERS.md): per-layer optimizer slots become planned tensors with
their own execution-order windows, packed device/host arenas and typed
schedule ops.

Per trainable weighted layer ``<l>`` one slot ``O:<l>`` holds the layer's
flattened ``m || v`` fp32 moments (``2 * weight_nbytes``).  The slot is
only needed around the layer's compute-gradient phase (the AdamW update
reads and writes the moments there), so its *device* residency is the
short window ``[CG - prefetch_margin, CG + 1]`` — packed by the regular
interval planners into a working region a fraction of the all-resident
footprint.  Between updates the state lives in a host pool as an int8
block-scaled copy (``optim/compression.py``'s ``_q``/``_deq`` geometry:
one fp32 absmax scale per :data:`CBLOCK` elements, ~3.94x under fp32).

Lowering emits one :class:`repro.core.plan.OptPrefetch` (compressed host
copy -> fp32 working buffer, ready by the CG update) and one
:class:`repro.core.plan.OptSwapOut` (updated state back to the host slot,
re-quantized with error feedback) per slot; both executor backends replay
them and account them in ``SwapExecStats`` (``opt_*`` counters).

The ``m`` half quantizes linearly; the ``v`` half quantizes in log space
(8-bit-Adam style dynamic-range compression).  ``v`` spans many orders of
magnitude inside one 256-element block — linear (or even sqrt-space) int8
collapses small-``v`` elements to zero, turning the Adam denominator into
``eps`` and exploding that update ~1e8x.  In log space the int8 grid error
becomes a bounded *multiplicative* error on ``sqrt(v)`` (~e^(absmax/254)
per element, a few percent), so the denominator can never collapse and
the per-step update error stays a small fraction of ``lr``.

Error feedback keeps updates unbiased over time: the host re-quantization
of the swapped-out state carries its (encoded-space) rounding error into
the *next* quantization (``total = enc(state) + residual; residual =
total - deq(q)``).
The fp32 residual is host-persistent and never crosses the bus — DMA
carries only the compressed payload H2D and the fp32 working state D2H —
so it is reported separately (``ef_residual_host_bytes``) and NOT counted
against the packed host pool, which holds only the DMA-addressable
compressed copies.

:class:`OptimRuntime` / :func:`offloaded_update` realise the host side of
the dance numerically: per-layer AdamW updates (same math and defaults as
``optim/optimizers.py:adamw``) against dequantized prefetched state, with
EF re-quantization on swap-out.  With ``optim_compress=False`` the host
copies are exact fp32 and the update matches the resident reference
bit-for-bit (modulo float noise); with compression it matches within the
established error-feedback tolerance (BENCH row ``optim_offload``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

from repro.core.execution_order import OrderedTensors
from repro.core.graph import WEIGHTED_KINDS, LayerGraph
from repro.core.lifespan import CreateMode, Lifespan, TensorSpec
from repro.core.planner import Plan, _SpecSet, _align, get_planner
from repro.runtime.trace import span

_HOST = "@host"

# compression geometry mirrors optim/compression.py: int8 payload plus one
# fp32 absmax scale per CBLOCK elements
CBLOCK = 256

# pricing defaults mirror the remat_policy cost model's documented
# fallbacks (MemoryPlanConfig.dma_gbps / device_tflops override them)
_DEFAULT_DMA_GBPS = 32.0
_DEFAULT_DEVICE_TFLOPS = 200.0
# quantize (absmax reduction, scale divide, round/clip) + dequantize
# (multiply) per element, both directions of one step
_COMPRESS_FLOPS_PER_ELEM = 6


def compressed_nbytes(n_elems: int) -> int:
    """Host bytes for an int8 block-scaled copy of ``n_elems`` fp32 values."""
    return n_elems + 4 * (-(-n_elems // CBLOCK))


@dataclasses.dataclass(frozen=True)
class OptimSlot:
    """One layer's planned optimizer state (flattened ``m || v``, fp32)."""

    layer: str
    name: str                # "O:<layer>"
    n_elems: int             # 2 * weight elements (m and v)
    nbytes: int              # fp32 working-buffer bytes (n_elems * 4)
    host_nbytes: int         # compressed host-copy bytes (== nbytes uncompressed)
    prefetch_eo: int         # H2D issue phase (CG - prefetch_margin)
    read_eo: int             # the layer's CG phase: the update reads here
    swapout_eo: int          # CG + 1: updated state drains back to host

    @property
    def dma_bytes(self) -> int:
        """Bus traffic per step: fp32 state D2H + compressed copy H2D."""
        return self.nbytes + self.host_nbytes


@dataclasses.dataclass
class OptimPlan:
    """Packed optimizer-state offload plan, attached to the memory plan.

    ``device`` packs the fp32 working buffers over their short per-layer
    CG windows (a separate region — nothing here aliases the activation
    arena); ``host`` packs the persistent compressed copies (keyed
    ``<slot>@host``).  ``resident_bytes`` is the all-resident baseline the
    reduction claim is measured against: every slot live simultaneously,
    same alignment.
    """

    slots: Tuple[OptimSlot, ...]
    device: Plan
    host: Plan
    compress: bool
    resident_bytes: int
    est_dma_s_per_step: float
    est_compress_s_per_step: float

    @property
    def device_peak_bytes(self) -> int:
        return self.device.arena_bytes

    @property
    def host_pool_bytes(self) -> int:
        return self.host.arena_bytes

    @property
    def host_fp32_bytes(self) -> int:
        """What the host pool would cost without compression."""
        return sum(_align(s.nbytes) for s in self.slots)

    @property
    def ef_residual_host_bytes(self) -> int:
        """fp32 error-feedback residual held host-side (never on the bus)."""
        return sum(s.nbytes for s in self.slots) if self.compress else 0

    @property
    def dma_bytes_per_step(self) -> int:
        return sum(s.dma_bytes for s in self.slots)

    @property
    def compress_flops_per_step(self) -> int:
        if not self.compress:
            return 0
        return _COMPRESS_FLOPS_PER_ELEM * sum(s.n_elems for s in self.slots)

    @property
    def reduction_x(self) -> float:
        """Device-resident optimizer bytes, all-resident / planned peak."""
        return self.resident_bytes / max(1, self.device_peak_bytes)

    def slot(self, name: str) -> OptimSlot:
        for s in self.slots:
            if s.name == name:
                return s
        raise KeyError(name)

    def summary(self) -> Dict[str, Any]:
        return {
            "n_slots": len(self.slots),
            "compress": self.compress,
            "resident_bytes": self.resident_bytes,
            "device_peak_bytes": self.device_peak_bytes,
            "reduction_x": self.reduction_x,
            "host_pool_bytes": self.host_pool_bytes,
            "host_fp32_bytes": self.host_fp32_bytes,
            "ef_residual_host_bytes": self.ef_residual_host_bytes,
            "dma_bytes_per_step": self.dma_bytes_per_step,
            "compress_flops_per_step": self.compress_flops_per_step,
            "est_dma_s_per_step": self.est_dma_s_per_step,
            "est_compress_s_per_step": self.est_compress_s_per_step,
        }

    def validate(self) -> None:
        self.device.validate()
        self.host.validate()
        for s in self.slots:
            if not (s.prefetch_eo <= s.read_eo < s.swapout_eo):
                raise AssertionError(
                    f"{s.name}: window prefetch={s.prefetch_eo} "
                    f"read={s.read_eo} swapout={s.swapout_eo} out of order")
            dp = self.device.placements.get(s.name)
            if dp is None:
                raise AssertionError(f"{s.name}: no device placement")
            if dp.min_eo > s.prefetch_eo or dp.max_eo < s.swapout_eo:
                raise AssertionError(
                    f"{s.name}: device placement [{dp.min_eo},{dp.max_eo}] "
                    f"does not cover [{s.prefetch_eo},{s.swapout_eo}]")
            hp = self.host.placements.get(s.name + _HOST)
            if hp is None:
                raise AssertionError(f"{s.name}: no host-pool placement")
            if hp.nbytes < s.host_nbytes:
                raise AssertionError(
                    f"{s.name}: host slot {hp.nbytes}B < compressed copy "
                    f"{s.host_nbytes}B")


def optim_slot_specs(graph: LayerGraph, ordered: OrderedTensors,
                     prefetch_margin: int) -> List[Tuple[Any, OptimSlot]]:
    """(LayerNode, OptimSlot) for every layer owning trainable weights.

    E-shared unrolled copies (``shares_weights_with``) and frozen layers
    carry no optimizer state of their own and get no slot.
    """
    out: List[Tuple[Any, OptimSlot]] = []
    for l in graph.layers:
        if l.kind not in WEIGHTED_KINDS or not l.trainable:
            continue
        if l.shares_weights_with or not l.weight_shapes():
            continue
        eo_cg = ordered.layer_orders[l.name][1]
        nbytes = 2 * l.weight_nbytes()          # m and v, fp32
        n_elems = nbytes // 4
        out.append((l, OptimSlot(
            layer=l.name,
            name=f"O:{l.name}",
            n_elems=n_elems,
            nbytes=nbytes,
            host_nbytes=compressed_nbytes(n_elems),
            prefetch_eo=max(0, eo_cg - prefetch_margin),
            read_eo=eo_cg,
            swapout_eo=eo_cg + 1,
        )))
    return out


def plan_optim_offload(graph: LayerGraph, ordered: OrderedTensors,
                       config) -> Optional[OptimPlan]:
    """Price and pack the optimizer slots; None when nothing is eligible.

    The same joint cost model as the activation offload lane prices the
    decision: offloading costs ``dma_bytes_per_step`` bus time plus the
    de/requantization FLOPs (``config.dma_gbps`` / ``config.device_tflops``,
    remat-policy defaults when unset), and buys back
    ``resident_bytes - device_peak_bytes`` of device memory; keeping
    resident costs nothing but holds the full 2x-params footprint.  The
    honest prices land in :meth:`OptimPlan.summary` — the BENCH row and
    the serving admission controller consume them.
    """
    pairs = optim_slot_specs(graph, ordered, config.prefetch_margin)
    if not pairs:
        return None
    compress = bool(config.optim_compress)
    slots = tuple(
        s if compress else dataclasses.replace(s, host_nbytes=s.nbytes)
        for _, s in pairs)

    # fp32 working buffers over their CG windows -> separate device region
    device_specs = [
        TensorSpec(name=s.name, shape=(s.n_elems,), dtype="float32",
                   lifespan=Lifespan.BACKWARD, create_mode=CreateMode.CREATE,
                   exec_orders=(s.prefetch_eo, s.swapout_eo))
        for s in slots
    ]
    device = get_planner(config.planner).plan(
        _SpecSet(device_specs, ordered.eo_max))

    # persistent compressed copies -> host pool (live the whole iteration:
    # the state must survive from one step's swap-out to the next's prefetch)
    host_specs = [
        TensorSpec(name=s.name + _HOST, shape=(s.host_nbytes,), dtype="int8",
                   lifespan=Lifespan.MAX, create_mode=CreateMode.CREATE,
                   exec_orders=(0, ordered.eo_max))
        for s in slots
    ]
    host = get_planner(config.host_planner).plan(
        _SpecSet(host_specs, ordered.eo_max))

    dma_gbps = config.dma_gbps if config.dma_gbps else _DEFAULT_DMA_GBPS
    tflops = config.device_tflops if config.device_tflops \
        else _DEFAULT_DEVICE_TFLOPS
    dma_bytes = sum(s.dma_bytes for s in slots)
    flops = (_COMPRESS_FLOPS_PER_ELEM * sum(s.n_elems for s in slots)
             if compress else 0)

    plan = OptimPlan(
        slots=slots, device=device, host=host, compress=compress,
        resident_bytes=sum(_align(s.nbytes) for s in slots),
        est_dma_s_per_step=dma_bytes / (dma_gbps * 1e9),
        est_compress_s_per_step=flops / (tflops * 1e12),
    )
    plan.validate()
    return plan


# ---------------------------------------------------------------------------
# Numerical runtime: host-side compressed state + offloaded AdamW update
# ---------------------------------------------------------------------------

class OptimRuntime:
    """Host tier of the offloaded optimizer: compressed copies + EF residual.

    One entry per :class:`OptimSlot`: the int8 block-scaled host copy of the
    layer's flattened ``m || v`` (or the exact fp32 copy when the plan is
    uncompressed) plus, under compression, the fp32 error-feedback residual
    that re-injects each re-quantization's rounding error into the next.
    The residual never crosses the bus; only ``prefetch()``'s compressed
    payload (H2D) and ``swap_out()``'s fp32 state (D2H) are DMA.
    """

    def __init__(self, plan: OptimPlan, graph: LayerGraph,
                 lr: float = 3e-4, b1: float = 0.9, b2: float = 0.95,
                 eps: float = 1e-8, weight_decay: float = 0.1):
        import jax.numpy as jnp

        from repro.core.exec.store import commit
        from repro.optim.compression import _deq, _q

        self.plan = plan
        self.lr, self.b1, self.b2 = lr, b1, b2
        self.eps, self.weight_decay = eps, weight_decay
        self.count = 0
        # per-layer flat layout: (wname, shape, size) in weight_shapes order
        self.layouts: Dict[str, List[Tuple[str, Tuple[int, ...], int]]] = {}
        self.halves: Dict[str, int] = {}
        self.host_state: Dict[str, Any] = {}
        self.residual: Dict[str, Any] = {}
        for s in plan.slots:
            l = graph.layer(s.layer)
            self.layouts[s.layer] = [
                (w, tuple(shape), int(math.prod(shape)) if shape else 1)
                for w, shape in l.weight_shapes().items()]
            self.halves[s.layer] = sum(
                sz for _, _, sz in self.layouts[s.layer])
            zero = jnp.zeros((s.n_elems,), jnp.float32)
            if plan.compress:
                # the host copy lives in encoded space: quantize
                # encode(0) so the first prefetch decodes back to exact
                # zero moments (raw zeros would decode v to exp(0) ~ 1)
                enc = self._encode(s.layer, zero)
                q, scale = _q(enc)
                self.host_state[s.layer] = {"q": q, "scale": scale}
                self.residual[s.layer] = enc - _deq(q, scale, enc.shape)
            else:
                self.host_state[s.layer] = zero
        # committed like the params and grads offloaded_update receives,
        # so the second step reuses the first step's programs
        self.host_state = commit(self.host_state)
        self.residual = commit(self.residual)

    # --------------------------------------------------------- quant space
    # The m half quantizes linearly (signed, roughly normal — the int8
    # grid fits; a collapsed m merely zeroes one step's momentum, which
    # error feedback re-injects).  The v half quantizes in LOG space:
    # v spans orders of magnitude within one block, and a small-v element
    # that linear int8 collapses to zero turns the update denominator
    # into ``eps`` — a 1e8x update explosion.  Encoding 0.5*log(v+floor)
    # makes the int8 grid error *multiplicative* on sqrt(v): with block
    # absmax <= 0.5*|log(floor)| ~ 18.4 the grid is ~0.145, so the
    # denominator is off by at most e^0.0725 ~ 7.5% — bounded, never
    # collapsed.  The floor maps v=0 to an exactly-representable block
    # constant that decodes back to exactly 0.
    _V_LOG_FLOOR = 1e-16

    def _encode(self, layer: str, state):
        import jax.numpy as jnp
        h = self.halves[layer]
        v = jnp.maximum(state[h:], 0.0) + self._V_LOG_FLOOR
        return jnp.concatenate([state[:h], 0.5 * jnp.log(v)])

    def _decode(self, layer: str, enc):
        import jax.numpy as jnp
        h = self.halves[layer]
        v = jnp.exp(2.0 * enc[h:]) - self._V_LOG_FLOOR
        return jnp.concatenate([enc[:h], jnp.maximum(v, 0.0)])

    # ------------------------------------------------------------- transfers
    def prefetch(self, layer: str, stats=None):
        """H2D: dequantize the host copy into the fp32 working state."""
        from repro.optim.compression import _deq

        s = self.plan.slot(f"O:{layer}")
        if self.plan.compress:
            hs = self.host_state[layer]
            state = self._decode(
                layer, _deq(hs["q"], hs["scale"], (s.n_elems,)))
        else:
            state = self.host_state[layer]
        if stats is not None:
            stats.opt_prefetches += 1
            stats.opt_dma_bytes += s.host_nbytes
        return state

    def swap_out(self, layer: str, state, stats=None) -> None:
        """D2H: re-quantize the updated fp32 state with error feedback."""
        from repro.optim.compression import _deq, _q

        s = self.plan.slot(f"O:{layer}")
        if self.plan.compress:
            # EF runs in the quantization (encoded) space: the residual
            # carries the encoded-domain rounding error forward
            total = self._encode(layer, state) + self.residual[layer]
            q, scale = _q(total)
            self.host_state[layer] = {"q": q, "scale": scale}
            self.residual[layer] = total - _deq(q, scale, total.shape)
        else:
            self.host_state[layer] = state
        if stats is not None:
            stats.opt_swap_outs += 1
            stats.opt_dma_bytes += s.nbytes
            stats.opt_compressed_bytes += s.host_nbytes

    # --------------------------------------------------------------- packing
    def unpack(self, layer: str, flat):
        """Flat ``m || v`` vector -> ({wname: m}, {wname: v})."""
        layout = self.layouts[layer]
        half = sum(sz for _, _, sz in layout)
        ms, vs, off = {}, {}, 0
        for wname, shape, sz in layout:
            ms[wname] = flat[off:off + sz].reshape(shape)
            vs[wname] = flat[half + off:half + off + sz].reshape(shape)
            off += sz
        return ms, vs

    def pack(self, layer: str, ms, vs):
        import jax.numpy as jnp
        layout = self.layouts[layer]
        parts = [ms[w].reshape(-1) for w, _, _ in layout]
        parts += [vs[w].reshape(-1) for w, _, _ in layout]
        return jnp.concatenate(parts)


def offloaded_update(runtime: OptimRuntime, params, grads, stats=None):
    """One AdamW step through the offload dance; returns new params.

    Walks the slots in schedule (prefetch) order, per layer: prefetch +
    dequantize the host state, apply the reference AdamW math
    (``optim/optimizers.py:adamw`` — same bias correction, decoupled weight
    decay), swap the updated state back out with EF re-quantization.
    Layers without a slot (frozen, E-shared) keep their params untouched.
    ``stats`` (a ``SwapExecStats``) accumulates the ``opt_*`` counters.
    """
    import jax.numpy as jnp

    from repro.core.exec.store import commit

    runtime.count += 1
    step = runtime.count
    with span("optim.update", step=step):
        # fresh init params are uncommitted, later ones are committed
        # outputs
        params, grads = commit((params, grads))
        t = float(step)
        c1 = 1.0 - runtime.b1 ** t
        c2 = 1.0 - runtime.b2 ** t
        new_params = {ln: dict(entry) for ln, entry in params.items()}
        for s in sorted(runtime.plan.slots, key=lambda s: s.prefetch_eo):
            layer = s.layer
            if layer not in grads:
                continue
            with span("optim.decode", step=step, layer=layer):
                flat = runtime.prefetch(layer, stats)
            with span("optim.unpack", step=step, layer=layer):
                ms, vs = runtime.unpack(layer, flat)
            with span("optim.adamw", step=step, layer=layer):
                for wname, _, _ in runtime.layouts[layer]:
                    g = grads[layer][wname].astype(jnp.float32)
                    p = params[layer][wname]
                    m = runtime.b1 * ms[wname] + (1 - runtime.b1) * g
                    v = runtime.b2 * vs[wname] + (1 - runtime.b2) * g * g
                    upd = (m / c1) / (jnp.sqrt(v / c2) + runtime.eps)
                    new_params[layer][wname] = (
                        p - runtime.lr * (upd + runtime.weight_decay
                                          * p.astype(jnp.float32))
                    ).astype(p.dtype)
                    ms[wname], vs[wname] = m, v
            with span("optim.pack", step=step, layer=layer):
                flat = runtime.pack(layer, ms, vs)
            with span("optim.encode", step=step, layer=layer):
                runtime.swap_out(layer, flat, stats)
    return new_params
