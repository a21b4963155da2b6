"""Serving launcher: multi-tenant personalization + LM generation.

Two subcommands::

    # N simulated users fine-tuning a zoo model over bucketed traffic
    python -m repro.launch.serve personalize --model lenet5 \
        --users 8 --steps 3 --buckets 8,16 --max-live 8 --json stats.json

    # batched prefill + greedy decode on an LM arch
    python -m repro.launch.serve generate --arch llama3.2-3b --test-mesh \
        --requests 8 --gen-tokens 16

``personalize`` drives :class:`repro.serve.PersonalizationService`: every
user shares one frozen base tree and one compiled memory plan per batch
bucket; admission control splits the device arena between live sessions
and the stats dump shows the QoS counters (cache hit rate, per-session
peak bytes vs share, steps/sec, rejections).

``generate`` implements the standard two-phase server: requests are
batched, prefilled — one fused full-sequence forward filling the KV cache
(``model.prefill_fn``) when the family supports it, falling back to the
sequential per-token cache fill otherwise — then decoded token-by-token
with greedy sampling.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List


# ---------------------------------------------------------------------------
# personalize: the multi-tenant fine-tuning loop
# ---------------------------------------------------------------------------

def _parse_qos(spec: str):
    """Parse ``name:weight:slots,...`` into QosClass objects plus a
    flattened slot list used to deal users across classes in order."""
    from repro.serve import QosClass

    classes, deal = [], []
    for part in spec.split(","):
        fields = part.split(":")
        if not 1 <= len(fields) <= 3 or not fields[0]:
            raise SystemExit(f"bad --qos entry {part!r}; "
                             "expected name[:weight[:slots]]")
        name = fields[0]
        weight = float(fields[1]) if len(fields) > 1 else 1.0
        slots = int(fields[2]) if len(fields) > 2 else 1
        classes.append(QosClass(name, weight, slots=slots))
        deal.extend([name] * slots)
    return tuple(classes), deal


def run_personalize(args: argparse.Namespace) -> None:
    import numpy as np

    from repro.core import MemoryPlanConfig
    from repro.core.zoo import ZOO
    from repro.runtime.fault import FaultInjector
    from repro.serve import PersonalizationService
    from repro.serve.buckets import dummy_batch

    if args.model not in ZOO:
        raise SystemExit(f"unknown zoo model {args.model!r}; "
                         f"choose from {sorted(ZOO)}")
    graph = ZOO[args.model]()
    buckets = tuple(int(b) for b in args.buckets.split(","))
    config = MemoryPlanConfig(executor=args.executor)
    injector = None
    if args.kill_user is not None:
        injector = FaultInjector()
        injector.arm_kill(f"session:u{args.kill_user}",
                          after=args.kill_after)

    qos_classes, qos_of = None, {}
    max_live = args.max_live
    if args.qos:
        qos_classes, deal = _parse_qos(args.qos)
        # deal users across the declared slots in order, wrapping so
        # --users larger than the slot total still gets a class label
        qos_of = {f"u{u}": deal[u % len(deal)] for u in range(args.users)}
        # admission requires the class slots to sum to the session cap
        max_live = len(deal)

    budget = args.device_budget_mb * (1 << 20) if args.device_budget_mb \
        else None
    svc = PersonalizationService(
        graph, buckets=buckets, max_live_sessions=max_live,
        device_budget_bytes=budget, config=config, lr=args.lr,
        qos=qos_classes, interleave=args.interleave,
        bus_gbps=args.bus_gbps if args.bus_gbps > 0 else None,
        bus_latency_s=args.bus_latency,
        injector=injector, seed=args.seed)
    t0 = time.time()
    svc.warmup()
    t_warm = time.time() - t0
    print(f"warmup: {len(svc.buckets)} buckets compiled + replayed in "
          f"{t_warm:.2f}s; arena share = "
          f"{svc.admission.arena_share_bytes} B/session")

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for step in range(args.steps):
        # enqueue the whole round, then drain once: in interleaved mode
        # the scheduler round-robins every user's cursor at phase
        # boundaries, hiding one tenant's DMA under another's compute;
        # with --no-interleave the same queue drains FIFO
        reqs = []
        for u in range(args.users):
            # bucketed traffic: odd users send short batches (padded up),
            # even users fill the largest bucket
            n = int(rng.integers(1, buckets[0] + 1)) if u % 2 \
                else buckets[-1]
            x, y = dummy_batch(graph, n, seed=step * args.users + u)
            reqs.append(svc.enqueue(f"u{u}", x, y,
                                    qos=qos_of.get(f"u{u}")))
        svc.drain()
        for u, req in enumerate(reqs):
            res = req.result
            tag = f"loss={res.loss:.4f} bucket={res.bucket}" \
                if res.ok else res.reason
            print(f"  step {step} u{u}: {res.status} {tag}")
    t_total = time.time() - t0

    rep = svc.report()
    rep["driver"] = {"users": args.users, "steps": args.steps,
                     "wall_time_s": round(t_total, 3)}
    sched = rep.get("scheduler")
    if args.interleave and sched:
        hidden = sched["hidden_dma_s"] + sched["opt_hidden_dma_s"]
        exposed = sched["exposed_dma_s"] + sched["opt_exposed_dma_s"]
        print(f"interleaved drain: {hidden*1e3:.1f} ms DMA hidden under "
              f"compute ({sched['cross_hidden_dma_s']*1e3:.1f} ms under "
              f"*other* sessions'), {exposed*1e3:.1f} ms exposed")
    print(json.dumps(rep, indent=2, default=str))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=2, default=str)
        print(f"stats written to {args.json}")


# ---------------------------------------------------------------------------
# generate: batched prefill + greedy decode
# ---------------------------------------------------------------------------

def run_generate(args: argparse.Namespace) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ARCHS
    from repro.launch.mesh import make_production_mesh, make_test_mesh
    from repro.models.model import build_model, reduce_config

    cfg = ARCHS[args.arch]
    if args.test_mesh:
        cfg = reduce_config(cfg)
        make_test_mesh(model=1)
    else:
        make_production_mesh()
    model = build_model(cfg)
    if model.decode_fn is None:
        raise SystemExit(f"{args.arch} has no decode path")

    params = model.init(jax.random.PRNGKey(0))
    b = args.requests
    max_seq = args.prompt_len + args.gen_tokens + 8

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(b, args.prompt_len),
                           dtype=np.int32)

    decode = jax.jit(model.decode_fn)
    state = model.decode_init(b, max_seq)
    tokens = jnp.asarray(prompts)

    # ---- prefill: one fused full-prompt forward when the family supports
    # it; sequential per-token cache fill as the fallback ------------------
    t0 = time.time()
    if model.prefill_fn is not None and not args.sequential_prefill:
        logits, state = jax.jit(model.prefill_fn)(params, state, tokens)
        mode = "batched"
    else:
        logits = None
        for t in range(args.prompt_len):
            logits, state = decode(params, state, tokens[:, t],
                                   jnp.full((b,), t, jnp.int32))
        mode = "sequential"
    t_prefill = time.time() - t0

    # ---- greedy decode ---------------------------------------------------
    out_tokens: List[np.ndarray] = []
    cur = jnp.argmax(logits[:, :cfg.vocab], axis=-1).astype(jnp.int32)
    t0 = time.time()
    for i in range(args.gen_tokens):
        out_tokens.append(np.asarray(cur))
        logits, state = decode(
            params, state, cur,
            jnp.full((b,), args.prompt_len + i, jnp.int32))
        cur = jnp.argmax(logits[:, :cfg.vocab], axis=-1).astype(jnp.int32)
    t_decode = time.time() - t0

    gen = np.stack(out_tokens, axis=1)
    print(f"prefill ({mode}): {t_prefill*1000:.1f} ms for "
          f"{b}x{args.prompt_len} tok")
    print(f"decode:  {t_decode*1000:.1f} ms for {b}x{args.gen_tokens} tok "
          f"({b*args.gen_tokens/max(t_decode,1e-9):.1f} tok/s)")
    print("generated token ids (first request):", gen[0].tolist())


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("personalize",
                       help="multi-tenant per-user fine-tuning")
    p.add_argument("--model", default="lenet5", help="zoo model name")
    p.add_argument("--users", type=int, default=8)
    p.add_argument("--steps", type=int, default=2,
                   help="fine-tune rounds per user")
    p.add_argument("--buckets", default="8,16",
                   help="comma-separated batch buckets")
    p.add_argument("--max-live", type=int, default=8)
    p.add_argument("--device-budget-mb", type=int, default=0,
                   help="arena budget (MiB); 0 derives it from the plans")
    p.add_argument("--executor", default="sim", choices=("sim", "async"))
    p.add_argument("--interleave", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="phase-interleave live sessions so one tenant's "
                        "DMA overlaps another's compute "
                        "(--no-interleave = synchronous FIFO drain)")
    p.add_argument("--qos", default="",
                   help="comma-separated QoS classes as "
                        "name[:weight[:slots]], e.g. "
                        "'premium:2.0:2,standard:1.0:6'; users are dealt "
                        "across the declared slots in order")
    p.add_argument("--bus-gbps", type=float, default=0.0,
                   help="emulated host<->device bus bandwidth (GB/s); "
                        "0 disables pacing")
    p.add_argument("--bus-latency", type=float, default=0.0,
                   help="emulated per-access bus latency (seconds); the "
                        "sync FIFO path pays it per transfer, the async "
                        "engine amortizes it across the queue")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--kill-user", type=int, default=None,
                   help="arm a fault-injection kill for user uN")
    p.add_argument("--kill-after", type=int, default=0,
                   help="fire on the Nth request after arming")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", default="", help="write stats JSON here")
    p.set_defaults(fn=run_personalize)

    g = sub.add_parser("generate", help="batched prefill + greedy decode")
    g.add_argument("--arch", required=True)
    g.add_argument("--test-mesh", action="store_true")
    g.add_argument("--requests", type=int, default=4)
    g.add_argument("--prompt-len", type=int, default=16)
    g.add_argument("--gen-tokens", type=int, default=16)
    g.add_argument("--sequential-prefill", action="store_true",
                   help="force the per-token fallback prefill")
    g.set_defaults(fn=run_generate)

    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
