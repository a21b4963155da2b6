"""A closed loop of users fine-tuning through ``PersonalizationService``.

The traffic file gives the users, the rows every even user sends
(``even_size``), the sizes odd users cycle through (``odd_sizes``; every
seed sends the same sizes, in its own order per user), how many distinct
request batches set-up makes for each user (``pool``, more than a run's
waves, so that no request repeats; a user that has sent them all starts
over), and the bounds of the warm-up in waves
(``warm_min``, ``warm_max``).  Each wave enqueues one request per user and
drains: every user waits for its reply before its next request, which
trains on the params the previous one produced.

``correct`` replays every request each user sent, set-up's included, in the
plain reference from the same weights once the window has closed: each
request's loss, and each user's head at the end.

Besides the device peak and set-up, a run records the requests completed a
second (``serve_steps_per_s``), the 95th percentile of their latency
(``serve_p95_ms``), the scheduler's transfer times and the model FLOPs a
second, and logs them; metrics that read them can be added as files.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import checks, flops
from chipbench import reference as ref
from chipbench.harness import Record, log, settle
from chipbench.loop_train import (_peak_bytes, _reduce_trace, _traced,
                                  check_layout, host)

SPANS = ("drain",)
SCHEDULER_KEYS = ("exposed_dma_s", "opt_exposed_dma_s", "hidden_dma_s",
                  "opt_hidden_dma_s", "cross_hidden_dma_s")


def request_plan(traffic: dict, seed: int) -> List[List[Tuple[int, int]]]:
    """Per user, the ``(pool entry, rows)`` of each request in order."""
    rng = np.random.default_rng(seed)
    odd = list(traffic["odd_sizes"])
    out = []
    for u in range(int(traffic["users"])):
        if u % 2:
            sizes = [odd[i] for i in rng.permutation(len(odd))]
        else:
            sizes = [int(traffic["even_size"])]
        out.append([(j, int(sizes[j % len(sizes)]))
                    for j in range(int(traffic["pool"]))])
    return out


def frozen_gains(cfg: dict, model) -> Dict[str, float]:
    """The init gain of every frozen layer (the configuration's
    ``init.frozen_gain``)."""
    g = float(cfg.get("init", {}).get("frozen_gain", 1.0))
    return {l["name"]: g for l in model.LAYERS
            if l["kind"] in ("conv", "dense")
            and l["name"] not in model.TRAINABLE}


def make_pool(cfg: dict, traffic: dict, seed: int):
    """``(xs, ys)`` of shape ``(users, pool, even_size, ...)``, on the
    device, in one jitted call."""
    shape = (int(traffic["users"]), int(traffic["pool"]),
             int(traffic["even_size"])) + tuple(cfg["input_shape"])
    return ref.make_images(seed, 1, shape, cfg["classes"])


class Service:
    """The system under test, built as the configuration states it, on
    the weights the benchmark makes from the seed."""

    def __init__(self, cfg: dict, model, base_params) -> None:
        import repro.serve.service as service_module
        from repro.core import MemoryPlanConfig
        from repro.core.zoo import ZOO
        from repro.serve import PersonalizationService

        self.graph = ZOO[cfg["graph"]]()
        check_layout(self.graph, ref.param_shapes(model.LAYERS),
                     model.TRAINABLE)
        o = cfg["optimizer"]
        self.svc = PersonalizationService(
            self.graph, buckets=tuple(cfg["buckets"]),
            max_live_sessions=int(cfg["max_live_sessions"]),
            config=MemoryPlanConfig(**cfg["plan"]), interleave=True,
            lr=o["lr"], momentum=o["momentum"])
        self.svc.servable.base_params = base_params
        # the planner's share of the warm-up, timed around its calls
        real = service_module.compile_plan
        spent = [0.0]

        def timed(*args, **kwargs):
            t = time.perf_counter()
            try:
                return real(*args, **kwargs)
            finally:
                spent[0] += time.perf_counter() - t

        service_module.compile_plan = timed
        try:
            self.svc.warmup()
        finally:
            service_module.compile_plan = real
        self.plan_compile_s = spent[0]

    def wave(self, reqs) -> List[Tuple[float, bool, float]]:
        """Enqueue one request per user, drain; per request
        ``(latency_s, ok, loss)``."""
        t_in, handles = [], []
        for user, x, y in reqs:
            t_in.append(time.perf_counter())
            handles.append(self.svc.enqueue(user, x, y))
        self.svc.drain()
        t_out = time.perf_counter()
        out = []
        for t, h in zip(t_in, handles):
            ok = h.result is not None and h.result.ok
            out.append((t_out - t, ok, h.result.loss if ok else math.nan))
        return out

    def scheduler_report(self) -> Dict[str, float]:
        return self.svc.report().get("scheduler", {})

    def head(self, user: str):
        sess = self.svc.servable.sessions.get(user)
        return None if sess is None else host(sess.params)


class Traffic:
    """Each user's requests, made on the device in set-up, and what was
    sent and answered."""

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        import jax
        self.xs, self.ys = make_pool(cfg, traffic, seed)
        self.plan = request_plan(traffic, seed)
        self.arrays = [[(self.xs[u, j, :n], self.ys[u, j, :n])
                        for j, n in cycle]
                       for u, cycle in enumerate(self.plan)]
        jax.block_until_ready(self.arrays)
        self.sent: Dict[int, List[Tuple[int, int]]] = {
            u: [] for u in range(len(self.plan))}
        self.losses: Dict[int, List[float]] = {
            u: [] for u in range(len(self.plan))}

    def wave_requests(self):
        reqs = []
        for u, cycle in enumerate(self.plan):
            i = len(self.sent[u]) % len(cycle)
            reqs.append((f"u{u}",) + self.arrays[u][i])
            self.sent[u].append(cycle[i])
        return reqs

    def note(self, results) -> None:
        for u, (_, ok, loss) in enumerate(results):
            self.losses[u].append(loss if ok else math.nan)


def served(service: Service, traffic: Traffic) -> dict:
    """What the timed path produced: every request's loss and every
    user's head at the end (host arrays)."""
    return {"losses": traffic.losses,
            "heads": {u: service.head(f"u{u}") for u in traffic.losses}}


def reference_replay(cfg: dict, model, base, xs, ys,
                     sent: Dict[int, List[Tuple[int, int]]],
                     precision: str = "highest") -> dict:
    """Each user's requests in order through the plain reference: losses
    and the final head."""
    import jax
    import jax.numpy as jnp

    o = cfg["optimizer"]
    features, vg, step = _reference_fns(model, precision, o["lr"],
                                        o["momentum"])
    users = xs.shape[0]
    entries = sorted({j for s in sent.values() for j, _ in s})
    feats = {}
    for j in entries:
        f = features(base, xs[:, j].reshape((-1,) + xs.shape[3:]))
        feats[j] = f.reshape(users, xs.shape[2], -1)
    head0 = {k: base[k] for k in model.TRAINABLE}
    out = {"losses": {}, "heads": {}, "head0": host(head0)}
    for u, entries_u in sent.items():
        p = head0
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses = []
        for j, n in entries_u:
            loss, g = vg(p, feats[j][u, :n], ys[u, j, :n])
            losses.append(float(loss))
            p, v = step(p, v, g)
        out["losses"][u] = losses
        out["heads"][u] = host(p)
    return out


# the jitted reference of each (configuration, precision, optimizer), built
# once per process so that runs sharing a process share its programs
_REFERENCE_FNS: Dict[tuple, Any] = {}


def _reference_fns(model, precision: str, lr: float, momentum: float):
    import jax
    key = (model, precision, lr, momentum)
    if key not in _REFERENCE_FNS:
        ops = ref.Ops(precision)

        def loss_fn(p, f, y):
            return ref.cross_entropy(model.head(p, f, ops), y)

        _REFERENCE_FNS[key] = (
            jax.jit(lambda p, x: model.features(p, x, ops, ref)),
            jax.jit(jax.value_and_grad(loss_fn)),
            jax.jit(lambda p, v, g: ref.momentum_sgd(p, v, g, lr,
                                                     momentum)))
    return _REFERENCE_FNS[key]


def numbers(got: dict, refr: dict) -> Dict[str, float]:
    """``loss_rel``: every request's loss against the reference's, the
    worst gap over the larger of the reference's loss and the median
    request's.  ``param_rel``: every user's final head against the
    reference's, the worst leaf's ``||a - b||`` over the larger of its
    change from the base weights and the median leaf's change."""
    ref_all = [abs(v) for ls in refr["losses"].values() for v in ls]
    floor = float(np.median(ref_all)) if ref_all else 0.0
    loss_gaps, param_gaps = [], []
    for u, ref_losses in refr["losses"].items():
        prog_losses = got["losses"].get(u, [])
        head = got["heads"].get(u)
        if len(prog_losses) != len(ref_losses) or head is None:
            return {"loss_rel": math.inf, "param_rel": math.inf}
        for a, b in zip(prog_losses, ref_losses):
            if not (math.isfinite(a) and math.isfinite(b)):
                loss_gaps.append(math.inf)
            else:
                loss_gaps.append(abs(a - b) / max(abs(b), floor))
        change = {k: {n: np.asarray(refr["heads"][u][k][n], np.float64)
                      - np.asarray(refr["head0"][k][n], np.float64)
                      for n in e} for k, e in refr["heads"][u].items()}
        rn = checks.norms(change)
        leaf_floor = float(np.median(list(rn.values())))
        pa, ra = checks.leaves(head), checks.leaves(refr["heads"][u])
        if set(pa) != set(ra):
            return {"loss_rel": math.inf, "param_rel": math.inf}
        param_gaps += [float(np.linalg.norm(pa[k] - ra[k]))
                       / max(rn[k], leaf_floor) for k in ra]
    return {"loss_rel": checks.worst(loss_gaps),
            "param_rel": checks.worst(param_gaps)}


@dataclasses.dataclass
class Served:
    """A run of the timed path, and what the reference needs to check it."""
    rec: Record
    got: dict
    sent: Dict[int, List[Tuple[int, int]]]
    xs: Any
    ys: Any


def serve(ctx) -> Served:
    """Set-up, warm-up and the measured window of one run; the program's
    state is freed when it returns."""
    import jax

    cell = ctx.cell
    cfg, tr = cell.config, cell.traffic
    rec = Record()
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        base = ref.make_params(ref.param_shapes(cell.model.LAYERS), ctx.seed,
                               frozen_gains(cfg, cell.model))
        t = time.perf_counter()
        service = Service(cfg, cell.model, base)
        adm = service.svc.admission
        rec.counters["plan_compile_s"] = service.plan_compile_s
        rec.counters["planned_peak_mb"] = adm.device_budget_bytes / 1e6
        log(f"service warm-up {time.perf_counter() - t:.2f} s, arena share "
            f"{adm.arena_share_bytes} B x {adm.max_live_sessions} sessions")
        traffic = Traffic(cfg, tr, ctx.seed)
        warm: List[float] = []
        c_prev = ctx.compiles.total
        while len(warm) < int(tr["warm_max"]):
            t = time.perf_counter()
            traffic.note(service.wave(traffic.wave_requests()))
            warm.append(time.perf_counter() - t)
            c_now = ctx.compiles.total
            # warm until a wave compiles nothing and is no faster than the
            # best before it
            if (len(warm) >= int(tr["warm_min"]) and c_now == c_prev
                    and (len(warm) == 1
                         or warm[-1] > 0.98 * min(warm[:-1]))):
                break
            c_prev = c_now
        log(f"warm waves: {[round(t, 4) for t in warm]} s")
        settle()

        lat: List[float] = []
        ok_count = [0]
        sched: List[Dict[str, float]] = []

        def one(i):
            if ctx.trace:
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation(SPANS[0]):
                    res = service.wave(traffic.wave_requests())
                rec.spans.append((SPANS[0], t, time.perf_counter()))
            else:
                res = service.wave(traffic.wave_requests())
            sched.append(service.scheduler_report())
            traffic.note(res)
            lat.extend(r[0] for r in res)
            ok_count[0] += sum(1 for r in res if r[1])

        c0, g0 = ctx.compiles.total, ctx.gcs.n
        with _traced(ctx) as window:
            # a wave ends when its losses are back; the window closes when
            # the last wave's head updates are done too
            n, t_start, t_end = window(
                one, ctx.seconds, lambda _: jax.block_until_ready(
                    [s.params for s in
                     service.svc.servable.sessions.values()]))
        rec.window_compiles = ctx.compiles.total - c0
        rec.window_gcs = ctx.gcs.n - g0
        rec.window_units = n
        rec.peak_bytes = _peak_bytes()
        got = served(service, traffic)
        sent = dict(traffic.sent)
        xs, ys = traffic.xs, traffic.ys
        del service, traffic
        gc.collect()

    window_s = t_end - t_start
    rec.e2e["setup_s"] = t_start - ctx.t0
    rec.e2e["serve_steps_per_s"] = ok_count[0] / window_s
    rec.e2e["serve_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    rec.e2e["peak_hbm_mb"] = rec.peak_bytes / 1e6
    rec.counters["window_s"] = window_s
    rec.counters["requests"] = len(lat)
    for key in SCHEDULER_KEYS:
        rec.counters[key] = float(sum(s.get(key, 0.0) for s in sched))
    rows = sum(n for user_sent in sent.values()
               for _, n in user_sent[len(user_sent) - rec.window_units:])
    rec.counters["serve_flops_per_s"] = (
        flops.train_per_sample(cell.model.LAYERS, cell.model.TRAINABLE)
        * rows / window_s)
    rec.trace = _reduce_trace(ctx, SPANS)
    rec.attempted = len(lat)
    rec.failed = len(lat) - ok_count[0]
    return Served(rec, got, sent, xs, ys)


def run(ctx) -> Record:
    cell = ctx.cell
    s = serve(ctx)
    t = time.perf_counter()
    base = host(ref.make_params(ref.param_shapes(cell.model.LAYERS),
                                ctx.seed, frozen_gains(cell.config,
                                                       cell.model)))
    refr = reference_replay(cell.config, cell.model, base, s.xs, s.ys,
                            s.sent)
    s.rec.numbers = numbers(s.got, refr)
    log(f"serving: {s.rec.e2e['serve_steps_per_s']!r} steps/s, p95 "
        f"{s.rec.e2e['serve_p95_ms']!r} ms over {s.rec.attempted} requests")
    log(f"reference replay of {sum(map(len, s.sent.values()))} requests "
        f"in {time.perf_counter() - t:.2f} s")
    return s.rec
