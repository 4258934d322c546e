"""Build the program's serving path for a cell, drive it for a window,
and check what it served.

The window drives the program's own pieces: ``compute_psgs``,
``compute_fap`` and ``quiver_placement`` → ``TieredFeatureStore.build`` →
``build_executors`` (host and device executors) → ``calibrate_executors``
→ ``CostModelRouter`` → ``ServingEngine``. The served model is the
configuration's ``model`` (``bench/models``). The load generator calls
``ServingEngine.submit_batch([request])`` for each request and times it
from when it was due. The benchmark's own wrappers sit around the
executors' ``submit``/``run``, the ``infer_fn`` and (traced runs only)
``store.lookup_hops``; they record spans and keep the captured requests
for the check.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import gc
import math
import resource
import threading
import time
from collections import defaultdict
from typing import Optional

import jax
import numpy as np

from bench.lib import cells, counts, data, reference, traffic as tr

GRACE_S = 60.0           # how long past the close a due answer is awaited
LEAD_S = 0.05            # schedule starts this long after the last set-up step


@dataclasses.dataclass
class Req:
    index: int
    seeds: np.ndarray
    due: float                       # monotonic seconds
    capture: bool = False
    sent: float = math.nan
    sent_end: float = math.nan
    start: float = math.nan          # executor run span
    end: float = math.nan
    done: float = math.nan           # future resolved
    executor: Optional[str] = None
    error: Optional[BaseException] = None
    captured: Optional[dict] = None


class Recorder:
    """Thread-safe spans, compile events and the capture side channel."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.active = False
        self.tls = threading.local()
        self.tags: dict[int, Req] = {}
        self.spans: dict[str, list] = defaultdict(list)
        self.collect_ids: list[np.ndarray] = []
        self.compiles = 0
        self._lock = threading.Lock()

    def span(self, name: str, start: float, end: float) -> None:
        if self.active:
            with self._lock:
                self.spans[name].append((start, end))

    def on_compile_event(self, event: str, *args, **kwargs) -> None:
        """Counts each compilation once: JAX times every backend compile,
        a read from the persistent compile cache included (which also
        sends a ``cache_hits`` event of its own)."""
        if self.active and event.endswith("backend_compile_duration"):
            with self._lock:
                self.compiles += 1


class Watch:
    """Host stalls inside the window, for the log: the longest gap between
    the wake-ups of a 20 Hz heartbeat thread (the process, or the GIL,
    held up) and the longest garbage-collector pause."""

    PERIOD_S = 0.05

    def __init__(self):
        self.gap = (0.0, 0.0)            # (longest gap s, its start)
        self.gc_max = (0.0, -1)          # (longest pause s, generation)
        self.gc_n = 0
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)

    def _beat(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.PERIOD_S):
            now = time.monotonic()
            if now - last - self.PERIOD_S > self.gap[0]:
                self.gap = (now - last - self.PERIOD_S, last)
            last = now

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
            return
        dt = time.monotonic() - self._gc_t0
        self.gc_n += 1
        if dt > self.gc_max[0]:
            self.gc_max = (dt, info.get("generation", -1))

    def __enter__(self) -> "Watch":
        gc.callbacks.append(self._gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._gc)

    def summary(self, t0: float) -> str:
        return (f"longest heartbeat gap {self.gap[0]:.3f} s at "
                f"{self.gap[1] - t0:+.2f} s; {self.gc_n} collections, "
                f"longest {self.gc_max[0]:.3f} s (generation "
                f"{self.gc_max[1]})")


def _wrap_infer(rec: Recorder, infer_fn):
    def infer(hop_feats, hop_ids, deep_agg=None):
        out = infer_fn(hop_feats, hop_ids, deep_agg)
        req = getattr(rec.tls, "req", None)
        if req is not None and req.capture:
            req.captured = {"hops": list(hop_ids), "rows": list(hop_feats)}
        return out
    return infer


def _wrap_executor(rec: Recorder, name: str, ex) -> None:
    orig_submit, orig_run = ex.submit, ex.run

    def submit(seeds):
        req = getattr(rec.tls, "req", None)
        if req is not None:
            rec.tags[id(seeds)] = req
        return orig_submit(seeds)

    def run(seeds):
        req = rec.tags.pop(id(seeds), None)
        rec.tls.req = req
        t0 = time.monotonic()
        try:
            out = orig_run(seeds)
        finally:
            rec.tls.req = None
        t1 = time.monotonic()
        if req is not None:
            req.start, req.end, req.executor = t0, t1, name
            if req.captured is not None:
                req.captured["out"] = out
        rec.span(f"run.{ex.kind}", t0, t1)
        return out

    ex.submit, ex.run = submit, run


def _wrap_collect(rec: Recorder, store) -> None:
    """Span of ``store.lookup_hops`` on the host: its dispatch and the
    syncs the store makes itself, no sync added. The hop ids are kept as
    they come (device arrays stay on the device) and read after the
    window, so the traced path runs what the timed one runs."""
    orig = store.lookup_hops

    def lookup_hops(hops, *args, **kwargs):
        t0 = time.monotonic()
        rows = orig(hops, *args, **kwargs)
        t1 = time.monotonic()
        if rec.active:
            rec.span("collect", t0, t1)
            with rec._lock:
                rec.collect_ids.append(list(hops))
        return rows

    store.lookup_hops = lookup_hops


def calibration_batches(cfg: dict, psgs: np.ndarray) -> list[np.ndarray]:
    """Seed batches across the PSGS range at every size the mix can
    produce a host bucket for: the program's launcher's recipe (nodes
    taken in PSGS order from evenly spaced quantiles) at several sizes."""
    cal = cfg["calibration"]
    order = np.argsort(psgs, kind="stable")
    n = psgs.shape[0]
    return [order[int(q * n):][:size].astype(np.int64)
            for size in cal["sizes"] for q in cal["quantiles"]]


class System:
    """The program's serving stack for one configuration and seed."""

    def __init__(self, cfg: dict, seed: int, rec: Recorder,
                 root: str | None = None, log=print):
        from repro.core import (TieredFeatureStore, TopologySpec,
                                compute_fap, compute_psgs, quiver_placement)
        from repro.graph import CSRGraph
        from repro.launch.serve import build_executors

        t = time.monotonic()
        self.cfg = cfg
        self.fanouts = tuple(cfg["fanouts"])
        self.indptr, self.indices, self.feats = data.load(cfg, root)
        log(f"data: {cfg['num_nodes']} nodes, {self.indices.shape[0]} "
            f"edges, d={cfg['feat_dim']} ({time.monotonic() - t:.1f} s)")
        graph = CSRGraph(indptr=self.indptr, indices=self.indices,
                         num_nodes=int(cfg["num_nodes"]))
        self.out_degree = graph.out_degree
        psgs = compute_psgs(graph, self.fanouts)
        w = self.out_degree.astype(np.float64) + 1e-6
        fap = compute_fap(graph, self.fanouts, seed_prob=w / w.sum())
        pl = cfg["placement"]
        topo = TopologySpec(num_pods=1, devices_per_pod=1,
                            rows_per_device=int(pl["hbm_rows"]),
                            rows_host=int(pl["host_rows"]),
                            hot_replicate_fraction=float(
                                pl["hot_replicate_fraction"]))
        plan = quiver_placement(fap, topo)
        self.tier = np.asarray(plan.tier)
        self.store = TieredFeatureStore.build(self.feats, plan)
        log(f"placement: {plan.tier_counts()} "
            f"({time.monotonic() - t:.1f} s)")
        self.model_mod = cells.model(cfg["model"], root)
        self.use_params(self.model_mod.make_params(seed, cfg))
        infer = _wrap_infer(rec, self._infer)
        sv = cfg["serving"]
        self.executors = build_executors(
            graph, self.store, self.fanouts, infer, psgs,
            num_workers=int(sv["lanes"]), max_batch=int(sv["max_batch"]),
            sharded=False, fused=True, fuse_aggregate=False)
        for name, ex in self.executors.items():
            _wrap_executor(rec, name, ex)
        if rec.trace:
            _wrap_collect(rec, self.store)
        self.psgs = psgs
        self.router = self.engine = None
        self._t = t

    def use_params(self, params) -> None:
        """Serve ``params`` from the next request on (the compiled
        programs take the weights as an argument)."""
        self.params = params
        self.model = self.model_mod.served(params, self.cfg)

    def _infer(self, hop_feats, hop_ids, deep_agg=None):
        return self.model(hop_feats, hop_ids, deep_agg)

    def calibrate(self, log=print) -> None:
        """The program's calibration, then its router and engine. Run it
        after ``warm_up``, so no sample times a first call."""
        from repro.serving import (CostModelRouter, ServingEngine,
                                   calibrate_executors)

        cfg, sv = self.cfg, self.cfg["serving"]
        cal = cfg["calibration"]
        curves = calibrate_executors(
            self.executors, calibration_batches(cfg, self.psgs), self.psgs,
            repeats=int(cal["repeats"]), tail=float(cal.get("tail", 1.0)))
        self.router = CostModelRouter.from_curves(
            self.psgs, curves, sv["policy"], executors=self.executors)
        self.engine = ServingEngine(self.executors, self.router,
                                    max_inflight=int(sv["max_inflight"]),
                                    admission=sv["admission"])
        log(f"calibrated: host/device PSGS cut-point "
            f"{self.router.crossover('host', 'device')} "
            f"({time.monotonic() - self._t:.1f} s; host peak RSS "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f}"
            f" GiB)")
        for name, c in curves.items():
            log(f"curve {name}: psgs {np.round(c.psgs, 1).tolist()} avg_ms "
                f"{np.round(c.avg * 1e3, 2).tolist()} tail_ms "
                f"{np.round(c.mx * 1e3, 2).tolist()}")

    def close(self) -> None:
        """Stop the lanes and free the program's device state."""
        self.engine.close()
        for k in ("engine", "router", "executors", "store"):
            setattr(self, k, None)
        gc.collect()


def _submit(engine, rec: Recorder, req: Req, futs: list) -> None:
    from repro.core.serving import Request

    rec.tls.req = req
    req.sent = time.monotonic()
    try:
        fut = engine.submit_batch([Request(req.index, req.seeds, req.due)])
    finally:
        rec.tls.req = None
    req.sent_end = time.monotonic()
    rec.span("generator", req.sent, req.sent_end)

    def done(f, req=req):
        req.done = time.monotonic()
        req.error = f.exception()
    fut.add_done_callback(done)
    futs.append(fut)


def mix_sizes(traffic: dict, seconds: float) -> np.ndarray:
    """Every request size the mix sends in a window (the same for every
    seed)."""
    n = tr.open_count(traffic, seconds) if traffic["loop"] == "open" else 1
    return np.unique(tr.size_quantiles(traffic["seeds"], n))


def warm_up(system: System, traffic: dict, seconds: float,
            draw: tr.SeedDraw, rec: Recorder, log=print) -> None:
    """Run every executor once at every request size the window will send
    (the executors slice each answer to its request's size, and each size
    is a program of its own), calibrate, then send a few of the largest
    requests through the engine with every lane busy."""
    rng = np.random.default_rng([7, 7])
    sizes = mix_sizes(traffic, seconds)
    for size in sizes:
        seeds = draw.draw(rng, int(size))
        for ex in system.executors.values():
            ex.run(seeds)
    system.calibrate(log)
    size = int(sizes[-1])
    futs: list = []
    lanes = int(system.cfg["serving"]["lanes"]) * len(system.executors)
    for i in range(2 * lanes):
        _submit(system.engine, rec,
                Req(-1 - i, draw.draw(rng, size), time.monotonic()), futs)
    cf.wait(futs)


def drive(system: System, traffic: dict, seconds: float, seed: int,
          draw: tr.SeedDraw, rec: Recorder) -> tuple[list, float]:
    """Serve one window. Returns (requests attempted, window start)."""
    engine = system.engine
    futs: list = []
    reqs: list[Req] = []
    if traffic["loop"] == "open":
        due, seeds = tr.open_schedule(traffic, seconds, seed, draw)
        keep = tr.capture_set(traffic, seed,
                              np.array([s.shape[0] for s in seeds]))
        t0 = time.monotonic() + LEAD_S
        rec.active = True
        for i, (d, s) in enumerate(zip(due, seeds)):
            req = Req(i, s, t0 + d, capture=i in keep)
            wait = req.due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            reqs.append(req)
            _submit(engine, rec, req, futs)
        t_end = t0 + seconds
        now = time.monotonic()
        if t_end > now:
            time.sleep(t_end - now)
        cf.wait(futs, timeout=max(t_end + GRACE_S - time.monotonic(), 0))
        rec.active = False
    elif traffic["loop"] == "closed":
        keep = tr.capture_set(traffic, seed, None)
        lock = threading.Lock()
        count = [0]
        t0 = time.monotonic() + LEAD_S
        t_end = t0 + seconds

        def client(c: int) -> None:
            stream = tr.client_stream(traffic, seed, c, draw)
            mine: list = []
            while True:
                s = next(stream)
                now = time.monotonic()
                if now >= t_end:
                    return
                if now < t0:
                    time.sleep(t0 - now)
                    now = t0
                with lock:
                    i = count[0]
                    count[0] += 1
                req = Req(i, s, now, capture=i in keep)
                with lock:
                    reqs.append(req)
                _submit(engine, rec, req, mine)
                try:
                    mine[-1].result(timeout=max(
                        t_end + GRACE_S - time.monotonic(), 0))
                except Exception:    # noqa: BLE001 — judged from req.error
                    pass

        rec.active = True
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(int(traffic["clients"]))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        rec.active = False
        reqs.sort(key=lambda r: r.index)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return reqs, t0


@dataclasses.dataclass
class RunRecord:
    """What a run measured; the per-layer metric readers read this."""
    cell: str
    config: dict
    traffic: dict
    seconds: float
    t0: float
    reqs: list
    spans: dict
    counters: dict
    routed: dict
    collect_bytes: Optional[int]
    trace: Optional[dict]
    peaks: dict
    flops_per_seed: int
    compiles: int = 0

    @property
    def completed(self) -> list:
        return [r for r in self.reqs
                if r.error is None and not math.isnan(r.done)]

    def latencies(self) -> list[float]:
        """Due → ready of every attempted request; one that failed or never
        came counts at the time the run stopped waiting for it."""
        stop = self.t0 + self.seconds + GRACE_S
        return [((r.done if r.error is None and not math.isnan(r.done)
                  else stop) - r.due) for r in self.reqs]


def slowest_stretch(reqs: list, t0: float) -> str:
    """The longest stretch in which requests were waiting and none
    completed, for the log."""
    done = np.sort([r.done for r in reqs if not math.isnan(r.done)])
    if done.size < 2:
        return "no stretch"
    due = np.sort([r.due for r in reqs])
    waiting = (np.searchsorted(due, done[:-1], side="right")
               - np.arange(1, done.size))
    gaps = np.where(waiting > 0, np.diff(done), 0.0)
    i = int(np.argmax(gaps))
    return (f"longest stretch with none completing {gaps[i]:.3f} s at "
            f"{done[i] - t0:+.2f} s, {int(waiting[i])} waiting")


def failed(reqs: list) -> int:
    return sum(1 for r in reqs if r.error is not None or math.isnan(r.done))


def end_to_end(run: RunRecord) -> dict:
    from bench.lib.stats import quantile

    t_end = run.t0 + run.seconds
    seeds = sum(r.seeds.shape[0] for r in run.completed if r.done <= t_end)
    lat = run.latencies()
    return {"seeds_per_s": seeds / run.seconds,
            "latency_p50_ms": quantile(lat, 0.5) * 1e3,
            "latency_p95_ms": quantile(lat, 0.95) * 1e3}


def host_captures(reqs: list) -> list[dict]:
    """Captured requests that completed, moved to host memory."""
    out = []
    for r in reqs:
        c = r.captured
        if c is None or r.error is not None or "out" not in c:
            continue
        out.append({"index": r.index, "executor": r.executor,
                    "seeds": r.seeds,
                    "hops": [np.asarray(h) for h in c["hops"]],
                    "rows": [np.asarray(x) for x in c["rows"]],
                    "out": np.asarray(c["out"])})
        r.captured = None
    return out


def check(caps: list[dict], model, params, system_data: tuple, fanouts,
          limit: dict, precision: str) -> tuple[dict, dict]:
    """Compare the captured requests with the plain reference of the
    served ``model`` (its module, ``cells.model``). Returns (numbers,
    per-executor counts)."""
    indptr, indices, feats = system_data
    bad = rows = 0
    gap = 0.0
    per_exec: dict[str, int] = defaultdict(int)
    for c in caps:
        per_exec[c["executor"]] += 1
        bad += reference.bad_sample_slots(c["seeds"], c["hops"], fanouts,
                                          indptr, indices)
        rows += reference.row_mismatches(c["rows"], c["hops"], feats)
        gap = max(gap, reference.output_gap(model, params, c["out"],
                                            c["hops"], feats, fanouts,
                                            precision=precision))
    return ({"sample_bad_slots": bad, "row_mismatches": rows,
             "output_gap": gap}, dict(per_exec))


def collect_bytes(rec: Recorder, tier: np.ndarray, feat_dim: int) -> int:
    """Useful gather bytes of every lookup of the window, read once the
    window has closed."""
    total = 0
    for hops in rec.collect_ids:
        ids = np.concatenate([np.asarray(h).reshape(-1) for h in hops])
        total += counts.gather_bytes(ids, tier, feat_dim)
    rec.collect_ids = []
    return total
