"""Per-user mini-batch requests in an open loop (traffic kind
``minibatch``).

Each request goes through the program's serving path:
``SamplingService.prepare`` (sample, normalize, bucket, lay out) in the
generator's thread, then ``ServeLoop.submit``; the loop batches
same-bucket requests and runs each batch as one jitted ``run_batch``
pass on one of its overlays.  A request is timed from the moment it was
due until its target logits are on the host.  The features stay in host
memory, where the service gathers each request's rows.

Set-up runs one request of each bucket the traffic reaches at every
batch size up to ``max_batch``, so nothing compiles inside the window:
the batched pass at each power of two, and the padding and slicing of
every ragged batch size.  The pool is fixed by the mix's ``pool_seed``,
so the buckets are too: the mix's ``warm`` lists the first pool index
that reaches each bucket (found once by ``perfbench/warmset.py``), and
set-up prepares only those.  A mix without ``warm`` prepares the whole
pool to find them.

After the window, a sample of the answered requests drawn from the seed
(with the largest ego networks and requests served in batches of two
or more in it) is checked: each sampled ego network against the
request and the parent graph (its first vertices are the request's
targets, every edge exists, every vertex has exactly its capped number
of in-edges), and each request's logits against the plain reference run
on its own un-padded ego network.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import (Batch, Metrics, OverlayPool, QueueFullError,
                           request_cost)
from repro.sampling import SamplingService, TargetRequest

from . import data, system, traffic
from .correct import Comparison

POLL_S = 2.5e-4          # the generator polls the loop's deadlines this often
ANSWER_GRACE_S = 60.0    # how long past the window an answer may come


class _Recorder(Metrics):
    """The loop's metrics, with a hook that sees each response as the
    worker records it."""

    sink = None

    def record_response(self, resp, latency_s, queue_wait_s=None,
                        execute_s=None, compile_s=None) -> None:
        super().record_response(resp, latency_s, queue_wait_s=queue_wait_s,
                                execute_s=execute_s, compile_s=compile_s)
        if self.sink is not None:
            self.sink(resp, queue_wait_s)


class Minibatch:
    def __init__(self, cell, seed: int, seconds: float, spans) -> None:
        self.cell, self.seed, self.spans = cell, seed, spans
        self.seconds = seconds
        self.cfg, self.tr = cell.config, cell.traffic
        self.reset()

    def reset(self) -> None:
        """Forget what a window recorded."""
        self.answers: Dict[str, tuple] = {}
        self.due: Dict[str, float] = {}
        self.egos: Dict[str, object] = {}
        self.asked: Dict[str, tuple] = {}
        self.prepare_s: List[float] = []
        self.late_s: List[float] = []
        self.backlog: List[int] = []
        self.refused = 0
        self.errors: List[str] = []

    # -- set-up --------------------------------------------------------- #
    def setup(self) -> Dict:
        self.build()
        return {"buckets": self.warm()}

    def build(self) -> None:
        """The parent graph, the inputs and the service; nothing run."""
        cfg, tr = self.cfg, self.tr
        n = cfg["n_vertices"]
        src, dst = data.synth_edges(cfg)
        self.parent = system.program_graph(
            cfg, n, src, dst, np.ones(src.shape[0], np.float32),
            cfg["name"])
        self.ref = system.reference_module(cfg)
        self.x, self.params = data.make_inputs(
            self.seed, n, cfg["feat_dim"], self.ref.param_leaves(cfg),
            features="host")
        self.model = system.model_ir(
            cfg, self.parent, self.ref.program_leaves(self.params, cfg))
        self.metrics = _Recorder()
        pool = OverlayPool(n_overlays=tr["n_overlays"],
                           geometry=system.geometry(tr["geometry"]),
                           metrics=self.metrics)
        self.svc = SamplingService(
            self.parent, self.x, pool=pool, norm=tr["norm"],
            max_batch=tr["max_batch"], max_wait_us=tr["max_wait_us"],
            max_queue=tr["max_queue"])

    def _request(self, model, pool_index: int, rid: str) -> TargetRequest:
        targets, seed = self.pool[pool_index]
        return TargetRequest(targets=list(targets), model=model,
                             fanouts=tuple(self.tr["fanouts"]),
                             request_id=rid, seed=seed)

    def _requests(self, model, seconds: float) -> None:
        self.pool, order, self.arrivals = traffic.minibatch(
            self.tr, self.cfg["n_vertices"], self.seed, seconds)
        self.requests = [self._request(model, j, f"r{i}")
                         for i, j in enumerate(order)]

    def representatives(self) -> Dict[str, int]:
        """Bucket key -> the first pool index whose request reaches it."""
        reps: Dict[str, int] = {}
        for j in range(len(self.pool)):
            inf, _, _ = self.svc.prepare(
                self._request(self.model, j, f"p{j}"), count=False)
            reps.setdefault(self.svc.pool.cache_key(inf), j)
        return reps

    def warm(self) -> int:
        """Draw the run's traffic, then run one request of each bucket
        it reaches at every batch size up to ``max_batch``: the batched
        executables (one per power of two) and the padding and slicing
        of ragged batches, each an executable of its own size.  Returns
        the number of buckets."""
        self._requests(self.model, self.seconds)
        svc = self.svc
        if "warm" in self.tr:
            idx = [j for j in self.tr["warm"] if j < len(self.pool)]
        else:
            idx = sorted(self.representatives().values())
        reps = {}
        for j in idx:
            inf, _, _ = svc.prepare(self._request(self.model, j, f"p{j}"),
                                    count=False)
            reps.setdefault(svc.pool.cache_key(inf), inf)
        for key, inf in reps.items():
            for size in range(1, svc.loop.max_batch + 1):
                resps = svc.pool.submit_batch(Batch(
                    key=key, requests=[inf] * size,
                    indices=list(range(size)), created_at=0.0,
                    cost=size * request_cost(inf)))
                for r in resps:
                    np.asarray(r.output)
        return len(reps)

    # -- the window ----------------------------------------------------- #
    def _answer(self, resp, queue_wait_s) -> None:
        out = np.asarray(resp.output)
        t = time.perf_counter()
        self.answers[resp.request_id] = (
            t, out, queue_wait_s, resp.batch_size, resp.t_loh, resp.overlay)

    def window(self, seconds: float) -> None:
        svc, loop, span = self.svc, self.svc.loop, self.spans
        self.metrics.sink = self._answer
        t0 = time.perf_counter()
        for req, at in zip(self.requests, self.arrivals):
            due = t0 + at
            with span("idle_wait"):
                now = time.perf_counter()
                while now < due:
                    loop.poll()
                    time.sleep(min(due - now, POLL_S))
                    now = time.perf_counter()
            self.late_s.append(now - due)
            with span("prepare"):
                inf, ego, _ = svc.prepare(req)
                self.prepare_s.append(time.perf_counter() - now)
            rid = req.request_id
            self.due[rid], self.egos[rid] = due, ego
            self.asked[rid] = tuple(req.targets)
            with span("submit"):
                try:
                    loop.submit(inf)
                except QueueFullError:
                    self.refused += 1
            self.backlog.append(
                len(self.due) - self.refused - len(self.answers))
        self.closed_at = time.perf_counter()
        with span("idle_wait"):
            expected = len(self.requests) - self.refused
            while (len(self.answers) < expected and time.perf_counter()
                   < self.closed_at + ANSWER_GRACE_S):
                loop.poll()
                time.sleep(POLL_S)
        try:
            loop.drain()
        except Exception as e:          # a failed batch: its requests
            self.errors.append(repr(e))  # stay unanswered
        self.metrics.sink = None
        self.window_s = self.closed_at - t0

    def latencies_s(self) -> List[float]:
        return [self.answers[r][0] - self.due[r] for r in self.due
                if r in self.answers]

    def end_to_end(self) -> Dict[str, float]:
        from .common import percentile
        lat = self.latencies_s()
        return {"p50_ms": percentile(lat, 50) * 1e3,
                "p95_ms": percentile(lat, 95) * 1e3}

    def counters(self) -> Dict:
        ans = [self.answers[r] for r in self.due if r in self.answers]
        inv_b = [1.0 / a[3] for a in ans]
        return {
            "requests": len(self.due), "answered": len(ans),
            "refused": self.refused, "window_s": self.window_s,
            "prepare_s": self.prepare_s, "late_s": self.late_s,
            "queue_wait_s": [a[2] for a in ans if a[2] is not None],
            # per batched pass: each request of a batch of b counts 1/b
            "batches": sum(inv_b),
            "batch_pass_s": sum(a[4] * w for a, w in zip(ans, inv_b)),
            "errors": self.errors,
            "backlog": self.backlog,
        }

    def release(self) -> None:
        self.svc.shutdown()
        self.svc = None

    # -- the check ------------------------------------------------------ #
    def _sample(self) -> List[str]:
        tr = self.tr["check"]
        done = [r for r in self.due if r in self.answers]
        rng = np.random.default_rng([self.seed, 3])
        pick = list(rng.choice(done, size=min(tr["sample"], len(done)),
                               replace=False)) if done else []
        by_size = sorted(done, key=lambda r: -self.egos[r].graph.n_vertices)
        pick += by_size[: tr["largest"]]
        batched = [r for r in done if self.answers[r][3] > 1]
        if batched:
            pick += list(rng.choice(
                batched, size=min(tr["batched"], len(batched)),
                replace=False))
        return list(dict.fromkeys(pick))

    def check(self, limits: Dict[str, float]):
        cmp = Comparison(limits)
        unanswered = len(self.due) - self.refused - sum(
            r in self.answers for r in self.due)
        cmp.add("unanswered", unanswered)
        rids = self._sample()
        ok = SampleCheck(self.parent, self.tr["fanouts"])
        cmp.add("bad_samples", sum(not ok(self.egos[r], self.asked[r])
                                   for r in rids))
        self.checked = rids
        errs = self.errors_of("program", self.cfg["matmul_precision"])
        cmp.add("rel_err", max(errs) if errs else math.inf)
        failed = self.refused + unanswered
        return cmp, len(self.due), failed

    def errors_of(self, served: str, precision: str) -> List[float]:
        """``rel_err`` per request the check sampled, of what is served
        against the fp32 reference at matmul ``precision``:
        ``"program"`` is the window's answers, ``"control"`` the
        reference in bfloat16 put in the program's place."""
        egos = [self.egos[r] for r in self.checked]
        c = self.tr["check"]
        logits = functools.partial(reference_logits, self.ref, self.params,
                                   self.x, egos, self.tr["norm"],
                                   c["pad_vertices"], c["pad_edges"])
        if served == "program":
            ys = [self.answers[r][1][e.targets]
                  for r, e in zip(self.checked, egos)]
        else:
            ys = logits(jnp.bfloat16, "default")
        return rel_errors(ys, logits(jnp.float32, precision))

    def reading(self, served: str, precision: str) -> float:
        return max(self.errors_of(served, precision))


class SampleCheck:
    """Whether a sampled ego network is a sample of the parent graph
    around the request's targets: its first vertices are the targets in
    the order asked, which are its first hop and the rows answered;
    every sampled edge is an edge of the parent (no more often than the
    parent holds it); and every vertex has exactly ``min(in-degree,
    cap)`` sampled in-edges: the hop's fan-out for the targets and the
    first hop, none for the last hop."""

    def __init__(self, parent, fanouts) -> None:
        self.n = n = parent.n_vertices
        self.keys = np.sort(parent.src.astype(np.int64) * n + parent.dst)
        self.indeg = np.bincount(parent.dst, minlength=n)
        self.fanouts = list(fanouts)

    def __call__(self, ego, targets) -> bool:
        v = ego.vertices.astype(np.int64)
        t = np.asarray(targets, np.int64)
        local = np.arange(t.shape[0])
        if not (np.array_equal(v[: t.shape[0]], t)
                and np.array_equal(ego.targets, local)
                and np.array_equal(ego.hops[0], local)):
            return False
        g = ego.graph
        uk, cnt = np.unique(v[g.src] * self.n + v[g.dst],
                            return_counts=True)
        have = np.searchsorted(self.keys, uk, "right") \
            - np.searchsorted(self.keys, uk)
        if np.any(cnt > have):
            return False
        got = np.bincount(g.dst, minlength=v.shape[0])
        cap = np.zeros(v.shape[0], np.int64)
        for hop, f in zip(ego.hops, self.fanouts):
            cap[hop] = f
        return bool(np.array_equal(got, np.minimum(self.indeg[v], cap)))


def _padded(ego, x, norm: str, pad_v: int, pad_e: int):
    """An ego network's edges, normalized as the mix states (``gcn``:
    self loops, then symmetric weights; ``mean``: 1 / in-degree;
    ``none``: 1), and its features, padded to fixed sizes: pad edges
    join the last (dummy) vertex to itself with weight 0, pad vertices
    have zero features."""
    g = ego.graph
    nv = g.n_vertices
    src, dst = g.src, g.dst
    if norm == "gcn":
        src, dst = data.add_self_loops(nv, src, dst)
    if nv >= pad_v or src.shape[0] > pad_e:
        raise ValueError(f"ego network V={nv} E={src.shape[0]} exceeds "
                         f"the reference padding ({pad_v}, {pad_e})")
    w = data.edge_weights(norm, nv, src, dst)
    e = src.shape[0]
    ps = np.full(pad_e, pad_v - 1, np.int32)
    pd = ps.copy()
    pw = np.zeros(pad_e, np.float32)
    ps[:e], pd[:e], pw[:e] = src, dst, w
    px = np.zeros((pad_v, x.shape[1]), np.float32)
    px[:nv] = x[ego.vertices]
    return px, ps, pd, pw


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8))
def _vmapped_reference(ref, params, px, ps, pd, pw, n, dtype, precision):
    return jax.vmap(lambda x1, s, d, w: ref.forward(
        params, {"src": s, "dst": d, "weight": w}, x1, n=n, dtype=dtype,
        precision=precision))(px, ps, pd, pw)


def reference_logits(ref, params, x, egos, norm, pad_v, pad_e, dtype,
                     precision):
    """The reference's logits for each ego network, ``[T, C]`` each."""
    if not egos:
        return []
    parts = [_padded(e, x, norm, pad_v, pad_e) for e in egos]
    px, ps, pd, pw = (np.stack(a) for a in zip(*parts))
    out = _vmapped_reference(ref, params, px, ps, pd, pw, pad_v,
                             jnp.dtype(dtype), precision)
    out = np.asarray(out.astype(jnp.float32))
    return [out[i, : e.n_targets] for i, e in enumerate(egos)]


def rel_errors(served, refs) -> List[float]:
    """``max |served - ref| / max |ref|`` per request."""
    out = []
    for y, r in zip(served, refs):
        if y.shape != r.shape or not np.all(np.isfinite(y)):
            out.append(math.inf)
            continue
        out.append(float(np.max(np.abs(y - r)))
                   / max(float(np.max(np.abs(r))), 1e-30))
    return out


Driver = Minibatch
