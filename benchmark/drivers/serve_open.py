"""Traffic driver `serve_open`: requests at a fixed rate, an open loop,
against the serve front end in process.

`serve.api.build_server` runs at the `wavetpu serve` defaults the mix
names (bucket sizes, `max_wait_ms`).  Arrivals are Poisson at the mix's
`rate_per_s`: the arrival times are drawn from the mix's
`arrivals_seed`, so every run offers the same schedule, and the run's
seed draws each request's initial phase (a runtime lane input of the
batched program: no compile).  A seed that also ordered the gaps made
p50 swing by a third between seeds (my chip runs, PR 22).  Each request is sent when
it is due, whether or not earlier ones have come back, only inside the
window; those in flight when it closes are waited for.  Latency is
timed from when a request was due, so a late generator counts against
the system, and how late it ran is printed.

- `serve_p95_ms`: the nearest-rank 95th percentile of the latency of
  every request sent in the window; a failed request counts as
  infinitely slow.  (The median is printed too; it is not an end-to-end
  metric: it settles in one of two batching rhythms, ~600 or ~800 ms,
  for a whole run, my chip runs, PR 22.)

After the window the server is drained and stopped; then a sample of
the answered requests, drawn from the seed, is solved again by the
plain reference at each request's phase, and each answer's per-layer
error rows are compared with the reference's.
"""

from __future__ import annotations

import concurrent.futures
import json
import math
import random
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference

TWO_PI = 2.0 * math.pi


def parse_server_timing(header: Optional[str]) -> dict:
    """`queue;dur=1.2, execute;dur=45` -> {"queue": 1.2, ...} in ms
    (copied from wavetpu/loadgen/runner.py, in milliseconds)."""
    out = {}
    for part in (header or "").split(","):
        name, _, params = part.strip().partition(";")
        for p in params.split(";"):
            k, _, v = p.strip().partition("=")
            if k == "dur":
                try:
                    out[name.strip()] = float(v)
                except ValueError:
                    pass
    return out


@dataclass
class Answer:
    phase: float
    sent: float
    done: float
    status: int
    body: dict
    timing: dict

    @property
    def ok(self) -> bool:
        return self.status == 200


@dataclass
class State:
    httpd: object
    server: object
    thread: threading.Thread
    base: str
    body: dict
    stopped: bool = False


@dataclass
class Outcome:
    attempted: int
    failed: int
    t0: float  # the window's start: set-up ends here
    compiles_in_window: int
    metrics: dict
    answers: List[Answer] = field(default_factory=list)


def post(base: str, body: dict, timeout: float = 600.0):
    req = urllib.request.Request(
        base + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), r.headers.get("Server-Timing")
    except urllib.error.HTTPError as e:
        return e.code, {}, None
    except (urllib.error.URLError, OSError, ValueError):
        return 0, {}, None


def _body(ctx) -> dict:
    a = ctx.problem_args
    body = {"N": a["N"], "timesteps": a["timesteps"],
            "scheme": ctx.cell.traffic["scheme"]}
    for k in ("Lx", "Ly", "Lz", "T"):
        if k in a:
            body[k] = a[k]
    return body


def _wave(base, body, phases):
    """Send one request per phase at once; all must come back 200."""
    out = [None] * len(phases)

    def one(i):
        out[i] = post(base, dict(body, phase=phases[i]))[0]

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(phases))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    if any(code != 200 for code in out):
        raise RuntimeError(f"warm-up requests answered {out}")


def setup(ctx) -> State:
    """Start the server and warm every bucket: the engine compiles (or
    loads) each bucket's program, then one wave of requests per bucket
    runs each of them once."""
    from wavetpu.core.problem import Problem
    from wavetpu.serve import api

    t = ctx.cell.traffic
    buckets = tuple(int(b) for b in t["bucket_sizes"])
    httpd, server = api.build_server(
        port=0, bucket_sizes=buckets, max_wait=t["max_wait_ms"] / 1e3,
        interpret=True if ctx.rehearse else None,
    )
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    state = State(httpd, server, thread,
                  f"http://127.0.0.1:{httpd.server_address[1]}", _body(ctx))
    try:
        with jax.profiler.TraceAnnotation("bench.setup"):
            # Fixed warm-up phases, none of them a window's draw.
            _wave(state.base, state.body, [1.0])
            problem = Problem(**ctx.problem_args)
            code, ans, _ = post(state.base, dict(state.body, phase=1.5))
            path = ans["batch"]["path"]
            server.engine.warmup(problem, scheme=t["scheme"], path=path,
                                 batches=list(buckets))
            for b in buckets:
                _wave(state.base, state.body,
                      [0.1 + 0.7 * i / b for i in range(b)])
    except BaseException:
        release(ctx, state)
        raise
    return state


def gaps(traffic, seconds: float) -> list:
    """The mix's inter-arrival gaps for a window of `seconds`: the same
    for every run seed."""
    rng = random.Random(int(traffic["arrivals_seed"]))
    out, t = [], 0.0
    while True:
        g = rng.expovariate(float(traffic["rate_per_s"]))
        if t + g >= seconds:
            return out
        t += g
        out.append(g)


def percentile(sorted_values, q: float) -> float:
    if not sorted_values:
        return math.inf
    return sorted_values[max(0, math.ceil(q * len(sorted_values)) - 1)]


def window(ctx, state: State, seed: int, seconds: float,
           rate: Optional[float] = None) -> Outcome:
    traffic = dict(ctx.cell.traffic)
    if rate is not None:
        traffic["rate_per_s"] = rate
    order = gaps(traffic, seconds)
    rng = random.Random(f"{seed}/phases")
    phases = [rng.uniform(0.0, TWO_PI) for _ in order]
    answers: List[Answer] = []
    late = []

    def send(phase: float, due: float) -> None:
        with jax.profiler.TraceAnnotation("bench.request"):
            code, body, timing = post(state.base, dict(state.body, phase=phase))
        answers.append(Answer(phase, due, time.perf_counter(), code, body,
                              parse_server_timing(timing)))

    pool = concurrent.futures.ThreadPoolExecutor(max_workers=64)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0, c0 = time.perf_counter(), ctx.compiles.compiled
            due = t0
            futures = []
            for gap, phase in zip(order, phases):
                due += gap
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late.append(time.perf_counter() - due)
                futures.append(pool.submit(send, phase, due))
            for f in futures:
                f.result()
            t1 = max([a.done for a in answers] + [t0 + seconds])
    finally:
        pool.shutdown(wait=True)
    flat = sorted(answers, key=lambda a: a.sent)
    ok = [a for a in flat if a.ok]
    lat = sorted((a.done - a.sent) * 1e3 if a.ok else math.inf for a in flat)
    late.sort()
    print(f"generator late: p95 {percentile(late, 0.95) * 1e3:.3f} ms, max "
          f"{late[-1] * 1e3 if late else 0.0:.3f} ms over {len(late)} sends; "
          f"latency p50 {percentile(lat, 0.50):.1f} ms", file=sys.stderr)
    return Outcome(
        attempted=len(flat), failed=len(flat) - len(ok), t0=t0,
        compiles_in_window=ctx.compiles.compiled - c0,
        metrics={"serve_p50_ms": percentile(lat, 0.50),
                 "serve_p95_ms": percentile(lat, 0.95),
                 "completed_per_s": len(ok) / (t1 - t0)},
        answers=flat,
    )


def release(ctx, state: State) -> None:
    """Drain and stop the server; its programs and buffers go with it."""
    if state.stopped:
        return
    state.stopped = True
    state.server.begin_drain(state.httpd)
    state.thread.join(timeout=120)
    state.server.batcher.close(timeout=120.0, drain=True)
    state.httpd.server_close()
    if state.thread.is_alive():
        raise RuntimeError("the server thread did not stop")


def sample(ctx, out: Outcome, seed: int) -> List[Answer]:
    ok = [a for a in out.answers if a.ok]
    n = min(int(ctx.cell.traffic["sample"]), len(ok))
    return random.Random(f"{seed}/sample").sample(ok, n)


def _rows(answer: Answer) -> np.ndarray:
    return np.asarray(answer.body["report"]["abs_errors"], np.float64)


def compare_rows(got: List[np.ndarray], want: List[np.ndarray]) -> float:
    gaps = [float(np.max(np.abs(g - w))) if g.shape == w.shape else math.inf
            for g, w in zip(got, want)]
    return max(gaps) if gaps else math.inf


def reference_rows(ctx, phases, dtype=jnp.float32) -> List[np.ndarray]:
    p = reference.RefProblem.of(ctx.problem_args)
    scheme = ctx.cell.traffic["scheme"]
    return [np.asarray(reference.solve(p, scheme, ph, dtype,
                                       ctx.devices[0]).abs_errors, np.float64)
            for ph in phases]


def check(ctx, out: Outcome) -> dict:
    """The widest gap between a sampled answer's per-layer error rows and
    the reference's; an answer with fewer rows (a solve stopped short)
    reads as infinite."""
    picked = sample(ctx, out, ctx.seed)
    want = reference_rows(ctx, [a.phase for a in picked])
    return {"err_rows_gap": compare_rows([_rows(a) for a in picked], want)}


def control(ctx, out: Outcome, dtype=jnp.bfloat16) -> dict:
    """The reference in a lower precision in the program's place, on the
    same sampled requests."""
    phases = [a.phase for a in sample(ctx, out, ctx.seed)]
    return {"err_rows_gap": compare_rows(reference_rows(ctx, phases, dtype),
                                         reference_rows(ctx, phases))}
