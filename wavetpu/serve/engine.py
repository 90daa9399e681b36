"""Compiled-program cache + batched execution for the serve layer.

One compiled batched program serves every request that matches its
identity: `ProgramKey` = the full problem geometry (N, Lx/y/z, T,
timesteps), scheme, kernel path, k, dtype, whether lanes carry c2 fields,
whether errors are computed, and the BATCH-SIZE BUCKET.  Requests are
padded up to the nearest bucket with `padding_lane()`s that stop where
the batch's longest request does (so a batch of full-length requests
marches without the per-step lane mask), which provably leave real
lanes bitwise unchanged - tests/test_ensemble.py - so a handful of
buckets (default 1/2/4/8) covers every occupancy without per-batch
recompilation.

The cache is a plain LRU: `max_programs` compiled executables, eviction
of the least-recently-used on overflow, hits/misses/evictions counted for
/metrics.  `warmup()` AOT-compiles ahead of traffic so the first request
of a bucket does not pay the XLA compile.

With `--program-cache-dir` set, a DISK tier (serve/progcache.py) sits
between the memory LRU and a fresh compile: memory miss -> try
adopting a persisted serialized executable (counted `disk_hit`, the
saved seconds credited in the registry and the compile ledger as
`source: disk`) -> else a fresh XLA compile (counted `miss`, recorded
`source: fresh`, and persisted for the next process).  `miss` therefore
still counts exactly the fresh compiles - the loadgen gate's
"second replica compiled nothing" assertion reads it unchanged.  Disk
problems (corrupt entries, stale fingerprints, full disk) are counted
misses that fall through to a fresh compile - never a request failure,
never a circuit-breaker feed.

Every batch passes the per-lane numerical-health watchdog (the same
guarded-amax reduction as run/health.py): a poisoned lane - NaN, Inf, or
amplitude blowup from e.g. a Courant-unstable request - yields a per-lane
error string while its batchmates' results stand.  One bad request can
not sink the batch.

Since the serving-resilience round the engine also carries a per-
ProgramKey CIRCUIT BREAKER (serve/resilience.py): K consecutive
compile/execute failures quarantine the key (batch bucket excluded - a
tier is one breaker however it batches), so a poisoned tier sheds fast
`QuarantinedError`s (HTTP 503 + Retry-After) instead of re-paying the
failing compile on every request and stalling the single scheduler
worker for everyone else.  After the cooldown one request probes
half-open; success closes the breaker.  `run/faults.py`'s serve plan
injects `compile-fail` (before the build) and `execute-nan` (after the
solve, proving the watchdog catches it) at this layer.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

from wavetpu.core.problem import Problem
from wavetpu.ensemble import batched as ensemble
from wavetpu.ensemble import sharded as ens_sharded
from wavetpu.obs import accuracy
from wavetpu.obs import ledger as compile_ledger
from wavetpu.obs import perf, tracing
from wavetpu.obs.registry import MetricsRegistry
from wavetpu.progkey import ProgramKey
from wavetpu.run import faults, health
from wavetpu.serve.resilience import CircuitBreaker, QuarantinedError


# ProgramKey moved to `wavetpu.progkey` (the fleet router derives the
# same identity without importing jax); imported above and still
# exported from this module - `from wavetpu.serve.engine import
# ProgramKey` keeps working everywhere.


class ServeEngine:
    """LRU-cached batched programs + watchdogged batch execution.

    Thread-safe for the single-scheduler-worker design (a lock guards the
    cache anyway so warmup from another thread is safe).  `interpret`
    defaults to auto (interpret-mode pallas off-TPU, native on TPU).
    """

    def __init__(
        self,
        bucket_sizes: Sequence[int] = (1, 2, 4, 8),
        max_programs: int = 8,
        compute_errors: bool = True,
        interpret: Optional[bool] = None,
        watchdog: bool = True,
        max_amp: Optional[float] = None,
        block_x: Optional[int] = None,
        registry: Optional[MetricsRegistry] = None,
        breaker_threshold: Optional[int] = 3,
        breaker_cooldown_s: float = 30.0,
        fault_plan: Optional[faults.ServeFaultPlan] = None,
        program_cache_dir: Optional[str] = None,
        program_cache_max_bytes: Optional[int] = None,
    ):
        if not bucket_sizes or any(b < 1 for b in bucket_sizes):
            raise ValueError(f"bad bucket_sizes {bucket_sizes}")
        if max_programs < 1:
            raise ValueError(f"max_programs must be >= 1, got {max_programs}")
        self.bucket_sizes = tuple(sorted(set(int(b) for b in bucket_sizes)))
        self.max_programs = max_programs
        self.compute_errors = compute_errors
        self.interpret = interpret
        self.watchdog = watchdog
        self.max_amp = max_amp
        self.block_x = block_x
        # `build_server` passes the server's registry so cache and
        # compile/execute metrics land in the same /metrics exposition
        # as the scheduler's; a standalone engine gets its own.
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._c_cache = self.registry.counter(
            "wavetpu_program_cache_events_total",
            "compiled-program cache events", ("event",),
        )
        self._h_compile = self.registry.histogram(
            "wavetpu_serve_compile_seconds",
            "batched-program build+compile time on cache miss",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                     120.0, 300.0),
        )
        self._h_execute = self.registry.histogram(
            "wavetpu_serve_execute_seconds",
            "batch solve wall time (warm=false includes this key's "
            "first compile in the same request)", ("warm",),
            buckets=(0.005, 0.025, 0.1, 0.25, 1.0, 2.5, 5.0, 10.0,
                     30.0, 60.0, 120.0, 300.0),
        )
        self._lock = threading.Lock()
        self._programs: "OrderedDict[ProgramKey, ensemble.EnsembleSolver]" = (
            OrderedDict()
        )
        # Per-ProgramKey circuit breaker (None = disabled): K
        # consecutive compile/execute failures quarantine the key
        # bucket-wide; state rides both /metrics views.
        self.breaker: Optional[CircuitBreaker] = (
            None if breaker_threshold is None else CircuitBreaker(
                threshold=breaker_threshold,
                cooldown_s=breaker_cooldown_s, registry=self.registry,
            )
        )
        # Chaos harness: the serve-path injection plan (shared server-
        # wide by build_server; a standalone engine reads WAVETPU_FAULT
        # itself).  None on the happy path - every seam is a None check.
        self.fault_plan = (
            fault_plan if fault_plan is not None
            else faults.serve_plan_from_env()
        )
        if self.fault_plan is not None:
            self.fault_plan.bind_registry(self.registry)
        # Persistent disk tier (serve/progcache.py): None without
        # --program-cache-dir - every use is a None check, so the
        # historical cacheless path is untouched.  A bad directory
        # raises HERE (operator config error at startup), not
        # per-request.
        self.progcache = None
        if program_cache_dir:
            from wavetpu.serve import progcache as progcache_mod

            self.progcache = progcache_mod.ProgramCache(
                program_cache_dir,
                max_bytes=program_cache_max_bytes,
                registry=self.registry, fault_plan=self.fault_plan,
            )

    # Cache hit/miss/eviction counts live in the registry counter - the
    # single source of truth for the JSON and Prometheus /metrics views;
    # these properties keep the historical attribute API readable.

    @property
    def hits(self) -> int:
        return int(self._c_cache.value(event="hit"))

    @property
    def misses(self) -> int:
        return int(self._c_cache.value(event="miss"))

    @property
    def evictions(self) -> int:
        return int(self._c_cache.value(event="eviction"))

    @property
    def disk_hits(self) -> int:
        return int(self._c_cache.value(event="disk_hit"))

    @property
    def max_batch(self) -> int:
        return self.bucket_sizes[-1]

    def bucket_for(self, n_lanes: int) -> int:
        """Smallest bucket >= n_lanes (the scheduler never exceeds
        max_batch, so there is always one)."""
        for b in self.bucket_sizes:
            if b >= n_lanes:
                return b
        raise ValueError(
            f"{n_lanes} lanes exceed the largest bucket "
            f"{self.bucket_sizes[-1]}"
        )

    def _dtype(self, dtype_name: str):
        import jax.numpy as jnp

        table = {"f32": jnp.float32, "f64": jnp.float64,
                 "bf16": jnp.bfloat16}
        if dtype_name not in table:
            raise ValueError(
                f"dtype must be one of {sorted(table)}, got {dtype_name!r}"
            )
        return table[dtype_name]

    def program(
        self, problem: Problem, scheme: str, path: str, k: int,
        dtype_name: str, with_field: bool, batch: int,
        mesh: Optional[Tuple[int, int, int]] = None,
    ):
        """The cached compiled program for this key, building (and
        compiling) on miss; a build or compile error raises.  `mesh`
        selects the sharded x batched composition (ensemble/sharded.py);
        a (mesh, bucket) pair is its own cached executable."""
        return self._program(
            problem, scheme, path, k, dtype_name, with_field, batch, mesh
        )[0]

    def _program(
        self, problem: Problem, scheme: str, path: str, k: int,
        dtype_name: str, with_field: bool, batch: int,
        mesh: Optional[Tuple[int, int, int]] = None,
    ):
        """`program()` plus THIS call's program-source attribution -
        (prog, source, compile_seconds) with source one of "memory"
        (LRU hit), "disk" (persistent-cache adoption) or "fresh" (paid
        the XLA compile).  Per-call state, not a counter diff - diffing
        the shared `misses` counter would race with a concurrent warmup
        taking a miss on a different key.  `compile_seconds` is 0.0 on
        a memory hit, the deserialize wall on a disk hit, and the
        measured build+compile wall on a fresh compile - the `compile`
        component of the response's Server-Timing header."""
        compute_errors = self.compute_errors and not with_field
        if mesh is not None and scheme != "standard":
            # Refuse loudly: silently serving a compensated request with
            # the standard scheme would be a wrong-result bug.  (The
            # HTTP layer 400s this at parse; this guards direct
            # ServeEngine users.)
            raise ValueError(
                "sharded x batched serves the standard scheme only; "
                f"got scheme={scheme!r} with mesh {tuple(mesh)}"
            )
        key = ProgramKey.for_batch(
            problem, scheme, path, k, dtype_name, with_field,
            compute_errors, batch, mesh,
        )
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                self._c_cache.inc(event="hit")
                return prog, "memory", 0.0

        def _build():
            if mesh is not None:
                return ens_sharded.ShardedEnsembleSolver(
                    problem, batch, mesh, dtype=self._dtype(dtype_name),
                    kernel=path, compute_errors=compute_errors,
                    interpret=self.interpret,
                )
            return ensemble.EnsembleSolver(
                problem, batch, dtype=self._dtype(dtype_name),
                path=path, k=k, compute_errors=compute_errors,
                interpret=self.interpret, block_x=self.block_x,
                with_field=with_field, scheme=scheme,
            )

        # Disk tier: adopt a persisted serialized executable before
        # paying a fresh compile.  A valid entry counts `disk_hit` ONLY
        # (not `miss` - `miss` stays exactly the fresh-compile count);
        # ANY disk problem falls through to the fresh path as a normal
        # miss.  The ledger gets a `source: disk` line whose compile_s
        # is the deserialize wall and whose fresh_compile_s is the
        # compile the entry replaced - the measured-savings record.
        key_dict = None
        if self.progcache is not None and self.progcache.usable:
            key_dict = compile_ledger.key_from_program_key(key)
            entry = self.progcache.load(key_dict)
            if entry is not None:
                payload, header = entry
                t0 = time.perf_counter()
                try:
                    prog = _build()
                    prog.adopt_executable(payload)
                except Exception:
                    # A checksum-valid entry whose payload this runtime
                    # refuses (the fingerprint net has a hole): counted,
                    # then the fresh path below pays the compile.
                    self.progcache.count("corrupt")
                    prog = None
                if prog is not None:
                    load_s = time.perf_counter() - t0
                    self._c_cache.inc(event="disk_hit")
                    fresh_s = header.get("compile_s")
                    if isinstance(fresh_s, (int, float)):
                        self.progcache.credit_saved(fresh_s, load_s)
                    compile_ledger.record_compile(
                        key_dict, load_s, source="disk",
                        fresh_compile_s=(
                            fresh_s
                            if isinstance(fresh_s, (int, float))
                            else None
                        ),
                    )
                    self._cache_insert(key, prog)
                    return prog, "disk", load_s
        self._c_cache.inc(event="miss")
        # Chaos seam: an injected compile failure lands exactly where a
        # real Mosaic/XLA build error would - after the miss is counted,
        # before any build work.
        if self.fault_plan is not None and self.fault_plan.fire(
            "compile-fail", n=problem.N, timesteps=problem.timesteps,
            scheme=scheme, path=path, k=key.k, dtype=dtype_name,
        ):
            raise faults.InjectedFault(
                f"injected compile failure ({scheme}:{path} "
                f"N={problem.N}/{problem.timesteps})"
            )
        # Build + compile OUTSIDE the lock (XLA compiles can take
        # seconds; warmup from another thread must not serialize on it).
        t0 = time.perf_counter()
        with tracing.span(
            "serve.compile", scheme=scheme, path=path, batch=batch,
            n=problem.N, mesh=None if mesh is None else list(mesh),
        ):
            prog = _build()
            if (
                self.progcache is not None
                and self.progcache.xla_hits is not None
            ):
                # XLA-fallback mode: the persistent compilation cache
                # serves transparently inside compile(); sample its hit
                # counter around the compile so the ledger still says
                # where the time (didn't) go.
                pre_hits = self.progcache.xla_hits.hits
            else:
                pre_hits = None
            prog.compile()
        compile_seconds = time.perf_counter() - t0
        self._h_compile.observe(compile_seconds)
        # Compile-cost ledger (obs/ledger.py): one appended line per
        # compile, keyed by the full ProgramKey, surviving process
        # restarts - the raw material for `wavetpu ledger-report`'s
        # cross-restart accounting and warmup manifest.  A None-check
        # no-op (zero file I/O) when no --telemetry-dir configured it.
        source = "fresh"
        xla_served = (
            pre_hits is not None
            and self.progcache.xla_hits.hits > pre_hits
        )
        if xla_served:
            # The XLA persistent cache served this compile.  In
            # fallback mode that IS the disk tier, so the ledger says
            # so; in AOT mode the request still paid a (fast) compile
            # call, no program was adopted, and the label stays fresh -
            # `warm: disk` must always mean an adoption.
            self.progcache.count("xla_hit")
            if self.progcache.xla_fallback:
                source = "disk"
        compile_ledger.record_compile(
            key_dict if key_dict is not None
            else compile_ledger.key_from_program_key(key),
            compile_seconds, source=source,
        )
        # Persist for the next process (AOT mode only; guarded - a full
        # disk must never fail the request that just compiled).  Never
        # from an xla-served compile: serializing a cache-served
        # executable yields a payload that cannot deserialize.
        if (
            not xla_served
            and self.progcache is not None and self.progcache.usable
        ):
            try:
                payload = prog.executable_payload()
                if payload is not None:
                    self.progcache.put(
                        key_dict, payload, compile_seconds
                    )
            except Exception:
                self.progcache.count("store_error")
        self._cache_insert(key, prog)
        return prog, "fresh", compile_seconds

    def _cache_insert(self, key: ProgramKey, prog) -> None:
        with self._lock:
            self._programs[key] = prog
            self._programs.move_to_end(key)
            while len(self._programs) > self.max_programs:
                self._programs.popitem(last=False)
                self._c_cache.inc(event="eviction")

    def warmup(
        self, problem: Problem, scheme: str = "standard",
        path: str = "roll", k: int = 4, dtype_name: str = "f32",
        with_field: bool = False, batches: Optional[Sequence[int]] = None,
        mesh: Optional[Tuple[int, int, int]] = None,
    ) -> List[int]:
        """AOT-compile the key for each requested bucket (default: all);
        returns the bucket sizes warmed.  `mesh` warms the sharded x
        batched (mesh, bucket) programs."""
        warmed = []
        for b in (self.bucket_sizes if batches is None else batches):
            self.program(
                problem, scheme, path, k, dtype_name, with_field, b, mesh
            )
            warmed.append(b)
        return warmed

    # ---- chunked long solves (serve/preempt.py) ----

    @staticmethod
    def chunk_program_key(problem: Problem, scheme: str, path: str,
                          k: int, dtype_name: str, compute_errors: bool,
                          chunk_len: int) -> ProgramKey:
        """The chunk-program identity: the full-march ProgramKey at
        batch=1 with the chunk geometry folded into the path string
        (`roll@chunk64`).  `timesteps` stays the TOTAL march length -
        the chunk program's error oracle and tau depend on it - and the
        suffix keeps chunked and monolithic executables from colliding
        in the LRU, the ledger, and the progcache.  Router affinity
        tables carry the suffixed path transparently (progkey's
        warm-key plumbing treats path as an opaque string)."""
        base = ProgramKey.for_batch(
            problem, scheme, path, k, dtype_name, False,
            compute_errors, 1, None,
        )
        # for_batch normalizes k to 1 off the kfused path, so the
        # suffix rides in AFTER derivation.
        return base._replace(path=f"{path}@chunk{chunk_len}")

    def chunk_runner(
        self, problem: Problem, scheme: str, path: str, k: int,
        dtype_name: str, chunk_steps: int,
    ):
        """The cached ChunkRunner (bootstrap + fixed-length chunk
        programs) for a long solve's tier - (runner, source,
        compile_seconds) with the same memory -> disk -> fresh
        three-tier discipline and attribution as `_program`.  Lives in
        the same LRU as the ensemble programs (one `max_programs`
        budget, one hit/miss/eviction account, one warm-keys view).
        The circuit breaker is NOT consulted here: the chunked path
        has its own failure handling (per-chunk watchdog 422s, crash
        re-enqueue, checkpoint-and-preempt), none of which may
        quarantine the tier."""
        from wavetpu.run import supervisor
        from wavetpu.serve import preempt

        fuse = int(k) if path == "kfused" else 1
        chunk_len = supervisor.chunk_length(int(chunk_steps), fuse)
        compute_errors = self.compute_errors
        key = self.chunk_program_key(
            problem, scheme, path, k, dtype_name, compute_errors,
            chunk_len,
        )
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                self._c_cache.inc(event="hit")
                return prog, "memory", 0.0

        def _build():
            return preempt.ChunkRunner(
                problem, scheme, path, fuse,
                self._dtype(dtype_name), dtype_name, compute_errors,
                chunk_steps=chunk_len, interpret=self.interpret,
                block_x=self.block_x,
            )

        key_dict = None
        if self.progcache is not None and self.progcache.usable:
            key_dict = compile_ledger.key_from_program_key(key)
            entry = self.progcache.load(key_dict)
            if entry is not None:
                payload, header = entry
                t0 = time.perf_counter()
                try:
                    prog = _build()
                    prog.adopt_executable(payload)
                except Exception:
                    self.progcache.count("corrupt")
                    prog = None
                if prog is not None:
                    load_s = time.perf_counter() - t0
                    self._c_cache.inc(event="disk_hit")
                    fresh_s = header.get("compile_s")
                    if isinstance(fresh_s, (int, float)):
                        self.progcache.credit_saved(fresh_s, load_s)
                    compile_ledger.record_compile(
                        key_dict, load_s, source="disk",
                        fresh_compile_s=(
                            fresh_s
                            if isinstance(fresh_s, (int, float))
                            else None
                        ),
                    )
                    self._cache_insert(key, prog)
                    return prog, "disk", load_s
        self._c_cache.inc(event="miss")
        # Same chaos seam placement as `_program`: after the miss is
        # counted, before any build work.
        if self.fault_plan is not None and self.fault_plan.fire(
            "compile-fail", n=problem.N, timesteps=problem.timesteps,
            scheme=scheme, path=path, k=key.k, dtype=dtype_name,
        ):
            raise faults.InjectedFault(
                f"injected compile failure ({scheme}:{path}@chunk"
                f"{chunk_len} N={problem.N}/{problem.timesteps})"
            )
        t0 = time.perf_counter()
        with tracing.span(
            "serve.compile", scheme=scheme,
            path=f"{path}@chunk{chunk_len}", batch=1, n=problem.N,
            mesh=None,
        ):
            prog = _build()
            prog.prime()
        compile_seconds = time.perf_counter() - t0
        self._h_compile.observe(compile_seconds)
        compile_ledger.record_compile(
            key_dict if key_dict is not None
            else compile_ledger.key_from_program_key(key),
            compile_seconds, source="fresh",
        )
        if self.progcache is not None and self.progcache.usable:
            try:
                payload = prog.executable_payload()
                if payload is not None:
                    self.progcache.put(key_dict, payload,
                                       compile_seconds)
            except Exception:
                self.progcache.count("store_error")
        self._cache_insert(key, prog)
        return prog, "fresh", compile_seconds

    def breaker_key(self, problem: Problem, scheme: str, path: str,
                    k: int, dtype_name: str, with_field: bool,
                    mesh: Optional[Tuple[int, int, int]] = None
                    ) -> ProgramKey:
        """The circuit-breaker identity: the ProgramKey with batch=0, so
        every bucket of a tier shares one breaker (a poisoned compile
        poisons the tier, not one bucket of it)."""
        return ProgramKey.for_batch(
            problem, scheme, path, k, dtype_name, with_field,
            self.compute_errors and not with_field, 0, mesh,
        )

    def breaker_stats(self) -> dict:
        """The JSON /metrics `breaker` block."""
        if self.breaker is None:
            return {"enabled": False}
        return {"enabled": True, **self.breaker.snapshot()}

    def cache_stats(self) -> dict:
        with self._lock:
            return {
                "programs": len(self._programs),
                "max_programs": self.max_programs,
                "hits": self.hits,
                "misses": self.misses,
                "disk_hits": self.disk_hits,
                "evictions": self.evictions,
                "keys": [list(k) for k in self._programs],
                # ProgramKey dicts the fleet router's affinity table
                # bootstraps from on a cold poll: programs compiled in
                # THIS process (memory LRU) plus .wtpc entries this
                # replica could adopt without a fresh compile (disk,
                # own-fingerprint only).
                "warm_keys": {
                    "memory": [
                        compile_ledger.key_from_program_key(k)
                        for k in self._programs
                    ],
                    "disk": (
                        self.progcache.entry_keys()
                        if self.progcache is not None else []
                    ),
                },
                # Disk tier (serve/progcache.py): entry count/bytes,
                # event counts, and the once-per-process AOT
                # serialization probe verdict.
                "progcache": (
                    self.progcache.stats()
                    if self.progcache is not None
                    else {"enabled": False}
                ),
            }

    # ---- execution ----

    def lane_health(
        self, result: ensemble.EnsembleResult
    ) -> List[Optional[str]]:
        """Per-lane watchdog verdicts: None = healthy, else the error
        string for that lane's response.  The guarded-amax reduction maps
        NaN/Inf to +inf (run/health.py), so a poisoned lane trips without
        touching its batchmates."""
        if not self.watchdog:
            return [None] * len(result.results)
        # The context manager (not begin/end) so a raising reduction
        # still closes the span: a leaked span id would become every
        # later batch span's parent on this worker thread.
        with tracing.span(
            "serve.watchdog", lanes=len(result.results)
        ) as sp:
            # One fused pass per state array over the whole batch (B
            # scalars to host), not B separate reductions, on the raw
            # batched outputs (no copy).
            amaxes = [
                health.guarded_amax_per_lane(batch)[: len(result.results)]
                for batch in (result.u_prev_batch, result.u_cur_batch)
            ]
            out = []
            for amax in map(max, zip(*amaxes)):
                amax = float(amax)
                if health.healthy(amax, self.max_amp):
                    out.append(None)
                else:
                    bound = (
                        health.DEFAULT_AMP_BOUND
                        if self.max_amp is None else self.max_amp
                    )
                    out.append(
                        f"numerical-health trip: guarded amax {amax:g} "
                        f"exceeds bound {bound:g} (NaN/Inf count as inf)"
                    )
            sp["tripped"] = sum(1 for o in out if o is not None)
        return out

    def solve(
        self, problem: Problem, lanes: Sequence[ensemble.LaneSpec],
        scheme: str = "standard", path: str = "roll", k: int = 4,
        dtype_name: str = "f32",
        mesh: Optional[Tuple[int, int, int]] = None,
        timing: Optional[dict] = None,
        feed_breaker: bool = True,
    ) -> Tuple[ensemble.EnsembleResult, List[Optional[str]]]:
        """Pad to the bucket, run the cached program, watchdog each
        lane; returns (EnsembleResult, per-lane health).  `mesh` routes
        the batch through the sharded x batched composition.  `timing`,
        when a dict is passed, is filled in place with
        `compile_seconds` (this call's cache-miss compile, 0.0 warm)
        and `warm` ("true"/"false"/"disk") - the scheduler threads it
        into each response's Server-Timing header without changing this
        method's return contract.
        `feed_breaker=False` (a batch of only shadow-solve lanes,
        serve/shadow.py) skips the circuit breaker entirely - neither
        admitted against an open key nor recorded on failure, so the
        off-hot-path accuracy sampler can never quarantine a program
        production traffic depends on."""
        lanes = list(lanes)
        with_field = any(lane.c2tau2_field is not None for lane in lanes)
        compute_errors = self.compute_errors and not with_field
        bucket = self.bucket_for(len(lanes))
        # Circuit breaker: an open key sheds HERE (fast QuarantinedError
        # the HTTP layer maps to 503 + Retry-After) before any compile
        # or device work; everything from program lookup through the
        # batched execute counts as one admit/record cycle.  Per-lane
        # watchdog trips are CLIENT errors (a Courant-unstable request)
        # and never feed the breaker.
        bkey = None
        if self.breaker is not None and feed_breaker:
            bkey = self.breaker_key(
                problem, scheme, path, k, dtype_name, with_field, mesh
            )
            self.breaker.admit(bkey)
        try:
            # Warm-vs-cold attribution: a solve whose program lookup had
            # to compile is this key's first-request latency, not its
            # steady state; the histogram label keeps the two
            # populations apart.
            prog, source, compile_seconds = self._program(
                problem, scheme, path, k, dtype_name, with_field, bucket,
                mesh
            )
            warm = source == "memory"
            # "disk" is its own label: a persistent-cache adoption pays
            # deserialize (ms) where a cold compile pays XLA (s) - the
            # two populations must not share a histogram bucket.
            warm_label = (
                "true" if warm
                else "disk" if source == "disk" else "false"
            )
            if timing is not None:
                timing["compile_seconds"] = compile_seconds
                timing["warm"] = warm_label
            with tracing.span(
                "serve.execute", scheme=scheme, path=path,
                occupancy=len(lanes), bucket=bucket, warm=warm,
            ) as sp:
                if mesh is not None:
                    result = ens_sharded.solve_ensemble_sharded(
                        problem, lanes, mesh_shape=mesh,
                        dtype=self._dtype(dtype_name), kernel=path,
                        compute_errors=compute_errors,
                        interpret=self.interpret, pad_to=bucket,
                        solver=prog,
                    )
                else:
                    result = ensemble.solve_ensemble(
                        problem, lanes, dtype=self._dtype(dtype_name),
                        scheme=scheme, path=path, k=k,
                        compute_errors=compute_errors,
                        interpret=self.interpret, block_x=self.block_x,
                        pad_to=bucket, solver=prog,
                    )
                # Roofline attribution for the batch program: the
                # vmapped march moves batch_size x the per-lane traffic
                # (padding lanes stream bytes too), so the program-level
                # Gcell/s - not just the real-lane aggregate - is what
                # sits on the roofline.  Same attrs as the solo solve
                # gauges, stamped on this serve.execute span and the
                # server registry.
                # Guarded: an X-ray bug must never fail the batch (an
                # exception here would even feed the circuit breaker).
                try:
                    steps = max(
                        (r.steps_computed or problem.timesteps
                         for r in result.results),
                        default=problem.timesteps,
                    )
                    prog_gcells = (
                        problem.cells_per_step * result.batch_size
                        * steps / result.solve_seconds / 1e9
                        if result.solve_seconds else 0.0
                    )
                    rf = perf.record_roofline(
                        self.registry, result.path, perf.solve_perf(
                            prog_gcells, result.path, scheme=scheme,
                            k=k, n=problem.N,
                            itemsize=perf.DTYPE_ITEMSIZE.get(
                                dtype_name, 4
                            ),
                            with_field=with_field,
                        ),
                    )
                    if rf is not None:
                        sp["model_bytes_per_cell"] = (
                            rf["model_bytes_per_cell"]
                        )
                        sp["model_gbps"] = rf["model_gbps"]
                        sp["roofline_fraction"] = rf["roofline_fraction"]
                    perf.record_memory(self.registry, context="serve")
                except Exception:
                    pass
        except QuarantinedError:
            raise
        except Exception as e:
            if self.breaker is not None and bkey is not None:
                self.breaker.record_failure(bkey, e)
            raise
        if self.breaker is not None and bkey is not None:
            self.breaker.record_success(bkey)
        self._h_execute.observe(result.solve_seconds, warm=warm_label)
        # Chaos seam: execute-NaN poisons the batch's final state AFTER
        # the solve - the per-lane watchdog below must catch it (422s),
        # exactly as it would a real device fault.
        if self.fault_plan is not None and self.fault_plan.fire(
            "execute-nan", n=problem.N, timesteps=problem.timesteps,
            scheme=scheme, path=path, k=k, dtype=dtype_name,
        ):
            import numpy as np

            result.u_cur_batch = np.full(
                np.shape(result.u_cur_batch), np.nan, np.float32
            )
        verdicts = self.lane_health(result)
        # Accuracy observatory: every HEALTHY lane that computed oracle
        # errors stamps its measured max_abs_err and appends one
        # accuracy-ledger line (obs/accuracy.py) - rides the watchdog
        # reduction so the per-lane error arrays are read exactly once.
        # Guarded: the X-ray must never fail the batch it measures.
        if compute_errors:
            try:
                accuracy.observe_serve_batch(
                    result, verdicts, scheme=scheme, k=k,
                    dtype=dtype_name, registry=self.registry,
                )
            except Exception:
                pass
        return result, verdicts
