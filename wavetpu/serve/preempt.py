"""Preemptible long solves: serve-side chunked march + resumable state
tokens.

PR 2's supervisor proved the chunked-march machinery for CLI runs
(fixed-length chunk programs, bitwise-identical trajectories, resumable
checkpoints, watchdog-per-chunk).  This module brings it inside the
serve path:

 * `ChunkRunner` wraps `run/supervisor._Path` for the single-backend
   standard-scheme serve tiers (roll / pallas / kfused) and adds the
   one piece the supervisor rebuilds per call: a cached, AOT-compiled
   BOOTSTRAP program (`stop_step=1`) that produces layers 0..1 exactly
   as the uninterrupted solve would.  tau stays `T / timesteps`
   regardless of where the march stops, so bootstrap-to-1 followed by
   fixed-length chunks from start=1 replays the monolithic program's
   op sequence bitwise (the invariant tests/test_supervisor.py pins).
   One ChunkRunner per chunk ProgramKey lives in the engine's program
   LRU under the same ledger/progcache discipline as ensemble programs.

 * `SolveStateStore` is the cross-replica handoff surface: mid-flight
   state checkpoints under `--solve-state-dir`, CONTENT-ADDRESSED (the
   token is the sha256 of the file bytes) and REPLICA-VERIFIED on load
   (hash re-check + solve-identity match against the resuming request),
   so a forged or corrupt token gets a clean 422
   (`InvalidStateTokenError`), never a traceback.  Entries expire after
   `--solve-state-ttl-s` (GC piggybacks on `put`).

Chunk boundaries land on the k-fusion block grid (`chunk_length`), and
resume steps are validated against that grid, so a resumed kfused march
reproduces the uninterrupted op sequence exactly.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from wavetpu.serve.resilience import InvalidStateTokenError

STATE_FORMAT_VERSION = 1

_TOKEN_PREFIX = "st-"
_TOKEN_SUFFIX = ".npz"
_TOKEN_HEX = frozenset("0123456789abcdef")

# Identity fields a resume token must match on the resuming request -
# everything that changes the trajectory or the chunk-program shape.
_IDENTITY_FIELDS = (
    "N", "Np", "Lx", "Ly", "Lz", "T", "timesteps",
    "scheme", "path", "k", "dtype", "compute_errors", "chunk_len",
)


def solve_identity(problem, scheme: str, path: str, k: int,
                   dtype_name: str, compute_errors: bool,
                   chunk_len: int) -> dict:
    """The JSON-stable identity a state token is bound to."""
    return {
        "format": STATE_FORMAT_VERSION,
        "N": int(problem.N),
        "Np": int(problem.Np),
        "Lx": float(problem.Lx),
        "Ly": float(problem.Ly),
        "Lz": float(problem.Lz),
        "T": float(problem.T),
        "timesteps": int(problem.timesteps),
        "scheme": str(scheme),
        "path": str(path),
        "k": int(k),
        "dtype": str(dtype_name),
        "compute_errors": bool(compute_errors),
        "chunk_len": int(chunk_len),
    }


def _file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


class SolveStateStore:
    """Content-addressed mid-flight solve checkpoints.

    `put` writes one .npz (state fields via io/checkpoint's bf16-safe
    codec + a JSON meta blob + error prefixes) to a temp file, names it
    by its own sha256, and atomically renames it in - so a half-written
    file is never loadable and identical states dedupe to one entry.
    `load` re-hashes the file and refuses on ANY mismatch or parse
    problem with `InvalidStateTokenError` (the 422 contract)."""

    def __init__(self, directory: str, ttl_s: float = 3600.0):
        self.directory = directory
        self.ttl_s = float(ttl_s)
        os.makedirs(directory, exist_ok=True)

    def path_for(self, token: str) -> str:
        return os.path.join(
            self.directory, _TOKEN_PREFIX + token + _TOKEN_SUFFIX
        )

    @staticmethod
    def valid_token(token) -> bool:
        return (
            isinstance(token, str)
            and len(token) == 64
            and all(c in _TOKEN_HEX for c in token)
        )

    def put(self, identity: dict, state: Sequence, step: int,
            abs_errors: np.ndarray, rel_errors: np.ndarray,
            origin_trace: Optional[Sequence[str]] = None,
            priority: Optional[str] = None) -> str:
        """Checkpoint `state` (layers up to `step` marched) -> token.

        `origin_trace` is the originating request's (trace id, span id)
        pair; it rides in the meta blob so a resuming replica can link
        its chunk spans back to the trace where the march began.
        `priority` is the march's QoS class: a resume adopts it, so a
        best_effort march stays best_effort however the resume request
        is labeled (the class was clamped at original admission).  Load
        identity verification only reads `_IDENTITY_FIELDS`, so the
        extra keys never affect token acceptance."""
        from wavetpu.io.checkpoint import _encode_field

        arrays = {}
        tags = []
        for i, field in enumerate(state):
            enc, tag = _encode_field(np.asarray(field))
            arrays[f"state{i}"] = enc
            tags.append(tag)
        meta = dict(identity)
        meta["step"] = int(step)
        meta["nstate"] = len(tags)
        meta["state_tags"] = tags
        if origin_trace is not None:
            meta["origin_trace"] = [str(x) for x in origin_trace]
        if priority is not None:
            meta["priority"] = str(priority)
        arrays["meta"] = np.frombuffer(
            json.dumps(meta, sort_keys=True).encode("utf-8"),
            dtype=np.uint8,
        )
        # Error prefixes ride along so the final result reports the full
        # per-layer history even across a handoff.
        arrays["abs_errors"] = np.asarray(
            abs_errors[: step + 1], dtype=np.float64
        )
        arrays["rel_errors"] = np.asarray(
            rel_errors[: step + 1], dtype=np.float64
        )
        fd, tmp = tempfile.mkstemp(dir=self.directory, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **arrays)
            token = _file_sha256(tmp)
            os.replace(tmp, self.path_for(token))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self.gc()
        return token

    def load(self, token: str, expect_identity: Optional[dict] = None
             ) -> Tuple[dict, int, Tuple[np.ndarray, ...],
                        np.ndarray, np.ndarray]:
        """Verify + decode a token -> (identity, step, state, abs, rel).

        Every failure mode - malformed token, missing file, content
        hash mismatch (truncation/corruption/forgery of the name),
        unparseable npz, or identity mismatch against
        `expect_identity` - raises `InvalidStateTokenError` with a
        one-line reason."""
        if not self.valid_token(token):
            raise InvalidStateTokenError(
                "resume_token must be 64 lowercase hex characters"
            )
        path = self.path_for(token)
        if not os.path.exists(path):
            raise InvalidStateTokenError(
                "resume_token not found (expired, GCed, or from a "
                "replica not sharing this --solve-state-dir)"
            )
        try:
            if _file_sha256(path) != token:
                raise InvalidStateTokenError(
                    "resume_token failed content verification "
                    "(checkpoint bytes do not hash to the token)"
                )
            with np.load(path) as z:
                meta = json.loads(bytes(z["meta"]).decode("utf-8"))
                from wavetpu.io.checkpoint import _decode_field

                tags = meta["state_tags"]
                state = tuple(
                    _decode_field(z[f"state{i}"], tags[i])
                    for i in range(int(meta["nstate"]))
                )
                abs_e = np.asarray(z["abs_errors"], dtype=np.float64)
                rel_e = np.asarray(z["rel_errors"], dtype=np.float64)
        except InvalidStateTokenError:
            raise
        except Exception as exc:
            raise InvalidStateTokenError(
                f"resume_token checkpoint is unreadable: "
                f"{type(exc).__name__}"
            ) from None
        step = int(meta.get("step", -1))
        if expect_identity is not None:
            for field in _IDENTITY_FIELDS:
                if meta.get(field) != expect_identity.get(field):
                    raise InvalidStateTokenError(
                        f"resume_token does not match this request "
                        f"({field}: token has {meta.get(field)!r}, "
                        f"request needs {expect_identity.get(field)!r})"
                    )
            chunk_len = int(expect_identity["chunk_len"])
            timesteps = int(expect_identity["timesteps"])
            # Resume steps must land on the chunk grid (checkpoints are
            # only ever written there); off-grid steps would de-align a
            # kfused march from the uninterrupted op sequence.
            if (step < 1 or step >= timesteps
                    or (step - 1) % chunk_len != 0):
                raise InvalidStateTokenError(
                    f"resume_token step {step} is off the chunk grid "
                    f"(1 + j*{chunk_len}, below {timesteps})"
                )
            if len(abs_e) != step + 1 or len(rel_e) != step + 1:
                raise InvalidStateTokenError(
                    "resume_token error history is inconsistent with "
                    "its step"
                )
        return meta, step, state, abs_e, rel_e

    def gc(self) -> int:
        """Drop entries older than ttl_s (by mtime); returns the count.
        Racing replicas double-unlinking is harmless (missing_ok)."""
        removed = 0
        cutoff = time.time() - self.ttl_s
        try:
            names = os.listdir(self.directory)
        except OSError:
            return 0
        for name in names:
            if not (name.startswith(_TOKEN_PREFIX)
                    and name.endswith(_TOKEN_SUFFIX)):
                continue
            full = os.path.join(self.directory, name)
            try:
                if os.path.getmtime(full) < cutoff:
                    os.unlink(full)
                    removed += 1
            except OSError:
                continue
        return removed


class ChunkRunner:
    """A cacheable chunked-march program set for ONE serve tier.

    Holds a `_Path` (the supervisor's PathSpec->solver adapter) plus an
    AOT-compiled bootstrap; the engine caches one per chunk ProgramKey
    so bootstrap + chunk programs compile once per process per config
    (the supervisor's `first()` re-jits per call - unacceptable on the
    request path)."""

    def __init__(self, problem, scheme: str, path: str, k: int,
                 dtype, dtype_name: str, compute_errors: bool,
                 chunk_steps: int, interpret: Optional[bool] = None,
                 block_x: Optional[int] = None):
        from wavetpu.run import supervisor

        if scheme != "standard":
            raise ValueError(
                "chunked serving supports scheme='standard' only "
                "(ensemble bootstrap results carry no compensation "
                "state); compensated tiers run monolithic"
            )
        if path not in ("roll", "pallas", "kfused"):
            raise ValueError(f"chunked serving does not cover path "
                             f"{path!r}")
        fuse = int(k) if path == "kfused" else 1
        spec = supervisor.PathSpec(
            backend="single",
            scheme=scheme,
            fuse_steps=fuse,
            kernel="pallas" if path == "pallas" else "roll",
            dtype=dtype,
            compute_errors=compute_errors,
            interpret=interpret,
            block_x=block_x,
        )
        self._path = supervisor._Path(problem, spec)
        if path == "kfused" and self._path.kind != "kfused":
            raise ValueError(
                f"kfused chunked serving needs N % k == 0 "
                f"(N={problem.N}, k={fuse})"
            )
        self.problem = problem
        self.scheme = scheme
        self.path_name = path
        self.k = fuse
        self.dtype_name = dtype_name
        self.compute_errors = compute_errors
        self.chunk_len = supervisor.chunk_length(int(chunk_steps), fuse)
        self.identity = solve_identity(
            problem, scheme, path, fuse, dtype_name, compute_errors,
            self.chunk_len,
        )
        self.compile_seconds = 0.0   # cumulative, for the LRU/ledger
        self._boot = None            # (jitted runner, call args)
        self._boot_exec = None       # AOT-compiled bootstrap

    # -- geometry ------------------------------------------------------

    def march_lengths(self) -> Tuple[int, ...]:
        """The distinct chunk lengths a full march uses: the main
        length, plus the tail remainder when T-1 is not a multiple."""
        total = self.problem.timesteps - 1
        lens = []
        if total // self.chunk_len:
            lens.append(self.chunk_len)
        if total % self.chunk_len:
            lens.append(total % self.chunk_len)
        return tuple(lens)

    def next_length(self, step: int) -> int:
        """The next chunk's length when `step` layers are done."""
        return min(self.chunk_len, self.problem.timesteps - step)

    def total_chunks(self) -> int:
        total = self.problem.timesteps - 1
        return -(-total // self.chunk_len)

    # -- bootstrap (layers 0..1) ---------------------------------------

    def _boot_builders(self):
        if self._boot is None:
            p = self._path
            if p.kind == "kfused":
                from wavetpu.solver import kfused

                runner, run_params = kfused.make_kfused_solver(
                    self.problem, dtype=p.dtype, k=p.k,
                    compute_errors=self.compute_errors, stop_step=1,
                    block_x=p.spec.block_x, interpret=p.interpret,
                )
                self._boot = (runner, tuple(run_params))
            else:
                from wavetpu.solver import leapfrog

                runner, step_params = leapfrog.make_solver(
                    self.problem, dtype=p.dtype,
                    step_fn=p._step_fn(),
                    compute_errors=self.compute_errors, stop_step=1,
                )
                self._boot = (runner, (step_params,))
        return self._boot

    def _compile_boot(self) -> float:
        runner, args = self._boot_builders()
        if self._boot_exec is not None:
            return 0.0
        t0 = time.perf_counter()
        self._boot_exec = runner.lower(*args).compile()
        spent = time.perf_counter() - t0
        self.compile_seconds += spent
        return spent

    def bootstrap(self):
        """Run layers 0..1 exactly as the uninterrupted solve would;
        returns (state, abs2, rel2, compile_s, solve_s)."""
        import jax

        compile_s = self._compile_boot()
        _, args = self._boot
        t0 = time.perf_counter()
        out = self._boot_exec(*args)
        jax.block_until_ready(out)
        u_prev, u_cur, abs_all, rel_all = out
        abs_np = np.asarray(abs_all, dtype=np.float64)
        solve_s = time.perf_counter() - t0
        rel_np = np.asarray(rel_all, dtype=np.float64)
        return (u_prev, u_cur), abs_np, rel_np, compile_s, solve_s

    # -- chunks --------------------------------------------------------

    def chunk(self, state, start: int, length: int):
        """(state', abs_chunk, rel_chunk, solve_s, compile_s) - the
        supervisor's cached fixed-length chunk program."""
        return self._path.chunk(state, start, length)

    def prime(self) -> float:
        """Compile the bootstrap and EVERY chunk length this march will
        use, without marching (beyond the two bootstrap layers needed
        as example args); returns the compile wall seconds.  This is
        the warmup/cold-start surface: a primed runner serves its first
        long solve with zero fresh compiles."""
        import jax.numpy as jnp

        spent = self._compile_boot()
        out = self._boot_exec(*self._boot[1])
        state = (out[0], out[1])
        for length in self.march_lengths():
            if length in self._path._compiled:
                continue
            if length not in self._path._jit:
                self._path._jit[length] = self._path._build_runner(
                    length, state
                )
            runner, extra = self._path._jit[length]
            args = tuple(state) + (jnp.int32(1),) + extra
            t0 = time.perf_counter()
            self._path._compiled[length] = (
                runner.lower(*args).compile()
            )
            chunk_s = time.perf_counter() - t0
            self.compile_seconds += chunk_s
            spent += chunk_s
        return spent

    # -- state plumbing ------------------------------------------------

    def health_arrays(self, state):
        return self._path.health_arrays(state)

    def prepare(self, state):
        return self._path.prepare(state)

    def to_result(self, state, abs_full, rel_full, final_step: int,
                  init_s: float, solve_s: float, marched: int):
        return self._path.to_result(
            state, abs_full, rel_full, final_step, init_s, solve_s,
            marched,
        )

    @staticmethod
    def state_to_numpy(state):
        return tuple(np.asarray(a) for a in state)

    # -- persistent-cache hooks (serve/progcache.py) -------------------

    def executable_payload(self):
        """Serialized (boot + per-length chunk) executables for the
        disk tier, or None before `prime`/first use.  Raises where the
        jaxlib cannot serialize; callers probe
        `progcache.aot_capability()` first (same contract as
        EnsembleSolver.executable_payload)."""
        if self._boot_exec is None or not self._path._compiled:
            return None
        from wavetpu.serve import progcache

        return {
            "format": 1,
            "boot": progcache.serialize_executable(self._boot_exec),
            "chunks": {
                int(length): progcache.serialize_executable(compiled)
                for length, compiled in self._path._compiled.items()
            },
        }

    def adopt_executable(self, payload) -> float:
        """Install deserialized executables (disk-tier warm path);
        returns the deserialize wall seconds.  Raises on an
        incompatible payload - the caller counts a miss and compiles
        fresh."""
        from wavetpu.serve import progcache

        t0 = time.perf_counter()
        self._boot_builders()
        boot_exec = progcache.load_executable(payload["boot"])
        chunk_execs = {}
        for length, blob in payload["chunks"].items():
            length = int(length)
            # The traced runner structure is needed alongside the
            # executable (chunk() reads its extra-args tuple); building
            # it is pure tracing setup, no compile.
            if length not in self._path._jit:
                self._path._jit[length] = self._path._build_runner(
                    length, None
                )
            chunk_execs[length] = progcache.load_executable(blob)
        self._boot_exec = boot_exec
        self._path._compiled.update(chunk_execs)
        spent = time.perf_counter() - t0
        self.compile_seconds += spent
        return spent
