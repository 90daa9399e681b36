"""Persistent AOT program cache: compiled programs survive restarts.

Chip runs and the compile ledger put N=512 XLA compiles at 30-62 s
against 2-7 s solves - for a serving fleet, compilation is the dominant
cold-start and autoscaling cost, and every process restart pays it
again.  This module is the disk tier under the serve engine's in-memory
LRU (`--program-cache-dir`):

    memory LRU  ->  disk (this module)  ->  fresh XLA compile

An entry is one file per (ProgramKey, environment fingerprint):

    DIR/<sha256(key)[:20]>-<sha256(fingerprint)[:8]>.wtpc

    MAGIC | u32 header_len | header JSON | pickled AOT payload

The header carries the full key, the fingerprint (wavetpu/jax/jaxlib
version, backend, device kind - an executable deserialized into the
wrong runtime is a crash or, worse, silent garbage), the FRESH compile
seconds it replaced (the measured savings credit), and a sha256 of the
payload.  Writes are atomic (tmp + os.replace); loads validate magic,
fingerprint, length, and checksum - a truncated, stale-fingerprint, or
cross-version entry is a COUNTED miss that falls through to a fresh
compile, never a crash and never a circuit-breaker feed.

The payload is `serialize_executable` (below) of the lowered-and-
compiled ensemble program; `aot_capability()` probes once per process
whether this jaxlib round-trips it (serialize -> deserialize -> execute
a tiny program) and the verdict rides /metrics next to the vmap probes.
Where the probe fails, JAX's persistent compilation cache (placed by
`wavetpu.jaxcache`) is the only persistence left - compiles are then
transparently fast but not adoptable, so they still count as engine
misses; the mode is visible in the same probe surface.  In AOT mode the
XLA cache rides along anyway: the incidental jits around the ensemble
program (watchdog reductions, padding helpers) are real cold-start cost
with no executable object to adopt, and the XLA cache is exactly their
shape.

Size is bounded by `--program-cache-max-bytes`: LRU by access time
(entry mtime, refreshed via os.utime on every hit), oldest evicted
first, the newest entry never evicted (a budget smaller than one
program must not make the cache a no-op).

`wavetpu warmup --manifest MANIFEST.json [--program-cache-dir DIR]`
(main below) consumes `wavetpu ledger-report --emit-warmup-manifest`'s
output verbatim - each key round-trips through `program_key_from_dict`
- and pre-populates a fresh replica's disk cache, printing per-key
timings.  `wavetpu serve --warmup-manifest` runs the same keys through
the engine on the background-warmup thread, so /healthz readiness
flips only once the manifest is warm.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import sys
import threading
import time
from typing import List, Optional, Sequence, Tuple

from wavetpu import jaxcache
from wavetpu.obs import ledger as compile_ledger

MAGIC = b"WTPC0001"
ENTRY_SUFFIX = ".wtpc"

FINGERPRINT_FIELDS = ("wavetpu", "jax", "jaxlib", "backend",
                      "device_kind")


def env_fingerprint() -> dict:
    """The environment identity a serialized executable is only valid
    under.  Any field drifting (jaxlib upgrade, different chip
    generation, CPU vs TPU) invalidates every entry written under the
    old value - by filename, so stale entries are simply never read."""
    import jax

    from wavetpu import __version__

    try:
        import jaxlib

        jaxlib_version = jaxlib.__version__
    except Exception:
        jaxlib_version = "unknown"
    try:
        devices = jax.devices()
        device_kind = devices[0].device_kind if devices else "none"
    except Exception:
        device_kind = "unknown"
    return {
        "wavetpu": __version__,
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "backend": jax.default_backend(),
        "device_kind": device_kind,
    }


# ------------------------------------------------ AOT capability probe

_AOT_PROBE: Optional[Tuple[bool, Optional[str]]] = None
_probe_lock = threading.Lock()


def serialize_executable(compiled) -> tuple:
    """`(payload, in_tree, out_tree, device_ids)` of a compiled program:
    `serialize_executable.serialize`'s triple plus the ids of the
    devices the program runs on, in its own order.  Without them a
    load would place the program on every visible device, which fails
    for a one-device program on a host with several."""
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return payload, in_tree, out_tree, ids


def load_executable(blob):
    """Inverse of `serialize_executable`: the compiled program, loaded
    onto the devices it was compiled for.  Raises on an incompatible
    payload (a missing device included)."""
    import jax
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree, ids = blob
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in ids],
    )


def aot_capability() -> Tuple[bool, Optional[str]]:
    """Can this jaxlib serialize, deserialize, AND execute a compiled
    executable?  Probed once per process with a tiny jit through the
    same `serialize_executable` / `load_executable` pair the engines
    store and adopt with (the verdict is recorded, never raised), and
    surfaced in /metrics via
    `probe_results()` - a replica silently running the XLA-cache
    fallback must be visible from the outside."""
    global _AOT_PROBE
    with _probe_lock:
        if _AOT_PROBE is not None:
            return _AOT_PROBE
        import jax
        import jax.numpy as jnp
        from jax.experimental.compilation_cache import compilation_cache

        # The probe must compile OUTSIDE the persistent compilation
        # cache: an XLA-cache-served executable serializes but fails
        # deserialize_and_load ("Symbols not found"), which would flip
        # every restarted replica into fallback mode - exactly the
        # processes the AOT tier exists for.
        restore = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            f = jax.jit(lambda x: x * 2.0 + 1.0)
            compiled = f.lower(jnp.zeros((4,), jnp.float32)).compile()
            # Round-trip through pickle exactly as an entry file does -
            # a PyTreeDef that serializes but does not pickle would
            # pass a weaker probe and still corrupt every store.
            again = load_executable(
                pickle.loads(pickle.dumps(serialize_executable(compiled)))
            )
            out = again(jnp.ones((4,), jnp.float32))
            if float(out[0]) != 3.0:
                raise RuntimeError(
                    f"deserialized program computed {float(out[0])}, "
                    f"want 3.0"
                )
            verdict = (True, None)
        except Exception as e:  # recorded, never raised
            verdict = (False, f"{type(e).__name__}: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", restore)
            # JAX decides once, at a compile, whether its cache is in
            # use: let the next compile decide again with the cache on.
            compilation_cache.reset_cache()
        _AOT_PROBE = verdict
        return verdict


def probe_results() -> list:
    """The cached AOT-serialization verdict as a /metrics row (empty
    until something touched the cache - the probe is lazy)."""
    if _AOT_PROBE is None:
        return []
    return [{
        "probe": "aot_serialize_executable",
        "ok": _AOT_PROBE[0],
        "reason": _AOT_PROBE[1],
    }]


# ------------------------------------------------------ the disk tier


class ProgramCache:
    """Disk-backed serialized-executable store for one directory.

    Thread-safe; every failure mode (corrupt entry, stale fingerprint,
    full disk, unpicklable payload) is a counted event in
    `wavetpu_progcache_events_total{event=}` and a None/False return -
    the serve path must treat disk problems as cache misses, never as
    request failures."""

    def __init__(self, directory: str,
                 max_bytes: Optional[int] = None,
                 registry=None, fault_plan=None):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self.max_bytes = max_bytes
        self.fault_plan = fault_plan
        self._lock = threading.Lock()
        # Private counts always; mirrored into the registry when the
        # engine hands us its /metrics registry.
        self.counts: dict = {}
        self._counter = None
        self._saved = None
        if registry is not None:
            self._counter = registry.counter(
                "wavetpu_progcache_events_total",
                "persistent program-cache events", ("event",),
            )
            self._saved = registry.counter(
                "wavetpu_progcache_saved_seconds_total",
                "compile seconds served from disk instead of XLA "
                "(fresh compile seconds minus deserialize seconds)",
            )
        # The XLA persistent cache rides along in BOTH modes: in AOT
        # mode it catches the incidental jits around the ensemble
        # program (watchdog reductions, padding helpers - real
        # cold-start cost with no adoptable executable); where the AOT
        # probe fails it IS the persistence mechanism (and gets the hit
        # counter, so fallback-mode compiles can be attributed).
        ok, _why = aot_capability()
        self.aot_ok = ok
        self.xla_cache = jaxcache.configure() is not None
        self.xla_fallback = bool(self.xla_cache and not ok)
        # The hit counter serves two masters: fallback-mode ledger
        # attribution (`source: disk` when the XLA cache served a
        # compile), and - in AOT mode - the store guard: a payload
        # serialized from a cache-served executable fails to
        # deserialize, so such compiles must never be put().
        self.xla_hits: Optional[jaxcache.XlaCacheHitCounter] = (
            jaxcache.shared_xla_hit_counter() if self.xla_cache else None
        )
        self.fingerprint = env_fingerprint()
        self._fp_hash = hashlib.sha256(
            json.dumps(self.fingerprint, sort_keys=True).encode()
        ).hexdigest()[:8]

    # ---- bookkeeping ----

    @property
    def usable(self) -> bool:
        """True when entries can be stored/adopted (AOT mode); the XLA
        fallback persists compiles on its own, invisibly to put/load."""
        return self.aot_ok

    def count(self, event: str, n: int = 1) -> None:
        with self._lock:
            self.counts[event] = self.counts.get(event, 0) + n
        if self._counter is not None:
            self._counter.inc(n, event=event)

    def credit_saved(self, fresh_compile_s: float,
                     load_s: float) -> float:
        saved = max(0.0, float(fresh_compile_s) - float(load_s))
        if self._saved is not None and saved > 0:
            self._saved.inc(saved)
        return saved

    def entry_path(self, key: dict) -> str:
        canon = compile_ledger.canonical_key(key)
        kh = hashlib.sha256(canon.encode()).hexdigest()[:20]
        return os.path.join(
            self.directory, f"{kh}-{self._fp_hash}{ENTRY_SUFFIX}"
        )

    def _entries(self):
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            if not name.endswith(ENTRY_SUFFIX):
                continue
            p = os.path.join(self.directory, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            out.append((p, st.st_size, st.st_mtime))
        return out

    # ---- store / load ----

    def put(self, key: dict, payload, compile_s: float) -> bool:
        """Atomically persist one serialized executable; returns True
        on success.  `compile_s` is the fresh compile this entry will
        spare future processes - the measured-savings credit a later
        load reports."""
        if not self.usable:
            return False
        try:
            blob = pickle.dumps(payload, protocol=4)
            header = {
                "format": 1,
                "key": compile_ledger.normalize_key(key),
                "fingerprint": dict(self.fingerprint),
                "created_unix": round(time.time(), 3),
                "compile_s": round(float(compile_s), 6),
                "payload_sha256": hashlib.sha256(blob).hexdigest(),
                "payload_len": len(blob),
            }
            hdr = json.dumps(header, sort_keys=True).encode()
            path = self.entry_path(key)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(MAGIC)
                f.write(struct.pack(">I", len(hdr)))
                f.write(hdr)
                f.write(blob)
            os.replace(tmp, path)
        except Exception:
            self.count("store_error")
            return False
        self.count("store")
        if self.max_bytes is not None:
            self.gc()
        return True

    def load(self, key: dict) -> Optional[Tuple[object, dict]]:
        """(payload, header) for a valid entry, else None - with the
        reason counted (`disk_miss` / `corrupt` /
        `fingerprint_mismatch`).  A hit refreshes the entry's mtime
        (the GC's LRU clock); a corrupt entry is deleted so later
        processes pay a plain disk_miss instead of re-parsing garbage.
        Never raises."""
        if not self.usable:
            return None
        path = self.entry_path(key)
        if not os.path.exists(path):
            self.count("disk_miss")
            return None

        def _corrupt():
            self.count("corrupt")
            try:
                os.remove(path)
            except OSError:
                pass
            return None

        # Chaos seams (run/faults.py): drive the REAL detection
        # branches, not simulations of them - truncate the entry on
        # disk, or poison the expected fingerprint, then read normally.
        expected_fp = self.fingerprint
        if self.fault_plan is not None:
            ctx = {
                "n": key.get("N"), "timesteps": key.get("timesteps"),
                "scheme": key.get("scheme"), "path": key.get("path"),
                "k": key.get("k"), "dtype": key.get("dtype"),
            }
            if self.fault_plan.fire("progcache-truncate", **ctx):
                from wavetpu.run import faults as _faults

                try:
                    _faults.truncate_tail(path, drop_bytes=64)
                except OSError:
                    pass
            if self.fault_plan.fire("progcache-fingerprint", **ctx):
                expected_fp = dict(self.fingerprint,
                                   wavetpu="injected-other-version")
        try:
            with open(path, "rb") as f:
                if f.read(len(MAGIC)) != MAGIC:
                    return _corrupt()
                raw_len = f.read(4)
                if len(raw_len) != 4:
                    return _corrupt()
                (hdr_len,) = struct.unpack(">I", raw_len)
                hdr = f.read(hdr_len)
                if len(hdr) != hdr_len:
                    return _corrupt()
                header = json.loads(hdr)
                if header.get("fingerprint") != expected_fp:
                    self.count("fingerprint_mismatch")
                    return None
                blob = f.read()
            if (
                len(blob) != header.get("payload_len")
                or hashlib.sha256(blob).hexdigest()
                != header.get("payload_sha256")
            ):
                return _corrupt()
            payload = pickle.loads(blob)
        except Exception:
            return _corrupt()
        try:
            os.utime(path)
        except OSError:
            pass
        self.count("disk_hit")
        return payload, header

    def gc(self) -> int:
        """Evict oldest-accessed entries until the directory fits
        `max_bytes`; the newest entry is never evicted (a budget
        smaller than one program must degrade to keep-latest, not
        keep-nothing).  Returns the eviction count."""
        if self.max_bytes is None:
            return 0
        entries = sorted(self._entries(), key=lambda e: e[2])
        total = sum(e[1] for e in entries)
        evicted = 0
        while total > self.max_bytes and len(entries) > 1:
            path, size, _mtime = entries.pop(0)
            try:
                os.remove(path)
            except OSError:
                continue
            total -= size
            evicted += 1
        if evicted:
            self.count("gc_evict", evicted)
        return evicted

    def entry_keys(self) -> List[dict]:
        """ProgramKey dicts of every ADOPTABLE disk entry: same-
        fingerprint `.wtpc` files whose header parses (headers only -
        no payload read, no pickle).  This is the disk half of the
        /metrics `program_cache.warm_keys` block the fleet router
        bootstraps its affinity table from: a replica that has not yet
        served a tier still attracts its traffic when the shared cache
        dir lets it adopt the program instead of compiling.  Corrupt or
        foreign-fingerprint entries are silently skipped (this is
        advertisement, not adoption - load() keeps the loud path)."""
        if not self.usable:
            return []
        suffix = f"-{self._fp_hash}{ENTRY_SUFFIX}"
        out: List[dict] = []
        for path, _size, _mtime in self._entries():
            if not os.path.basename(path).endswith(suffix):
                continue
            try:
                with open(path, "rb") as f:
                    if f.read(len(MAGIC)) != MAGIC:
                        continue
                    raw_len = f.read(4)
                    if len(raw_len) != 4:
                        continue
                    (hdr_len,) = struct.unpack(">I", raw_len)
                    if hdr_len > 1 << 20:
                        continue
                    header = json.loads(f.read(hdr_len))
            except Exception:
                continue
            key = header.get("key")
            if isinstance(key, dict):
                out.append(key)
        return out

    def stats(self) -> dict:
        """The /metrics `program_cache.progcache` block."""
        entries = self._entries()
        with self._lock:
            counts = dict(self.counts)
        return {
            "enabled": True,
            "dir": self.directory,
            "aot": self.aot_ok,
            "xla_cache": self.xla_cache,
            "xla_fallback": self.xla_fallback,
            "entries": len(entries),
            "bytes": sum(e[1] for e in entries),
            "max_bytes": self.max_bytes,
            "events": counts,
            "aot_probes": probe_results(),
        }


# ----------------------------------------- manifest-driven warmup CLI


def _dtype_from_name(name: str):
    import jax.numpy as jnp

    table = {"f32": jnp.float32, "f64": jnp.float64,
             "bf16": jnp.bfloat16}
    if name not in table:
        raise ValueError(f"unknown dtype {name!r}")
    return table[name]


def build_solver_for_key(pk, interpret: Optional[bool] = None):
    """The (uncompiled) ensemble program a ProgramKey describes - the
    same constructor calls `ServeEngine._program` makes, honoring the
    key's own compute_errors (a manifest key replays what was actually
    served, not what this process would derive)."""
    from wavetpu.core.problem import Problem
    from wavetpu.ensemble import batched as ensemble
    from wavetpu.ensemble import sharded as ens_sharded

    problem = Problem(N=pk.N, Np=1, Lx=pk.Lx, Ly=pk.Ly, Lz=pk.Lz,
                      T=pk.T, timesteps=pk.timesteps)
    if pk.mesh is not None:
        return ens_sharded.ShardedEnsembleSolver(
            problem, pk.batch, pk.mesh,
            dtype=_dtype_from_name(pk.dtype), kernel=pk.path,
            compute_errors=pk.compute_errors, interpret=interpret,
        )
    return ensemble.EnsembleSolver(
        problem, pk.batch, dtype=_dtype_from_name(pk.dtype),
        path=pk.path, k=pk.k, compute_errors=pk.compute_errors,
        interpret=interpret, with_field=pk.with_field, scheme=pk.scheme,
    )


def load_manifest(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        manifest = json.load(f)
    if not isinstance(manifest, dict) or not manifest.get(
        compile_ledger.MANIFEST_FLAG
    ):
        raise ValueError(
            f"{path} is not a wavetpu warmup manifest (missing "
            f"{compile_ledger.MANIFEST_FLAG!r}; produce one with "
            f"`wavetpu ledger-report DIR --emit-warmup-manifest OUT`)"
        )
    keys = manifest.get("keys")
    if not isinstance(keys, list):
        raise ValueError(f"{path}: manifest `keys` must be a list")
    return manifest


def warm_manifest_into_cache(
    manifest: dict, cache: Optional[ProgramCache] = None,
    interpret: Optional[bool] = None, out=None,
) -> dict:
    """Compile (or disk-adopt) every manifest key, storing fresh
    compiles into `cache`; prints one per-key timing line to `out` and
    returns the summary dict.  Per-key failures are recorded and do not
    stop the sweep."""
    import jax

    out = sys.stdout if out is None else out
    n_dev = len(jax.devices())
    summary = {"keys": 0, "disk_hits": 0, "compiled": 0, "skipped": 0,
               "failed": 0, "compile_s": 0.0, "errors": []}
    for raw in manifest.get("keys", ()):
        summary["keys"] += 1
        try:
            pk = compile_ledger.program_key_from_dict(raw)
        except Exception as e:
            summary["failed"] += 1
            summary["errors"].append(f"bad key {raw!r}: {e}")
            print(f"  bad key: {e}", file=out)
            continue
        label = compile_ledger._key_label(
            compile_ledger.key_from_program_key(pk)
        )
        if pk.mesh is not None:
            need = pk.mesh[0] * pk.mesh[1] * pk.mesh[2]
            if need > n_dev:
                summary["skipped"] += 1
                print(f"  {label}: skip (mesh needs {need} devices, "
                      f"{n_dev} available)", file=out)
                continue
        key_dict = compile_ledger.key_from_program_key(pk)
        try:
            t0 = time.perf_counter()
            solver = build_solver_for_key(pk, interpret=interpret)
            if cache is not None and cache.usable:
                entry = cache.load(key_dict)
                if entry is not None:
                    try:
                        solver.adopt_executable(entry[0])
                        dt = time.perf_counter() - t0
                        summary["disk_hits"] += 1
                        print(f"  {label}: disk hit ({dt:.3f}s)",
                              file=out)
                        continue
                    except Exception:
                        cache.count("corrupt")
            pre_hits = (
                cache.xla_hits.hits
                if cache is not None and cache.xla_hits is not None
                else None
            )
            compile_s = solver.compile()
            summary["compiled"] += 1
            summary["compile_s"] += compile_s
            stored = False
            xla_served = (
                pre_hits is not None
                and cache.xla_hits.hits > pre_hits
            )
            if cache is not None and cache.usable and not xla_served:
                payload = solver.executable_payload()
                if payload is not None:
                    stored = cache.put(key_dict, payload, compile_s)
            print(
                f"  {label}: compiled {compile_s:.3f}s"
                + (" -> cached" if stored else ""),
                file=out,
            )
        except Exception as e:
            summary["failed"] += 1
            summary["errors"].append(f"{label}: {e}")
            print(f"  {label}: FAILED ({type(e).__name__}: {e})",
                  file=out)
    summary["compile_s"] = round(summary["compile_s"], 6)
    return summary


_USAGE = (
    "usage: wavetpu warmup --manifest MANIFEST.json "
    "[--program-cache-dir DIR] [--program-cache-max-bytes B] "
    "[--platform NAME]"
)

_KNOWN = ("manifest", "program-cache-dir", "program-cache-max-bytes",
          "platform")


def main(argv: Optional[Sequence[str]] = None) -> int:
    """`wavetpu warmup`: pre-populate a replica's program cache from a
    ledger-report manifest.  Exit 0 on success (skips are not
    failures), 1 when any key failed to build/compile, 2 on usage."""
    from wavetpu.core.flags import split_flags

    argv = list(sys.argv[1:] if argv is None else argv)
    try:
        _, flags = split_flags(argv, _KNOWN, (),
                               allow_positionals=False)
        if "manifest" not in flags:
            raise ValueError("missing --manifest MANIFEST.json")
        manifest = load_manifest(flags["manifest"])
        max_bytes = (
            int(flags["program-cache-max-bytes"])
            if "program-cache-max-bytes" in flags else None
        )
    except (ValueError, OSError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        print(_USAGE, file=sys.stderr)
        return 2

    import jax

    platform = flags.get("platform") or os.environ.get("JAX_PLATFORMS")
    if platform and platform != jax.config.jax_platforms:
        jax.config.update("jax_platforms", platform)

    cache = None
    if "program-cache-dir" in flags:
        cache = ProgramCache(flags["program-cache-dir"],
                             max_bytes=max_bytes)
        mode = (
            "AOT serialized executables" if cache.usable
            else "XLA persistent compilation cache (fallback: "
            + str(aot_capability()[1]) + ")"
            if cache.xla_fallback else "DISABLED (no mechanism)"
        )
        print(f"program cache: {cache.directory} [{mode}]")
    else:
        print("note: no --program-cache-dir; compiles will not "
              "persist beyond this process")

    t0 = time.perf_counter()
    summary = warm_manifest_into_cache(manifest, cache)
    wall = time.perf_counter() - t0
    print(
        f"warmed {summary['keys']} key(s) in {wall:.3f}s: "
        f"{summary['disk_hits']} disk hit(s), "
        f"{summary['compiled']} compiled "
        f"({summary['compile_s']:.3f}s), "
        f"{summary['skipped']} skipped, {summary['failed']} failed"
    )
    return 1 if summary["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
