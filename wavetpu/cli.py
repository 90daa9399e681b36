"""Command-line entry point, argv-compatible with every reference variant.

Positional contract (must never break): `N Np Lx Ly Lz [T] [timesteps]`,
where Lx/Ly/Lz accept the literal string "pi" and T/timesteps default to
1 and 20 (openmp_sol.cpp:192-204, mpi_new.cpp:376-404, README.txt:7-8).
Np is parsed for compatibility; like the reference MPI/CUDA variants it does
not influence the computation (mpi_sol.cpp:381).

Beyond the positional contract, optional flags select the TPU backend
pieces (the reference picks variants by compiling different binaries; we
pick at runtime):

  --backend {auto,single,sharded}   auto = sharded iff >1 device
  --mesh MX,MY,MZ                   explicit 3D mesh shape (sharded)
  --dtype {f32,f64,bf16}            state dtype (f64 only meaningful on CPU)
  --no-errors                       skip the fused analytic-error oracle
  --out-dir DIR                     where the report file goes
  --platform NAME                   jax platform (e.g. cpu), as the
                                    JAX_PLATFORMS env var would set it
  --profile DIR                     capture a jax.profiler device trace of
                                    the solve into DIR (TensorBoard/xprof
                                    format) - the deep-dive complement to
                                    --phase-timing's summary numbers
  --phase-timing                    measure the loop vs ICI-exchange split
                                    (probe programs; see solver/timing.py) and
                                    add it to the report, like the reference's
                                    "new" variants (mpi_new.cpp:368-371);
                                    covers the standard step and both k-fused
                                    onions (incl. --scheme compensated with
                                    --fuse-steps K)
  --scheme {standard,compensated}   time-integration scheme: compensated =
                                    Kahan incremental leapfrog, pushing f32
                                    to the discretization limit (5.7e-6 vs
                                    1.1e-3 L-inf at N=512/1000 on v5e);
                                    composes with --fuse-steps K into the
                                    FLAGSHIP velocity-form onion (~42
                                    Gcell/s at 5.7e-6 single-device, and
                                    sharded over --mesh MX,1,1 at K=2 for
                                    N=512 - VMEM bounds K;
                                    solver/kfused_comp.py); f32/f64, 1-step
                                    form also on any sharded mesh
                                    (checkpointable; no --overlap /
                                    --phase-timing)
  --v-dtype {f32,bf16}              increment-stream dtype for the
                                    compensated k-fused mode: bf16 = the
                                    increment-form bf16 config (bf16 v +
                                    f32 carrier u, carry-less; ~46 Gcell/s
                                    at L-inf ~6e-4 - the bf16 mode whose
                                    numbers mean something, vs the 0.66
                                    garbage of a bf16 carrier state)
  --c2-field PRESET|FILE.npy        spatially varying wave speed c^2(x,y,z):
                                    a preset (constant, gaussian-lens,
                                    two-layer) or an .npy file of c^2 values
                                    on the fundamental (N,N,N) grid
                                    (tau^2 applied internally).  Disables
                                    the analytic-error oracle (no closed
                                    form).  Composes with --fuse-steps K
                                    (the c^2tau^2 slab rides the k-step
                                    onion as its own slab + k-plane halos)
                                    and with --scheme compensated when
                                    K >= 2 (the velocity-form onion takes
                                    the field coefficient in the increment,
                                    incl. --v-dtype bf16); single or
                                    sharded backend, even or pad-and-mask
                                    decompositions
  --kernel {auto,roll,pallas}       hot-kernel selection: pallas = the fused
                                    slab kernel (kernels/stencil_pallas.py,
                                    the analog of the reference shipping its
                                    CUDA kernel in every binary,
                                    Makefile:4-8); roll = the XLA reference
                                    stencil; auto = pallas on TPU, roll
                                    elsewhere (off-TPU pallas runs in
                                    interpret mode - correct but slow)
  --fuse-steps K                    temporal blocking: K leapfrog layers per
                                    HBM pass (solver/kfused.py; ~44 vs ~20
                                    Gcell/s at K=4, N=512/1000 on v5e, with
                                    per-layer errors still reported).
                                    Requires the pallas kernel; single device
                                    or an (MX,MY,1) mesh (--mesh ->
                                    solver/sharded_kfused.py, K-deep ghost
                                    exchange per K layers, corners via
                                    sequenced y-then-x ppermute); layers are
                                    bitwise identical to K=1, including the
                                    uneven pad-and-mask path when K does not
                                    divide N/MX (x-only meshes)
  --overlap                         overlap halo exchange with the bulk
                                    stencil update (sharded backend, even
                                    shard splits only)
  --debug-nans                      enable jax debug_nans: the solve traps
                                    on the first NaN instead of reporting
                                    a garbage error norm (SURVEY section 5
                                    sanitizer row - e.g. a Courant-unstable
                                    config, or a VMEM overflow that
                                    silently NaNs inside lax.scan)
  --distributed                     multi-process launch: call
                                    jax.distributed.initialize() (explicit
                                    JAX_COORDINATOR_ADDRESS /
                                    JAX_NUM_PROCESSES / JAX_PROCESS_ID env
                                    vars, or the TPU-pod auto-detection)
                                    and gate stdout + the report file on
                                    process 0 - the rank-0 gating of every
                                    reference variant (mpi_new.cpp:356-371)
  --stop-step S                     halt after layer S (tau unchanged); pairs
                                    with --save-state for preemptible runs
  --save-state PATH                 write the final (u_prev, u_cur, step)
                                    checkpoint: one .npz (single backend) or
                                    a per-shard directory (sharded backend)
                                    (io/checkpoint.py)
  --resume PATH                     continue a checkpointed run to its
                                    timesteps (positionals then unnecessary);
                                    a directory resumes on the sharded
                                    backend, a .npz on the single-device one.
                                    A checkpoint ROTATION root (what
                                    --ckpt-dir maintains) resolves through
                                    its `latest` pointer automatically, so
                                    `--resume DIR --ckpt-every S` composes
                                    across repeated preemptions
  --ckpt-every S                    SUPERVISED solve (run/supervisor.py):
                                    march in ~S-layer chunks (snapped to the
                                    --fuse-steps block so supervised layers
                                    stay bitwise-identical), checkpointing
                                    each boundary into a fresh rotation
                                    entry under --ckpt-dir with an atomic
                                    `latest` pointer and keep-last-2 GC;
                                    SIGTERM/SIGINT finish the chunk, save,
                                    and exit resumable (code 3); each chunk
                                    is health-checked (run/health.py) and a
                                    NaN/amplitude blowup halts with the
                                    last-good checkpoint (code 4)
  --ckpt-dir DIR                    the rotation root for --ckpt-every
                                    (defaults to the --resume rotation root
                                    when resuming one)
  --retries N                       bounded auto-retry: reload the last-good
                                    checkpoint after a watchdog trip and
                                    re-run the chunk up to N times (the
                                    transient-fault model) before halting
  --max-amp X                       watchdog amplitude bound (default 1e3;
                                    the analytic solution is |u| <= 1, so
                                    the default only trips real blowups)
  --no-watchdog                     disable the per-chunk health check
  --telemetry-dir DIR               unified telemetry (wavetpu/obs/,
                                    docs/observability.md): structured
                                    JSONL spans into DIR/trace.jsonl
                                    (supervisor chunks, health checks,
                                    checkpoint writes - aligned with
                                    --profile device traces via
                                    jax.profiler.TraceAnnotation) plus
                                    periodic registry snapshots
                                    (DIR/heartbeat.jsonl to tail,
                                    DIR/metrics.prom to scrape) plus the
                                    append-only compile-cost ledger
                                    (DIR/compile_ledger.jsonl);
                                    summarize with `wavetpu trace-report
                                    DIR/trace.jsonl` and
                                    `wavetpu ledger-report DIR`

Exit codes (docs/robustness.md): 0 complete; 2 usage or checkpoint-load
error; 3 preempted but checkpointed (requeue + --resume); 4 numerical-
health halt with the last-good checkpoint preserved (page an operator).
Non-zero supervised exits print `resumable checkpoint: PATH`.

Subcommands: `wavetpu serve [...]` starts the batched-inference HTTP
front end (wavetpu/serve/api.py, also installed as `wavetpu-serve`;
endpoint contract in docs/serving.md; request-path resilience -
deadlines, Retry-After, circuit breaker, worker supervision, chaos
injection via WAVETPU_FAULT serve-* specs - in docs/robustness.md,
with `wavetpu.client.WavetpuClient` as the retrying client half).
`wavetpu trace-report
[TRACE.jsonl ...] [--dir DIR ...] [--kind K] [--request ID]` summarizes
--telemetry-dir span traces (per-kind count/total/p50/p95; critical-path
view of one request - wavetpu/obs/report.py; rotated segment sets are
read whole); with several sources (router + replicas) it joins W3C
traceparent-linked spans into ONE cross-process tree, including solves
preempted on one replica and resumed on another (docs/observability.md
"Distributed tracing").
`wavetpu ledger-report TELEMETRY_DIR [--json]
[--emit-warmup-manifest OUT.json]` aggregates the compile-cost ledger
(wavetpu/obs/ledger.py): per-ProgramKey compile spend, keys recompiled
across restarts, a what-if simulation of the persistent AOT cache
(ROADMAP direction 2), and the warmup-manifest export that direction's
`wavetpu warmup --manifest` will consume.
`wavetpu plan-report TELEMETRY_DIR [--json]
[--emit-plan-table OUT.json]` joins the accuracy ledger
(wavetpu/obs/accuracy.py - oracle errors + shadow-solve divergence)
with the compile ledger and the obs/perf.py roofline model into the
measured speed-accuracy frontier per (plan, N-bucket): Gcell/s, wall
s/request, error percentiles, Pareto-dominated plans flagged; the
emitted plan_table.json is the input ROADMAP direction 4's planner
consumes.  `wavetpu profile --out DIR
ARGS...` runs a full wavetpu command line under `jax.profiler` so the
telemetry spans land inside the device trace, then prints a
post-capture summary.
`wavetpu loadgen generate|replay|gate` is the traffic-realism harness
(wavetpu/loadgen/, docs/observability.md): generate or record mixed-
scenario JSONL traces, replay them open-/closed-loop against a live
`wavetpu serve`, emit loadgen_report.json with per-tier p50/p95/p99 +
occupancy + Server-Timing attribution, and diff two reports as a
perf-regression gate (exit 1 on SLO violation); `replay --retries N`
drives the retrying client (chaos drills), `--duration S` soaks a
looped trace against a wall-clock budget.  `wavetpu --version`
prints the package version (both entry points accept it).
"""

from __future__ import annotations

import sys
from typing import List, Optional, Sequence, Tuple

from wavetpu.core.problem import Problem


_KNOWN_FLAGS = (
    "backend", "mesh", "dtype", "no-errors", "out-dir", "platform",
    "phase-timing", "stop-step", "save-state", "resume",
    "kernel", "overlap", "scheme", "distributed", "profile",
    "fuse-steps", "debug-nans", "v-dtype", "c2-field",
    "ckpt-every", "ckpt-dir", "retries", "max-amp", "no-watchdog",
    "telemetry-dir",
)
_VALUELESS = (
    "no-errors", "phase-timing", "overlap", "distributed", "debug-nans",
    "no-watchdog",
)


# resolve_kernel moved to `wavetpu.progkey` (the fleet router resolves
# kernel=auto from polled replica backends without jax); re-exported
# here for the existing callers.
from wavetpu.progkey import resolve_kernel  # noqa: E402,F401


def _split_flags(argv: Sequence[str]) -> Tuple[List[str], dict]:
    """Separate reference-style positionals from --flag[=value] options
    (the shared core.flags parser bound to this CLI's flag table)."""
    from wavetpu.core.flags import split_flags

    return split_flags(argv, _KNOWN_FLAGS, _VALUELESS)


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "serve":
        # The serving front end is its own flag namespace; dispatch before
        # the solver CLI's parser can reject it.
        from wavetpu.serve import api as serve_api

        return serve_api.main(argv[1:])
    if argv and argv[0] == "trace-report":
        # Telemetry trace summarizer (stdlib-only; never touches jax).
        from wavetpu.obs import report as obs_report

        return obs_report.main(argv[1:])
    if argv and argv[0] == "loadgen":
        # Trace-replay load generator + SLO regression gate (stdlib
        # HTTP client; never touches jax - runnable off-accelerator).
        from wavetpu.loadgen import cli as loadgen_cli

        return loadgen_cli.main(argv[1:])
    if argv and argv[0] == "ledger-report":
        # Compile-cost ledger aggregator + persistent-cache what-if +
        # warmup-manifest export (stdlib-only; never touches jax).
        from wavetpu.obs import ledger as compile_ledger

        return compile_ledger.main(argv[1:])
    if argv and argv[0] == "plan-report":
        # Measured speed-accuracy plan table: joins the accuracy ledger
        # with the compile ledger and the roofline model (stdlib-only
        # unless the roofline join needs perf constants; never jax).
        from wavetpu.obs import accuracy as obs_accuracy

        return obs_accuracy.main(argv[1:])
    if argv and argv[0] == "profile":
        # jax.profiler bracket around one solve or a serve window, so
        # the telemetry span annotations land in a device trace.
        from wavetpu.obs import perf as obs_perf

        return obs_perf.profile_main(argv[1:])
    if argv and argv[0] == "router":
        # Fleet front tier: ProgramKey-affinity proxy over N serve
        # replicas (stdlib-only; never touches jax - routers run on
        # hosts with no accelerator stack).
        from wavetpu.fleet import router as fleet_router

        return fleet_router.main(argv[1:])
    if argv and argv[0] == "fleet":
        # Fleet operations; currently `fleet roll`, the warm-handoff
        # zero-cold-compile rolling-deploy driver (stdlib-only).
        if len(argv) > 1 and argv[1] == "roll":
            from wavetpu.fleet import roll as fleet_roll

            return fleet_roll.main(argv[2:])
        print("error: fleet wants a subcommand: roll", file=sys.stderr)
        print("usage: wavetpu fleet roll ...", file=sys.stderr)
        return 2
    if argv and argv[0] == "warmup":
        # Manifest-driven replica warmup: pre-populate a persistent
        # program cache from a ledger-report warmup manifest.
        from wavetpu.serve import progcache

        return progcache.main(argv[1:])
    if "--version" in argv:
        from wavetpu import __version__

        print(f"wavetpu {__version__}")
        return 0
    try:
        pos, flags = _split_flags(argv)
        if flags.get("dtype", "f32") not in ("f32", "f64", "bf16"):
            raise ValueError(f"--dtype must be f32|f64|bf16, got {flags['dtype']}")
        if flags.get("kernel", "auto") not in ("auto", "roll", "pallas"):
            raise ValueError(
                f"--kernel must be auto|roll|pallas, got {flags['kernel']}"
            )
        scheme = flags.get("scheme", "standard")
        if scheme not in ("standard", "compensated"):
            raise ValueError(
                f"--scheme must be standard|compensated, got {scheme}"
            )
        fuse_steps = int(flags.get("fuse-steps", "1"))
        if fuse_steps < 1:
            raise ValueError(f"--fuse-steps must be >= 1, got {fuse_steps}")
        v_dtype_flag = flags.get("v-dtype")
        if v_dtype_flag is not None and v_dtype_flag not in ("f32", "bf16"):
            raise ValueError(
                f"--v-dtype must be f32|bf16, got {v_dtype_flag}"
            )
        if v_dtype_flag == "bf16" and (
            scheme != "compensated" or fuse_steps < 2
        ):
            raise ValueError(
                "--v-dtype bf16 is the increment-form bf16 mode: it "
                "requires --scheme compensated --fuse-steps K (the bf16 "
                "increment stream rides the velocity-form onion)"
            )
        if fuse_steps > 1:
            if flags.get("kernel", "auto") == "roll":
                raise ValueError("--fuse-steps needs the pallas kernel")
            if "mesh" in flags:
                # k-fusion composes with (MX, MY, 1) decompositions; z is
                # the lane dimension and stays whole
                # (solver/sharded_kfused.py).
                try:
                    _m = tuple(int(x) for x in flags["mesh"].split(","))
                except ValueError:
                    _m = ()
                if len(_m) == 3 and (
                    _m[2] != 1 or _m[0] < 1 or _m[1] < 1
                ):
                    raise ValueError(
                        "--fuse-steps supports (MX,MY,1) meshes "
                        f"(MX, MY >= 1, MZ = 1); got {flags['mesh']}"
                    )
            if "overlap" in flags:
                raise ValueError(
                    "--overlap applies to the 1-step sharded backend, not "
                    "--fuse-steps (whose exchange is amortized over k "
                    "layers)"
                )
        if "c2-field" in flags:
            if scheme == "compensated" and fuse_steps < 2:
                raise ValueError(
                    "--c2-field with the compensated scheme rides the "
                    "velocity-form onion: add --fuse-steps K (the 1-step "
                    "compensated kernels carry a scalar coefficient)"
                )
            if "phase-timing" in flags:
                raise ValueError(
                    "--phase-timing's probe times the constant-c step; "
                    "drop it for --c2-field runs"
                )
        if flags.get("backend") == "single" and "mesh" in flags:
            raise ValueError("--mesh contradicts --backend single")
        if flags.get("backend") == "single" and "overlap" in flags:
            raise ValueError("--overlap applies to the sharded backend")
        supervised = "ckpt-every" in flags
        if supervised:
            ckpt_every = int(flags["ckpt-every"])
            if ckpt_every < 1:
                raise ValueError(
                    f"--ckpt-every must be >= 1, got {ckpt_every}"
                )
            if "stop-step" in flags:
                raise ValueError(
                    "--ckpt-every supervises the run to completion; it "
                    "is exclusive with --stop-step (preempt a supervised "
                    "run with SIGTERM instead)"
                )
        else:
            for dep in ("ckpt-dir", "retries", "max-amp", "no-watchdog"):
                if dep in flags:
                    raise ValueError(
                        f"--{dep} requires --ckpt-every S (the "
                        f"supervised-solve mode)"
                    )
        sup_retries = int(flags.get("retries", "0"))
        if sup_retries < 0:
            raise ValueError(f"--retries must be >= 0, got {sup_retries}")
        sup_max_amp = (
            float(flags["max-amp"]) if "max-amp" in flags else None
        )
        if sup_max_amp is not None and not sup_max_amp > 0:
            raise ValueError(
                f"--max-amp must be > 0, got {sup_max_amp}"
            )
        if "resume" in flags:
            if "stop-step" in flags:
                raise ValueError("--resume and --stop-step are exclusive")
            problem = None  # comes from the checkpoint
        else:
            problem = Problem.from_argv(pos)
        stop_step = int(flags["stop-step"]) if "stop-step" in flags else None
        if stop_step is not None and not (
            1 <= stop_step <= problem.timesteps
        ):
            raise ValueError(
                f"--stop-step must be in [1, {problem.timesteps}], "
                f"got {stop_step}"
            )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        print(
            "usage: wavetpu N Np Lx Ly Lz [T] [timesteps] | "
            "wavetpu serve [...] | "
            "wavetpu trace-report [TRACE.jsonl ...] [--dir DIR ...] | "
            "wavetpu loadgen generate|replay|gate [...] | "
            "wavetpu ledger-report DIR [...] | "
            "wavetpu plan-report DIR [...] | "
            "wavetpu profile --out DIR ARGS... | "
            "wavetpu warmup --manifest MANIFEST.json [...] | "
            "wavetpu --version\n"
            "       wavetpu N Np Lx Ly Lz [T] [timesteps] "
            "[--backend auto|single|sharded] [--mesh MX,MY,MZ] "
            "[--dtype f32|f64|bf16] [--kernel auto|roll|pallas] "
            "[--fuse-steps K] [--scheme standard|compensated] "
            "[--v-dtype f32|bf16] [--c2-field PRESET|FILE.npy] "
            "[--overlap] [--no-errors] [--phase-timing] [--profile DIR] "
            "[--debug-nans] [--distributed] [--stop-step S] "
            "[--save-state PATH] [--resume PATH] "
            "[--ckpt-every S] [--ckpt-dir DIR] [--retries N] "
            "[--max-amp X] [--no-watchdog] [--telemetry-dir DIR] "
            "[--out-dir DIR] [--platform NAME]",
            file=sys.stderr,
        )
        return 2

    resume_state = None
    resume_is_sharded = False
    rotation_root = None
    if "resume" in flags:
        import os as _os

        from wavetpu.io import checkpoint as _ckpt
        from wavetpu.run import supervisor as _sup

        if _sup.looks_like_rotation_root(flags["resume"]):
            # A --ckpt-dir rotation root: follow its `latest` pointer to
            # the newest checkpoint (and remember the root so a
            # supervised resume keeps rotating in place).
            rotation_root = flags["resume"]
            resolved = _sup.resolve_latest(rotation_root)
            if resolved is None:
                print(
                    f"error: {rotation_root} holds no resumable "
                    f"checkpoint",
                    file=sys.stderr,
                )
                return 2
            flags["resume"] = resolved
        resume_is_sharded = _os.path.isdir(flags["resume"])
        try:
            if resume_is_sharded:
                if flags.get("backend") == "single":
                    print(
                        "error: checkpoint is a per-shard directory; "
                        "--backend single cannot resume it",
                        file=sys.stderr,
                    )
                    return 2
                # Meta only (numpy): the shard arrays are loaded after the
                # jax platform is configured below.
                problem, _start, _ck_mesh, _ck_dtype, _ck_scheme = (
                    _ckpt.load_sharded_meta(flags["resume"])
                )
                if "mesh" in flags and tuple(
                    int(x) for x in flags["mesh"].split(",")
                ) != _ck_mesh:
                    print(
                        f"error: --mesh contradicts the checkpoint's mesh "
                        f"{_ck_mesh}",
                        file=sys.stderr,
                    )
                    return 2
                if fuse_steps > 1 and _ck_mesh[2] != 1:
                    print(
                        f"error: --fuse-steps supports (MX,MY,1) meshes; "
                        f"the checkpoint was saved on {_ck_mesh}",
                        file=sys.stderr,
                    )
                    return 2
            else:
                if flags.get("backend") == "sharded" or "mesh" in flags:
                    print(
                        "error: checkpoint is a single-device .npz; "
                        "--backend sharded/--mesh cannot resume it",
                        file=sys.stderr,
                    )
                    return 2
                problem, _u_prev0, _u_cur0, _start = _ckpt.load_checkpoint(
                    flags["resume"]
                )
                _ck_scheme = _ckpt.checkpoint_scheme(flags["resume"])
                _ck_aux = (
                    _ckpt.load_checkpoint_aux(flags["resume"])
                    if _ck_scheme == "compensated"
                    else None
                )
                resume_state = (_u_prev0, _u_cur0, _start)
        except Exception as e:
            # OSError, KeyError, ValueError, zipfile.BadZipFile (truncated
            # .npz from a mid-save preemption - the exact case --resume
            # exists for), ... all mean the same thing to the user.
            print(f"error: cannot load checkpoint: {e}", file=sys.stderr)
            return 2

    distributed = "distributed" in flags
    # Courant printout before solving (openmp_sol.cpp:214, mpi_new.cpp:404).
    # Under --distributed it waits until the process index is known so only
    # process 0 speaks (rank-0 gating, mpi_new.cpp:356-371).
    if not distributed:
        print(f"C = {problem.courant:.6g}")

    import os

    import jax
    import jax.numpy as jnp

    from wavetpu import jaxcache

    if "platform" in flags:
        jax.config.update("jax_platforms", flags["platform"])
    cache_dir = jaxcache.configure()
    if "debug-nans" in flags:
        jax.config.update("jax_debug_nans", True)

    if distributed:
        dist_kwargs = {}
        addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
        if addr:
            # Explicit env-var cluster (the CPU smoke-test path and any
            # launcher that exports these); without them initialize()
            # auto-detects TPU pod / GKE / SLURM environments.
            dist_kwargs = dict(
                coordinator_address=addr,
                num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
                process_id=int(os.environ["JAX_PROCESS_ID"]),
            )
        jax.distributed.initialize(**dist_kwargs)
    is_main = jax.process_index() == 0
    say = print if is_main else (lambda *a, **k: None)
    if distributed:
        say(f"C = {problem.courant:.6g}")

    dtype = {
        "f32": jnp.float32,
        "f64": jnp.float64,
        "bf16": jnp.bfloat16,
    }[flags.get("dtype", "f32")]
    resume_dtype_name = None
    if resume_state is not None:
        resume_dtype_name = resume_state[1].dtype.name
    elif resume_is_sharded:
        resume_dtype_name = _ck_dtype
    if dtype == jnp.float64 or (
        "dtype" not in flags and resume_dtype_name == "float64"
    ):
        # Without x64, device_put would silently canonicalize a checkpointed
        # f64 state to f32 and break the bitwise-equal-resume guarantee.
        jax.config.update("jax_enable_x64", True)
    compute_errors = "no-errors" not in flags
    out_dir = flags.get("out-dir", ".")

    n_devices = len(jax.devices())
    backend = flags.get("backend", "auto")
    mesh_shape = None
    if "mesh" in flags:
        mesh_shape = tuple(int(x) for x in flags["mesh"].split(","))
        if len(mesh_shape) != 3:
            print("error: --mesh wants MX,MY,MZ", file=sys.stderr)
            return 2
        backend = "sharded"
    elif resume_is_sharded:
        backend = "sharded"
    elif resume_state is not None:
        backend = "single"
    elif backend == "auto":
        backend = "sharded" if n_devices > 1 else "single"
    if fuse_steps > 1:
        # k-fusion goes sharded only on EXPLICIT request (--mesh MX,1,1,
        # --backend sharded, or a sharded checkpoint); plain auto stays
        # single-device, preserving the K=1 CLI's behavior.
        explicit_sharded = (
            "mesh" in flags or resume_is_sharded
            or flags.get("backend") == "sharded"
        )
        backend = "sharded" if explicit_sharded else "single"
        _grid = (
            (mesh_shape or (_ck_mesh if resume_is_sharded else None)
             or (n_devices, 1, 1)) if backend == "sharded" else (1, 1, 1)
        )
        _even_x = (
            problem.N % _grid[0] == 0
            and (problem.N // _grid[0]) % fuse_steps == 0
        )
        if (
            problem.N % _grid[1]
            or problem.N // _grid[1] < fuse_steps
            or (_grid[1] > 1 and not _even_x)
        ):
            print(
                f"error: --fuse-steps {fuse_steps} must fit the y depth "
                f"N/MY = {problem.N}/{_grid[1]}; on 2D meshes it must "
                f"also divide the x depth N/MX = {problem.N}/{_grid[0]} "
                f"(uneven N is supported on (MX,1,1) meshes)",
                file=sys.stderr,
            )
            return 2
        if not _even_x:
            if scheme == "compensated":
                print(
                    f"error: compensated k-fusion requires MX | N and "
                    f"--fuse-steps {fuse_steps} | N/MX "
                    f"(N={problem.N}, MX={_grid[0]})",
                    file=sys.stderr,
                )
                return 2
            if "phase-timing" in flags:
                print(
                    "error: --phase-timing's k-fused probe covers even "
                    "decompositions (k | N/MX); drop it for uneven N",
                    file=sys.stderr,
                )
                return 2
            # Uneven x decomposition: verify a pad-and-mask layout
            # exists BEFORE compiling anything (solver/sharded_kfused.py
            # handles the actual march; a (1,1,1) grid covers the
            # single-device k-does-not-divide-N case).
            from wavetpu.solver import sharded_kfused as _sk

            try:
                _sk.uneven_layout(problem, fuse_steps, _grid[0])
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2

    c2_field = None
    if "c2-field" in flags:
        import numpy as np

        from wavetpu.kernels import stencil_ref

        spec = flags["c2-field"]
        # Preset table shared with the serving API
        # (stencil_ref.make_preset_c2tau2_field): one source of truth,
        # so a preset name means the same physics on both surfaces.
        if spec in stencil_ref.C2_PRESET_NAMES:
            c2_field = stencil_ref.make_preset_c2tau2_field(problem, spec)
        else:
            try:
                arr = np.load(spec)
            except Exception as e:
                print(
                    f"error: --c2-field {spec!r} is neither a preset "
                    f"({', '.join(sorted(stencil_ref.C2_PRESET_NAMES))}) "
                    f"nor a loadable .npy file: {e}",
                    file=sys.stderr,
                )
                return 2
            if arr.shape != (problem.N,) * 3:
                print(
                    f"error: --c2-field array shape {arr.shape} != "
                    f"{(problem.N,) * 3} (c^2 values on the fundamental "
                    f"grid)",
                    file=sys.stderr,
                )
                return 2
            c2_field = np.asarray(arr, np.float64) * problem.tau**2
        if compute_errors:
            # The analytic oracle only holds for constant speed; a report
            # of "errors" against it would be meaningless.  The constant
            # preset keeps the same contract for uniformity (its library
            # collapse to a2tau2 is pinned by tests/test_variable_c.py).
            say("errors: disabled (--c2-field has no analytic oracle)")
            compute_errors = False

    kernel = resolve_kernel(
        flags.get("kernel", "auto"), jax.default_backend()
    )
    if fuse_steps > 1:
        kernel = "pallas"  # k-fusion IS a pallas kernel (interpret off-TPU)
    if "resume" in flags:
        # A checkpoint is resumed under the scheme it was saved with; a
        # contradicting explicit --scheme is a user error.
        if "scheme" in flags and scheme != _ck_scheme:
            print(
                f"error: checkpoint was saved with scheme {_ck_scheme}; "
                f"--scheme {scheme} cannot resume it",
                file=sys.stderr,
            )
            return 2
        scheme = _ck_scheme
    # Scheme-conditional flag checks run HERE - after a resumed run has
    # inherited its scheme from the checkpoint - so they also cover
    # `--resume comp_ck --phase-timing` etc., not just explicit --scheme.
    if scheme == "compensated":
        bad = None
        if flags.get("dtype") == "bf16":
            bad = ("--dtype bf16 (compensated requires an f32/f64 carrier; "
                   "for a bf16 increment stream use --v-dtype bf16)")
        elif "overlap" in flags:
            bad = "--overlap"
        elif "phase-timing" in flags and fuse_steps < 2:
            # The velocity-form probe covers the k-fused program only
            # (solver/timing.py); a 1-step compensated solve still has no
            # probe, and reporting the standard step's numbers against it
            # would describe a program that never ran.
            bad = ("--phase-timing (the compensated probe covers "
                   "--fuse-steps K programs; the 1-step scheme has none)")
        elif "c2-field" in flags and fuse_steps < 2:
            # Covers `--resume comp_ck --c2-field ...` without
            # --fuse-steps: the scheme arrives from the checkpoint after
            # the flag-level check, which only sees scheme == "standard".
            bad = ("--c2-field without --fuse-steps K (the 1-step "
                   "compensated kernels carry a scalar coefficient)")
        elif fuse_steps > 1 and (
            problem.N % _grid[0]
            or (problem.N // _grid[0]) % fuse_steps
        ):
            # Covers `--resume comp_ck --fuse-steps K` with K (or MX)
            # not dividing: the scheme arrives from the checkpoint AFTER
            # the flag-level divisibility check, which only sees
            # scheme == "standard" there.
            bad = (f"--fuse-steps {fuse_steps} (compensated k-fusion "
                   f"requires MX | N and K | N/MX; N={problem.N}, "
                   f"MX={_grid[0]})")
        if bad:
            print(
                f"error: {bad} is not available for the compensated "
                f"scheme",
                file=sys.stderr,
            )
            return 2
    say(f"kernel: {kernel}")
    say(f"scheme: {scheme}")
    if fuse_steps > 1:
        say(f"fuse-steps: {fuse_steps}")
    overlap = "overlap" in flags

    profile_dir = flags.get("profile")
    if profile_dir and is_main:
        # jax.profiler hook (SURVEY section 5 tracing row): full XLA device
        # traces; the phase probes give the summary split, this gives the
        # op-level picture.
        jax.profiler.start_trace(profile_dir)

    from wavetpu.obs import tracing as _tracing

    telemetry = None
    if "telemetry-dir" in flags and is_main:
        # Unified telemetry: spans to DIR/trace.jsonl + heartbeat
        # registry snapshots (docs/observability.md).  Spans open
        # jax.profiler.TraceAnnotations, so with --profile the
        # application structure lands inside the device trace too.
        from wavetpu.obs import telemetry as _telemetry

        telemetry = _telemetry.start(flags["telemetry-dir"])
        say(f"telemetry: {flags['telemetry-dir']}")
    xla_cache_hits = None
    if cache_dir is not None and is_main:
        # Solo solvers jit internally (no executable object to adopt),
        # so persistence here is JAX's own compilation cache.  The hit
        # counter marks the ledger entry `source: disk` when the cache
        # actually served this solve's compile.
        xla_cache_hits = jaxcache.shared_xla_hit_counter()
    solve_span = _tracing.begin_span(
        "cli.solve", backend=backend, scheme=scheme, kernel=kernel,
        fuse_steps=fuse_steps, n=problem.N,
        timesteps=problem.timesteps, supervised=supervised,
        resumed="resume" in flags,
    )

    def _abort_telemetry():
        # Error exits after telemetry started must still emit the open
        # span and the final heartbeat (atexit only covers process
        # death, not in-process callers like the tests).
        _tracing.end_span(solve_span, aborted=True)
        if telemetry is not None:
            telemetry.stop()

    try:
        if backend == "sharded" and resume_is_sharded:
            # Shared load for both sharded resume paths (1-step and k-fused).
            from wavetpu.io import checkpoint as _ckpt

            try:
                (problem, _u_prev0, _u_cur0, _start, _ck_mesh,
                 _ck_scheme, _ck_aux) = (
                    _ckpt.load_sharded_checkpoint(flags["resume"])
                )
            except Exception as e:
                # Missing/truncated shard files, step/meta mismatch from a
                # mid-save preemption, or too few devices for the stored
                # mesh - same clean exit as a corrupt .npz.
                print(f"error: cannot load checkpoint: {e}", file=sys.stderr)
                _abort_telemetry()
                return 2
            resume_dtype = (
                dtype if "dtype" in flags else jnp.dtype(_u_cur0.dtype)
            )

        sup_out = None
        if supervised:
            # Supervised solve (run/supervisor.py): every solver path below
            # has a supervised twin - chunked march through cached chunk
            # programs, rotating checkpoints, watchdog, signal handling.
            from wavetpu.run import supervisor as _sup

            ckpt_dir = flags.get("ckpt-dir") or rotation_root
            if not ckpt_dir:
                print(
                    "error: --ckpt-every needs --ckpt-dir DIR (or --resume "
                    "of an existing rotation root)",
                    file=sys.stderr,
                )
                _abort_telemetry()
                return 2
            spec_vdtype = None
            spec_carry = True
            sup_state = None
            sup_start = None
            sup_mesh = mesh_shape
            sup_dtype = dtype
            if scheme == "compensated" and fuse_steps > 1 and \
                    "resume" not in flags:
                v_bf16 = flags.get("v-dtype") == "bf16"
                spec_vdtype = jnp.bfloat16 if v_bf16 else None
                spec_carry = not v_bf16
            def _comp_resume_state(u_cur0, aux, st_dtype):
                # Shared bf16-increment detection: a bf16 v stream beside a
                # non-bf16 carrier marks the carry-less increment form
                # (k-fused only); the sidecar must record the mode that ran.
                _v, _c = aux
                inc = (
                    fuse_steps > 1
                    and jnp.dtype(_v.dtype) == jnp.bfloat16
                    and jnp.dtype(st_dtype) != jnp.bfloat16
                )
                if inc:
                    flags["v-dtype"] = "bf16"
                return (
                    (u_cur0, _v, None if inc else _c),
                    jnp.bfloat16 if inc else None,
                    not inc,
                )

            if "resume" in flags:
                if resume_is_sharded:
                    sup_dtype = resume_dtype
                    sup_mesh = _ck_mesh
                    sup_start = _start
                    if scheme == "compensated":
                        sup_state, spec_vdtype, spec_carry = (
                            _comp_resume_state(_u_cur0, _ck_aux, sup_dtype)
                        )
                    else:
                        sup_state = (_u_prev0, _u_cur0)
                else:
                    u_prev0, u_cur0, sup_start = resume_state
                    sup_dtype = (
                        dtype if "dtype" in flags
                        else jnp.dtype(u_cur0.dtype)
                    )
                    if scheme == "compensated":
                        sup_state, spec_vdtype, spec_carry = (
                            _comp_resume_state(u_cur0, _ck_aux, sup_dtype)
                        )
                    else:
                        sup_state = (u_prev0, u_cur0)
            if backend == "sharded":
                if sup_mesh is None and fuse_steps > 1:
                    sup_mesh = (n_devices, 1, 1)
                if sup_mesh is None:
                    from wavetpu.core.grid import choose_mesh_shape

                    shape = choose_mesh_shape(n_devices)
                else:
                    shape = sup_mesh
                n_procs = shape[0] * shape[1] * shape[2]
            else:
                sup_mesh = None
                n_procs = 1
            variant = "TPU"
            spec = _sup.PathSpec(
                backend=backend,
                scheme=scheme,
                fuse_steps=fuse_steps,
                kernel=kernel,
                dtype=sup_dtype,
                v_dtype=spec_vdtype,
                carry=spec_carry,
                mesh_shape=sup_mesh,
                c2tau2_field=c2_field,
                compute_errors=compute_errors,
                overlap=overlap,
            )
            opts = _sup.SupervisorOptions(
                ckpt_every=ckpt_every,
                ckpt_dir=ckpt_dir,
                retries=sup_retries,
                watchdog="no-watchdog" not in flags,
                max_amp=sup_max_amp,
            )
            sup_out = _sup.supervise(
                problem, spec, opts, state=sup_state, start_step=sup_start
            )
            result = sup_out.result
            say(
                f"supervisor: {sup_out.status}; "
                f"{sup_out.checkpoints_written} checkpoint(s), "
                f"{sup_out.retries_used} retr"
                f"{'y' if sup_out.retries_used == 1 else 'ies'}, "
                f"overhead {sup_out.overhead_seconds * 1000:.0f}ms"
            )
        elif backend == "sharded" and fuse_steps > 1 and \
                scheme == "compensated":
            # Distributed velocity-form flagship ((MX, 1, 1) meshes).
            from wavetpu.solver import kfused_comp

            if resume_is_sharded:
                _v, _c = _ck_aux
                inc = (
                    jnp.dtype(_v.dtype) == jnp.bfloat16
                    and jnp.dtype(resume_dtype) != jnp.bfloat16
                )
                if inc:
                    flags["v-dtype"] = "bf16"
                result = kfused_comp.resume_kfused_comp_sharded(
                    problem,
                    _u_cur0,
                    _v,
                    None if inc else _c,
                    start_step=_start,
                    mesh_shape=_ck_mesh,
                    dtype=resume_dtype,
                    k=fuse_steps,
                    compute_errors=compute_errors,
                    v_dtype=jnp.bfloat16 if inc else None,
                    c2tau2_field=c2_field,
                )
                shape = _ck_mesh
            else:
                shape = mesh_shape or (n_devices, 1, 1)
                v_bf16 = flags.get("v-dtype") == "bf16"
                result = kfused_comp.solve_kfused_comp_sharded(
                    problem,
                    mesh_shape=shape,
                    dtype=dtype,
                    k=fuse_steps,
                    compute_errors=compute_errors,
                    stop_step=stop_step,
                    v_dtype=jnp.bfloat16 if v_bf16 else None,
                    carry=not v_bf16,
                    c2tau2_field=c2_field,
                )
            n_procs = shape[0] * shape[1] * shape[2]
            variant = "TPU"
        elif backend == "sharded" and fuse_steps > 1:
            from wavetpu.solver import sharded_kfused

            if resume_is_sharded:
                result = sharded_kfused.resume_sharded_kfused(
                    problem,
                    _u_prev0,
                    _u_cur0,
                    start_step=_start,
                    mesh_shape=_ck_mesh,
                    dtype=resume_dtype,
                    k=fuse_steps,
                    compute_errors=compute_errors,
                    c2tau2_field=c2_field,
                )
                shape = _ck_mesh
            else:
                shape = mesh_shape or (n_devices, 1, 1)
                result = sharded_kfused.solve_sharded_kfused(
                    problem,
                    mesh_shape=shape,
                    dtype=dtype,
                    k=fuse_steps,
                    compute_errors=compute_errors,
                    stop_step=stop_step,
                    c2tau2_field=c2_field,
                )
            n_procs = shape[0] * shape[1] * shape[2]
            variant = "TPU"
        elif backend == "sharded":
            from wavetpu.solver import sharded

            if resume_is_sharded:
                _v, _c = _ck_aux if _ck_aux is not None else (None, None)
                result = sharded.resume_sharded(
                    problem,
                    _u_prev0,
                    _u_cur0,
                    start_step=_start,
                    mesh_shape=_ck_mesh,
                    dtype=resume_dtype,
                    kernel=kernel,
                    overlap=overlap,
                    compute_errors=compute_errors,
                    scheme=scheme,
                    comp_v=_v,
                    comp_carry=_c,
                    c2tau2_field=c2_field,
                )
                shape = _ck_mesh
            else:
                result = sharded.solve_sharded(
                    problem,
                    mesh_shape=mesh_shape,
                    dtype=dtype,
                    compute_errors=compute_errors,
                    kernel=kernel,
                    overlap=overlap,
                    stop_step=stop_step,
                    scheme=scheme,
                    c2tau2_field=c2_field,
                )
                from wavetpu.core.grid import choose_mesh_shape

                shape = mesh_shape or choose_mesh_shape(n_devices)
            n_procs = shape[0] * shape[1] * shape[2]
            variant = "TPU"
        else:
            from wavetpu.solver import leapfrog

            step_fn = None
            interpret = jax.default_backend() != "tpu"
            if kernel == "pallas":
                from wavetpu.kernels import stencil_pallas

                step_fn = stencil_pallas.make_step_fn(
                    interpret=interpret, c2tau2_field=c2_field
                )
            elif c2_field is not None:
                from wavetpu.kernels import stencil_ref as _sr

                step_fn = _sr.make_variable_c_step(c2_field)
            if resume_state is not None:
                u_prev0, u_cur0, start = resume_state
                # Unless --dtype was given explicitly, resume in the dtype the
                # checkpoint was saved with - casting would break the
                # bitwise-equal-resume guarantee (io/checkpoint.py).
                resume_dtype = (
                    dtype if "dtype" in flags else jnp.dtype(u_cur0.dtype)
                )
                if scheme == "compensated" and fuse_steps > 1:
                    from wavetpu.solver import kfused_comp

                    _v, _c = _ck_aux
                    # A bf16 increment stream marks the carry-less
                    # increment-form checkpoint; its stored carry (zeros) is
                    # dropped.
                    inc = (
                        jnp.dtype(_v.dtype) == jnp.bfloat16
                        and jnp.dtype(resume_dtype) != jnp.bfloat16
                    )
                    if inc:
                        # The sidecar must record the mode that actually ran,
                        # not the (absent) flag.
                        flags["v-dtype"] = "bf16"
                    result = kfused_comp.resume_kfused_comp(
                        problem,
                        u_cur0,
                        _v,
                        None if inc else _c,
                        start_step=start,
                        dtype=resume_dtype,
                        k=fuse_steps,
                        compute_errors=compute_errors,
                        interpret=interpret,
                        v_dtype=jnp.bfloat16 if inc else None,
                        c2tau2_field=c2_field,
                    )
                elif scheme == "compensated":
                    comp_step_fn = None
                    if kernel == "pallas":
                        from wavetpu.kernels import stencil_pallas as _sp

                        comp_step_fn = _sp.make_compensated_step_fn(
                            interpret=interpret
                        )
                    _v, _c = _ck_aux
                    result = leapfrog.resume_compensated(
                        problem,
                        u_cur0,
                        _v,
                        _c,
                        start_step=start,
                        dtype=resume_dtype,
                        comp_step_fn=comp_step_fn,
                        compute_errors=compute_errors,
                    )
                elif fuse_steps > 1 and problem.N % fuse_steps:
                    # Uneven single-device k-fusion runs the pad-and-mask
                    # path on a (1,1,1) grid (bitwise equal to the 1-step
                    # pallas march on real planes).
                    from wavetpu.solver import sharded_kfused

                    result = sharded_kfused.resume_sharded_kfused(
                        problem,
                        u_prev0,
                        u_cur0,
                        start_step=start,
                        n_shards=1,
                        dtype=resume_dtype,
                        k=fuse_steps,
                        compute_errors=compute_errors,
                        interpret=interpret,
                        c2tau2_field=c2_field,
                    )
                elif fuse_steps > 1:
                    from wavetpu.solver import kfused

                    result = kfused.resume_kfused(
                        problem,
                        u_prev0,
                        u_cur0,
                        start_step=start,
                        dtype=resume_dtype,
                        k=fuse_steps,
                        compute_errors=compute_errors,
                        interpret=interpret,
                        c2tau2_field=c2_field,
                    )
                else:
                    result = leapfrog.resume(
                        problem,
                        u_prev0,
                        u_cur0,
                        start_step=start,
                        dtype=resume_dtype,
                        step_fn=step_fn,
                        compute_errors=compute_errors,
                    )
            elif scheme == "compensated" and fuse_steps > 1:
                from wavetpu.solver import kfused_comp

                v_bf16 = flags.get("v-dtype") == "bf16"
                result = kfused_comp.solve_kfused_comp(
                    problem,
                    dtype=dtype,
                    k=fuse_steps,
                    compute_errors=compute_errors,
                    stop_step=stop_step,
                    interpret=interpret,
                    v_dtype=jnp.bfloat16 if v_bf16 else None,
                    carry=not v_bf16,
                    c2tau2_field=c2_field,
                )
            elif scheme == "compensated":
                comp_step_fn = None
                if kernel == "pallas":
                    comp_step_fn = stencil_pallas.make_compensated_step_fn(
                        interpret=interpret
                    )
                result = leapfrog.solve_compensated(
                    problem,
                    dtype=dtype,
                    comp_step_fn=comp_step_fn,
                    compute_errors=compute_errors,
                    stop_step=stop_step,
                )
            elif fuse_steps > 1 and problem.N % fuse_steps:
                from wavetpu.solver import sharded_kfused

                result = sharded_kfused.solve_sharded_kfused(
                    problem,
                    n_shards=1,
                    dtype=dtype,
                    k=fuse_steps,
                    compute_errors=compute_errors,
                    stop_step=stop_step,
                    interpret=interpret,
                    c2tau2_field=c2_field,
                )
            elif fuse_steps > 1:
                from wavetpu.solver import kfused

                result = kfused.solve_kfused(
                    problem,
                    dtype=dtype,
                    k=fuse_steps,
                    compute_errors=compute_errors,
                    stop_step=stop_step,
                    interpret=interpret,
                    c2tau2_field=c2_field,
                )
            else:
                result = leapfrog.solve(
                    problem,
                    dtype=dtype,
                    step_fn=step_fn,
                    compute_errors=compute_errors,
                    stop_step=stop_step,
                )
            n_procs = 1
            variant = "TPU"

        # Roofline attribution on the cli.solve span: read back the
        # gauges record_solve just stamped at the solver entry point
        # (ONE computation, no second model that could drift), under
        # the same path label the solver used.  Traced runs only -
        # untraced runs skip even the lookup.
        span_extra = {}
        if _tracing.enabled():
            try:
                from wavetpu.obs.registry import get_registry as _greg

                if backend == "sharded":
                    _perf_path = (
                        ("kfused_comp_sharded"
                         if scheme == "compensated"
                         else "sharded_kfused")
                        if fuse_steps > 1 else "sharded"
                    )
                else:
                    _perf_path = (
                        ("kfused_comp" if scheme == "compensated"
                         else "kfused")
                        if fuse_steps > 1
                        else ("compensated" if scheme == "compensated"
                              else "leapfrog")
                    )
                _reg = _greg()
                _gbps = _reg.gauge(
                    "wavetpu_solve_model_gbps", "", ("path",)
                ).value(path=_perf_path)
                if _gbps:
                    # The fraction gauge is only set on a chip with a
                    # published peak (obs/perf.peak_gbps).
                    span_extra = {
                        "model_gbps": _gbps,
                        "roofline_fraction": _reg.gauge(
                            "wavetpu_solve_roofline_fraction", "",
                            ("path",)
                        ).value(path=_perf_path) or None,
                    }
            except Exception:
                pass  # the X-ray must never fail a finished solve
        _tracing.end_span(
            solve_span, final_step=result.final_step,
            gcells_per_s=round(result.gcells_per_second, 3),
            **span_extra,
        )
        # Compile-cost ledger entry for the solo solve (no-op without
        # --telemetry-dir): `init_seconds` is the CLI's compile proxy -
        # grid init + build + XLA compile.
        from wavetpu.obs import ledger as _ledger

        if _ledger.enabled():
            try:
                _dtype_names = {
                    "float32": "f32", "float64": "f64",
                    "bfloat16": "bf16",
                }
                _ledger.record_compile(_ledger.solo_key(
                    problem, scheme,
                    "kfused" if fuse_steps > 1 else kernel, fuse_steps,
                    _dtype_names.get(
                        jnp.dtype(result.u_cur.dtype).name, "f32"
                    ),
                    c2_field is not None, compute_errors,
                    mesh=shape if backend == "sharded" else None,
                ), result.init_seconds, source=(
                    # The persistent XLA cache serves inside init (no
                    # adoptable executable on the solo path): hits on
                    # the monitoring listener mean disk paid for this
                    # compile, so the ledger attributes it there.
                    "disk" if (xla_cache_hits is not None
                               and xla_cache_hits.hits > 0)
                    else ("fresh" if xla_cache_hits is not None
                          else None)
                ))
            except Exception:
                pass  # ledger bookkeeping must never fail the run

        if "save-state" in flags:
            from wavetpu.io import checkpoint as _ckpt

            if backend == "sharded":
                # Multi-process aware internally: each process writes only its
                # addressable shards, meta is gated on process 0.
                ck_path = _ckpt.save_sharded_checkpoint(
                    flags["save-state"], result
                )
                say(f"checkpoint: {ck_path}")
            elif is_main:
                # Single-device state is fully replicated; one writer suffices
                # (concurrent np.savez to one path is not atomic).
                ck_path = _ckpt.save_checkpoint(flags["save-state"], result)
                say(f"checkpoint: {ck_path}")

        if profile_dir and is_main:
            jax.profiler.stop_trace()
            say(f"profile trace: {profile_dir}")

        exchange_seconds = loop_seconds = None
        probe_steps = None
        if "phase-timing" in flags:
            from wavetpu.solver import timing

            # `shape` is the mesh the solve actually ran on (incl. a resumed
            # checkpoint's mesh); the probe must time the same program.
            pb = timing.measure_phase_breakdown(
                problem,
                mesh_shape=shape if backend == "sharded" else (1, 1, 1),
                dtype=dtype,
                kernel=kernel,
                overlap=overlap,
                fuse_steps=fuse_steps,
                scheme=scheme,
                v_dtype=(
                    jnp.bfloat16 if flags.get("v-dtype") == "bf16" else None
                ),
            )
            exchange_seconds = pb.exchange_seconds
            loop_seconds = pb.loop_seconds
            probe_steps = pb.steps_measured

        if is_main:
            from wavetpu.io import report

            path = report.write_report(
                result,
                out_dir=out_dir,
                n_procs=n_procs,
                variant=variant,
                errors_computed=compute_errors,
                exchange_seconds=exchange_seconds,
                loop_seconds=loop_seconds,
                probe_steps=probe_steps,
                run_config={
                    "backend": backend,
                    "kernel": kernel,
                    "scheme": scheme,
                    "fuse_steps": fuse_steps,
                    "mesh": list(shape) if backend == "sharded" else None,
                    # The state's actual dtype (a resumed run inherits the
                    # checkpoint's, which may differ from the flag default).
                    "dtype": jnp.dtype(result.u_cur.dtype).name,
                    "v_dtype": flags.get("v-dtype"),
                    "c2_field": flags.get("c2-field"),
                    "distributed": distributed,
                    "resumed": "resume" in flags,
                    "supervised": supervised,
                    "ckpt_every": ckpt_every if supervised else None,
                    "supervisor_status": (
                        sup_out.status if sup_out is not None else None
                    ),
                },
            )
        say(f"grids initialized in {int(result.init_seconds * 1000)}ms")
        say(
            f"numerical solution calculated in "
            f"{int(result.solve_seconds * 1000)}ms"
        )
        if exchange_seconds is not None:
            say(f"total ICI exchange time: {int(exchange_seconds * 1000)}ms")
            say(f"total loop time: {int(loop_seconds * 1000)}ms")
        if compute_errors:
            say(f"max abs error: {result.abs_errors.max():.6g}")
        say(f"throughput: {result.gcells_per_second:.3f} Gcell-updates/s")
        if is_main:
            say(f"report: {path}")
        if sup_out is not None and sup_out.status != "complete":
            # Orchestration contract: distinct exit codes (3 = requeue with
            # --resume, 4 = page an operator) and the resumable path in the
            # output (docs/robustness.md).
            if sup_out.status == "preempted":
                say(f"preempted: checkpointed at step {sup_out.final_step}")
            else:
                say(
                    f"watchdog: numerical-health trip "
                    f"(guarded amax {sup_out.amax_last:g}); "
                    f"last good step {sup_out.final_step}"
                )
            if sup_out.checkpoint_path:
                say(f"resumable checkpoint: {sup_out.checkpoint_path}")
            if telemetry is not None:
                telemetry.stop()
            return sup_out.exit_code
        if telemetry is not None:
            telemetry.stop()
        return 0
    except BaseException:
        # A crash mid-dispatch (XLA error, bad mesh, report I/O)
        # must still emit the open cli.solve span and the final
        # heartbeat, and must not leave the process tracer bound to
        # this run's trace file: in-process callers (tests, library
        # use of cli.main) never reach the atexit net, and their
        # next cli.main call must not inherit a stale tracer.
        # (Span end and telemetry.stop() are both idempotent, so a
        # raise after the success-path end_span is safe too.)
        _abort_telemetry()
        raise


if __name__ == "__main__":
    sys.exit(main())
