"""Temporally fused k-step solver over an (MX, MY, 1)-sharded device mesh.

Composes the repo's two flagship mechanisms: the k-step VMEM-onion kernel
(solver/kfused.py - the single-chip HBM-traffic win) and the shard_map
decomposition with ppermute halo exchange (solver/sharded.py - the
reference's MPI role, mpi_new.cpp:324-372).  Exchanging k-deep ghosts per
k LAYERS amortizes the per-step latency cost of the reference's per-layer
exchange (mpi_new.cpp:327-352) by k - halo BYTES per layer stay the same,
messages drop k-fold.

Two kernel regimes, dispatched on the mesh:

 * **x-only** ((P, 1, 1)): y/z stay full-domain per shard, so the
   in-kernel y/z rolls and Dirichlet mask are exactly the single-device
   kernel's; one cyclic x-ppermute pair per field per k-block.
 * **x/y** ((MX, MY, 1)): each block is first extended with k cyclic
   ghost ROWS per y side (one y-ppermute pair), then the x ghost planes
   are ppermute'd FROM THE EXTENDED blocks - the diagonal corner data a
   2D onion needs arrives through that sequencing with no extra
   collectives.  The kernel keeps the extended y width constant (rolls
   still deliver neighbours for every onion-valid row; staleness creeps
   only through ghost rows that are never written back) and re-imposes
   the Dirichlet zero on the WRAPPED global y index, so evolved ghost
   copies of the y=0 stored plane stay zero.  Ops per valid element are
   identical to the single-device kernel's - results stay bitwise equal
   across every mesh shape (tests/test_sharded_kfused.py).

z stays unsharded (MZ = 1): z is the 128-lane dimension, and cutting it
would shrink every vector register tile; BASELINE's target meshes up to
256 chips factor as (MX, MY, 1) without it.

Per-layer L-inf errors: each shard's kernel emits (k, N/MX) per-x-plane
maxes over its y range, pmax'd over the y axis and concatenated along x
(out_spec P(None, "x")) into global (layer, N) rows; the tiny per-plane
rescale + interior mask run on the replicated result.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from wavetpu.core.grid import build_mesh
from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_pallas, stencil_ref
from wavetpu.obs import metrics as obs_metrics
from wavetpu.obs import tracing
from wavetpu.solver import kfused, leapfrog
from wavetpu.solver.leapfrog import SolveResult


def _is_even(problem: Problem, k: int, n_x: int) -> bool:
    """True when the x decomposition divides evenly (the point-to-point
    flagship path); False routes to the pad-and-mask path."""
    return problem.N % n_x == 0 and (problem.N // n_x) % k == 0


def uneven_layout(problem: Problem, k: int, n_x: int, itemsize: int = 4):
    """(bx, D, r) for the pad-and-mask x-only path.

    D is the uniform padded per-shard depth (a multiple of the slab
    depth bx, itself a multiple of k), chosen as the largest
    VMEM-fitting bx with D = bx * ceil(N / (MX * bx)).  r = N - (MX-1)*D
    is the last shard's real-plane count - the remainder-folding analog
    of the reference (mpi_sol.cpp:417-421).  Raises when no layout keeps
    every leading shard full AND the last shard non-empty (r >= 1): that
    means the mesh is too large for N at this k - use fewer shards.
    """
    n = problem.N
    best = None
    bx = k
    while bx <= 8:
        d = bx * (-(-n // (n_x * bx)))  # bx * ceil(n / (n_x * bx))
        r = n - (n_x - 1) * d
        fits = stencil_pallas.choose_kstep_block(
            n, k, itemsize, depth=d, ghosts=True
        )
        if r >= 1 and fits is not None and fits >= bx:
            best = (bx, d, r)
        bx *= 2
    if best is None:
        raise ValueError(
            f"no pad-and-mask layout for N={n} over {n_x} x-shards at "
            f"k={k}: every candidate leaves the last shard empty or "
            f"exceeds VMEM; use fewer shards or a smaller k"
        )
    return best


def _validate(problem: Problem, k: int, n_x: int, n_y: int = 1,
              c2tau2_field=None, compute_errors: bool = True):
    if c2tau2_field is not None and compute_errors:
        raise ValueError(
            "variable-c runs have no analytic oracle; pass "
            "compute_errors=False with c2tau2_field"
        )
    if k < 2:
        raise ValueError(f"k must be >= 2 (got {k})")
    if n_x < 1 or n_y < 1:
        raise ValueError(
            f"mesh axes must be >= 1 (got MX={n_x}, MY={n_y})"
        )
    if problem.N < k:
        raise ValueError(f"k={k} exceeds N={problem.N}")
    if not _is_even(problem, k, n_x):
        if n_y > 1:
            raise ValueError(
                f"2D-mesh k-fusion needs N % MX == 0 and k | N/MX "
                f"(N={problem.N}, MX={n_x}, k={k}); uneven N is "
                f"supported on (MX, 1, 1) meshes"
            )
        uneven_layout(problem, k, n_x)  # raises if no layout exists
    if problem.N % n_y:
        raise ValueError(
            f"y-sharded k-fusion needs N % y-shards == 0 "
            f"(N={problem.N}, y-shards={n_y})"
        )
    if problem.N // n_y < k:
        raise ValueError(
            f"k={k} exceeds the y shard depth {problem.N // n_y} "
            f"(the k-row ghost strip must fit one neighbour)"
        )


def _assemble_errors(oracle_parts, dmax_rows, rmax_rows):
    """Global per-layer abs/rel errors from (layers, N) plane-max rows.

    Thin adapter over the single source of the error-rescale contract
    (kfused._oracle_parts / _block_errors): the same exact-zero guards and
    x!=0 interior mask, applied to all layers' rows at once (ctk is just
    longer)."""
    _, ct, _, _, xmask, inv_absx = oracle_parts
    return kfused._block_errors(
        dmax_rows, rmax_rows, ct[: dmax_rows.shape[0]], xmask, inv_absx
    )


def _make_runner(
    problem: Problem,
    mesh,
    shard_grid: Tuple[int, int],
    dtype,
    k: int,
    compute_errors: bool,
    nsteps: int,
    start_step: Optional[int],
    block_x: Optional[int],
    interpret: bool,
    has_field: bool = False,
    chunk_len: Optional[int] = None,
):
    """One jitted program: [bootstrap +] k-block scan + 1-step remainder.

    `shard_grid` = (n_x, n_y) mesh extents.  n_y == 1 runs the x-only
    kernel (in-shard y rolls ARE the boundary condition); n_y > 1 extends
    each block with k ghost rows per side via a cyclic y-ppermute pair and
    runs the xy kernel - the x ghosts are then sliced FROM the extended
    blocks, which ships the diagonal corners without extra collectives.

    `start_step=None` builds the from-scratch solver (bootstrap included);
    an int builds the resume program re-entering at that layer; with
    `chunk_len` set (start_step None) the runner is the supervised chunk
    program `run(u_prev, u, start, ...)` marching exactly chunk_len
    layers from a RUNTIME start (run/supervisor.py's cached program).
    All use the same local march so the per-layer op sequence is
    identical (the bitwise-resume invariant, solver/kfused.py).

    With `has_field` the c^2tau^2 field rides as an extra P("x","y")
    runtime argument; being time-invariant, its y extension and x-ghost
    exchange are hoisted OUT of the layer scan (once per solve per
    needed ghost depth: k for the blocks, 1 for bootstrap/remainder).
    """
    n_x, n_y = shard_grid
    f = stencil_ref.compute_dtype(dtype)
    nl = problem.N // n_x
    nl_y = problem.N // n_y
    oracle_parts = kfused._oracle_parts(problem, f)
    sx, ct, syz, rsyz, xmask, inv_absx = oracle_parts
    sxct_all = ct[:, None] * sx[None, :]            # (T+1, N)
    perm_fwd = [(i, (i + 1) % n_x) for i in range(n_x)]
    perm_bwd = [(i, (i - 1) % n_x) for i in range(n_x)]
    perm_fwd_y = [(i, (i + 1) % n_y) for i in range(n_y)]
    perm_bwd_y = [(i, (i - 1) % n_y) for i in range(n_y)]
    coeff = problem.a2tau2
    if chunk_len is None:
        start = 1 if start_step is None else start_step
        nblocks = (nsteps - start) // k
        rem = (nsteps - start) - nblocks * k
    else:
        nblocks = chunk_len // k
        rem = chunk_len - nblocks * k

    def ghosts(a, depth):
        """(lo, hi) ghost planes from the cyclic x-neighbours."""
        lo = lax.ppermute(a[-depth:], "x", perm_fwd)
        hi = lax.ppermute(a[:depth], "x", perm_bwd)
        return lo, hi

    def extend_y(a, depth):
        """Block extended with `depth` cyclic ghost rows per y side."""
        lo = lax.ppermute(a[:, -depth:], "y", perm_fwd_y)
        hi = lax.ppermute(a[:, :depth], "y", perm_bwd_y)
        return jnp.concatenate([lo, a, hi], axis=1)

    def field_pack(fld, kk):
        """(block_or_ext, x-ghost pair) of the time-invariant field at
        ghost depth kk - built once per solve, outside the scan."""
        if fld is None:
            return None
        if n_y == 1:
            return fld, ghosts(fld, kk)
        fe = extend_y(fld, kk)
        return fe, ghosts(fe, kk)

    def kcall(syz_c, rsyz_c, u_prev, u, sxct_k, kk, with_errors, bxo,
              fp=None):
        c2b = fp[0] if fp is not None else None
        c2g = fp[1] if fp is not None else None
        if n_y == 1:
            return stencil_pallas.fused_kstep_sharded(
                u_prev, u, ghosts(u_prev, kk), ghosts(u, kk), syz_c,
                rsyz_c, sxct_k, k=kk, coeff=coeff, inv_h2=problem.inv_h2,
                c2tau2_block=c2b, c2_ghosts=c2g,
                block_x=bxo, interpret=interpret, with_errors=with_errors,
            )
        pe = extend_y(u_prev, kk)
        ce = extend_y(u, kk)
        y0 = lax.axis_index("y") * nl_y
        up, uc, dm, rm = stencil_pallas.fused_kstep_sharded_xy(
            pe, ce, ghosts(pe, kk), ghosts(ce, kk), syz_c, rsyz_c,
            sxct_k, y0, problem.N, k=kk, nl_y=nl_y, coeff=coeff,
            inv_h2=problem.inv_h2, c2tau2_ext=c2b, c2_ghosts=c2g,
            block_x=bxo, interpret=interpret,
            with_errors=with_errors,
        )
        if with_errors:
            dm = lax.pmax(dm, "y")
            rm = lax.pmax(rm, "y")
        return up, uc, dm, rm

    def layer_rows(syz_c, rsyz_c, u, sxct_row):
        """Bootstrap-layer rows (kfused._layer_rows_local), pmax'd across
        the y mesh axis on 2D meshes."""
        d, r = kfused._layer_rows_local(u, sxct_row, syz_c, rsyz_c, f)
        if n_y > 1:
            d = lax.pmax(d, "y")
            r = lax.pmax(r, "y")
        return d, r

    def local_march(syz_c, rsyz_c, u_prev, u, sxct_loc, first, fld=None):
        """Layers first+1..nsteps; returns carry + (rows_d, rows_r) for
        exactly nsteps - first layers."""
        rows_d, rows_r = [], []
        fp_k = field_pack(fld, k)
        fp_1 = field_pack(fld, 1) if rem else None

        def body(carry, nstart):
            u_prev, u = carry
            sxct_k = lax.dynamic_slice(sxct_loc, (nstart + 1, 0), (k, nl))
            up, uc, dm, rm = kcall(
                syz_c, rsyz_c, u_prev, u, sxct_k, k, compute_errors,
                block_x, fp_k,
            )
            if not compute_errors:
                dm = rm = jnp.zeros((k, nl), f)
            return (up, uc), (dm, rm)

        starts = first + k * jnp.arange(nblocks)
        (u_prev, u), (dmb, rmb) = lax.scan(body, (u_prev, u), starts)
        rows_d.append(dmb.reshape(-1, nl))
        rows_r.append(rmb.reshape(-1, nl))
        for t in range(rem):
            # == nsteps - rem + 1 + t on the full march; off `first` the
            # identical arithmetic also serves a traced chunk start.
            layer = jnp.asarray(first + nblocks * k + 1 + t, jnp.int32)
            sxct_1 = lax.dynamic_slice(
                sxct_loc, (layer, jnp.int32(0)), (1, nl)
            )
            u_prev, u, dm, rm = kcall(
                syz_c, rsyz_c, u_prev, u, sxct_1, 1, compute_errors, None,
                fp_1,
            )
            if not compute_errors:
                dm = rm = jnp.zeros((1, nl), f)
            rows_d.append(dm)
            rows_r.append(rm)
        return u_prev, u, jnp.concatenate(rows_d), jnp.concatenate(rows_r)

    state_spec = P("x", "y")
    rows_spec = P(None, "x")
    plane_spec = P("y", None)

    field_specs = (state_spec,) if has_field else ()

    if chunk_len is not None:
        assert start_step is None

        def local_chunk(u_prev, u, start, sxct_loc, syz_c, rsyz_c,
                        *fargs):
            return local_march(
                syz_c, rsyz_c, u_prev, u, sxct_loc, start,
                fargs[0] if has_field else None,
            )

        local_fn = jax.shard_map(
            local_chunk, mesh=mesh,
            in_specs=(state_spec, state_spec, P(), rows_spec, plane_spec,
                      plane_spec) + field_specs,
            out_specs=(state_spec, state_spec, rows_spec, rows_spec),
            check_vma=False,
        )

        def run_chunk(u_prev, u, start, *fargs):
            u_prev, u, dmax, rmax = local_fn(
                u_prev, u, start, sxct_all, syz, rsyz, *fargs
            )
            if compute_errors:
                ctk = lax.dynamic_slice(ct, (start + 1,), (chunk_len,))
                abs_e, rel_e = kfused._block_errors(
                    dmax, rmax, ctk, xmask, inv_absx
                )
            else:
                abs_e = rel_e = jnp.zeros((chunk_len,), f)
            return u_prev, u, abs_e, rel_e

        return jax.jit(run_chunk), ()

    if start_step is None:

        def local(u0, sxct_loc, syz_c, rsyz_c, *fargs):
            fld = fargs[0] if has_field else None
            # kcall returns (layer n+k-1, layer n+k, ...): the stepped
            # field u0 + C*lap(u0) is the SECOND output.  With a field
            # the same identity holds per point (s0 = u0 + c^2tau^2*lap),
            # so the bootstrap needs no half-field.
            _, s0, _, _ = kcall(
                syz_c, rsyz_c, u0, u0, jnp.zeros((1, nl), f), 1, False,
                None, field_pack(fld, 1),
            )
            u1 = (0.5 * (u0.astype(f) + s0.astype(f))).astype(dtype)
            if compute_errors:
                d1, r1 = layer_rows(syz_c, rsyz_c, u1, sxct_loc[1])
            else:
                d1 = r1 = jnp.zeros((1, nl), f)
            u_prev, u, rows_d, rows_r = local_march(
                syz_c, rsyz_c, u0, u1, sxct_loc, 1, fld
            )
            zero = jnp.zeros((1, nl), f)
            return (
                u_prev, u,
                jnp.concatenate([zero, d1, rows_d]),
                jnp.concatenate([zero, r1, rows_r]),
            )

        local_fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(state_spec, rows_spec, plane_spec, plane_spec)
            + field_specs,
            out_specs=(state_spec, state_spec, rows_spec, rows_spec),
            # vma inference cannot see through the pallas kernel's mixed
            # ghost/wraparound concat (same workaround as solver/timing.py)
            check_vma=False,
        )

        def run(*fargs):
            u0 = lax.with_sharding_constraint(
                leapfrog.initial_layer0(problem, dtype),
                NamedSharding(mesh, state_spec),
            )
            u_prev, u, dmax, rmax = local_fn(
                u0, sxct_all, syz, rsyz, *fargs
            )
            if compute_errors:
                abs_e, rel_e = _assemble_errors(oracle_parts, dmax, rmax)
            else:
                abs_e = rel_e = jnp.zeros((nsteps + 1,), f)
            return u_prev, u, abs_e, rel_e

        return jax.jit(run), ()

    def local_resume(u_prev, u, sxct_loc, syz_c, rsyz_c, *fargs):
        u_prev, u, rows_d, rows_r = local_march(
            syz_c, rsyz_c, u_prev, u, sxct_loc, start_step,
            fargs[0] if has_field else None,
        )
        head = jnp.zeros((start_step + 1, nl), f)
        return (
            u_prev, u,
            jnp.concatenate([head, rows_d]),
            jnp.concatenate([head, rows_r]),
        )

    local_fn = jax.shard_map(
        local_resume, mesh=mesh,
        in_specs=(state_spec, state_spec, rows_spec, plane_spec,
                  plane_spec) + field_specs,
        out_specs=(state_spec, state_spec, rows_spec, rows_spec),
        check_vma=False,
    )

    def run(u_prev, u, *fargs):
        u_prev, u, dmax, rmax = local_fn(u_prev, u, sxct_all, syz, rsyz,
                                         *fargs)
        if compute_errors:
            abs_e, rel_e = _assemble_errors(oracle_parts, dmax, rmax)
        else:
            abs_e = rel_e = jnp.zeros((nsteps + 1,), f)
        return u_prev, u, abs_e, rel_e

    return jax.jit(run), None


def _make_padded_runner(
    problem: Problem,
    mesh,
    n_x: int,
    dtype,
    k: int,
    compute_errors: bool,
    nsteps: int,
    start_step: Optional[int],
    block_x: Optional[int],
    interpret: bool,
    has_field: bool = False,
    chunk_len: Optional[int] = None,
):
    """Pad-and-mask x-only runner for uneven decompositions.

    Covers N % MX != 0 and/or k not dividing N/MX (the reference folds
    the remainder into the last rank, mpi_sol.cpp:417-421).  Every shard
    holds a uniform padded depth D; ghosts are true cyclic REAL planes,
    assembled from up to two source shards when the last shard owns
    fewer than k real planes (one extra two-hop ppermute pair, built
    only when r < k), and each block is locally extended to
    [lo(k) | D | junk(k)] with the hi ghost spliced at the real boundary
    (see stencil_pallas.fused_kstep_padded).  The runner's raw outputs
    are (MX*D, N, N) globals; solve/resume re-place them on the 1-step
    sharded path's Topology layout so checkpointing, gather_fundamental
    and every downstream consumer see the SAME convention as all other
    sharded results.

    Cost: the per-block ext assembly (concat + hi-ghost splice) is one
    extra memory pass over both fields per k layers (~+4/k field-streams
    per step).  Measured on v5e at N=510/1000 k=4: 26.9 Gcell/s vs 44.9
    for the even point-to-point path and 20.3 for the 1-step kernel -
    the fallback is still a clear win over not fusing.

    With `has_field` the c^2tau^2 field arrives zero-padded to the
    (MX*D, N, N) layout as an extra P("x") runtime argument; its
    extended form (lo ghosts | D | hi spliced, zero junk) is assembled
    ONCE per solve per ghost depth with exactly the state's machinery.
    """
    f = stencil_ref.compute_dtype(dtype)
    n = problem.N
    bx, d, r = uneven_layout(
        problem, k, n_x, jnp.dtype(dtype).itemsize
    )
    if block_x is not None:
        bx = block_x
        d = bx * (-(-n // (n_x * bx)))
        r = n - (n_x - 1) * d
        if r < 1 or d % bx or bx % k:
            raise ValueError(
                f"block_x={bx} gives no valid pad-and-mask layout for "
                f"N={n} over {n_x} shards at k={k}"
            )
    dg = n_x * d
    pad = dg - n
    sx, ct, syz, rsyz, xmask, inv_absx = kfused._oracle_parts(problem, f)
    zpad = jnp.zeros((pad,), f)
    sx_p = jnp.concatenate([sx, zpad])
    xmask_p = jnp.concatenate([xmask, jnp.zeros((pad,), bool)])
    inv_absx_p = jnp.concatenate([inv_absx, zpad])
    padded_parts = (sx_p, ct, syz, rsyz, xmask_p, inv_absx_p)
    sxct_all = ct[:, None] * sx_p[None, :]          # (T+1, MX*D)
    perm_fwd = [(i, (i + 1) % n_x) for i in range(n_x)]
    perm_bwd = [(i, (i - 1) % n_x) for i in range(n_x)]
    perm_fwd2 = [(i, (i + 2) % n_x) for i in range(n_x)]
    perm_bwd2 = [(i, (i - 2) % n_x) for i in range(n_x)]
    coeff = problem.a2tau2
    if chunk_len is None:
        start = 1 if start_step is None else start_step
        nblocks = (nsteps - start) // k
        rem = (nsteps - start) - nblocks * k
    else:
        nblocks = chunk_len // k
        rem = chunk_len - nblocks * k
    multi = n_x > 1

    def nm_scalar():
        if not multi:
            return jnp.int32(r)
        return jnp.where(
            lax.axis_index("x") == n_x - 1, r, d
        ).astype(jnp.int32)

    def ghosts_of(both, kk):
        """True cyclic real-plane ghosts of the leading-stacked fields
        (shape (F, D, N, N)).

        lo = the kk real planes globally preceding this shard's start,
        hi = the kk real planes following its real end.  When the last
        shard owns r < kk real planes, the seam windows span two source
        shards; the static r makes the piece sizes static, so two extra
        two-hop ppermutes + concats assemble them.
        """
        if not multi:
            lo = lax.dynamic_slice_in_dim(both, r - kk, kk, 1)
            hi = lax.slice_in_dim(both, 0, kk, axis=1)
            return lo, hi
        ai = lax.axis_index("x")
        tail_start = jnp.where(ai == n_x - 1, max(r - kk, 0), d - kk)
        tail = lax.dynamic_slice_in_dim(both, tail_start, kk, 1)
        head = lax.slice_in_dim(both, 0, kk, axis=1)
        lo = lax.ppermute(tail, "x", perm_fwd)
        hi = lax.ppermute(head, "x", perm_bwd)
        if r < kk:
            lo2 = lax.ppermute(tail, "x", perm_fwd2)
            hi2 = lax.ppermute(head, "x", perm_bwd2)
            # Shard 0's lo window = [N-kk, N): the last shard's r real
            # planes preceded by the second-to-last shard's tail.
            lo0 = jnp.concatenate([lo2[:, r:], lo[:, :r]], axis=1)
            lo = jnp.where(ai == 0, lo0, lo)
            # Shard MX-2's hi window = the last shard's r real planes
            # followed by shard 0's head (the cyclic wrap).
            him = jnp.concatenate([hi[:, :r], hi2[:, :kk - r]], axis=1)
            hi = jnp.where(ai == n_x - 2, him, hi)
        return lo, hi

    def ghosts(up, uc, kk):
        return ghosts_of(jnp.stack([up, uc]), kk)

    def field_ext(fld, nm, kk):
        """The field's (D + 2kk, N, N) extended array - same lo-ghost /
        hi-splice / zero-junk layout as the state ext, assembled once per
        solve (the field is time-invariant)."""
        if fld is None:
            return None
        lo, hi = ghosts_of(fld[None], kk)
        return build_ext(fld, lo[0], hi[0], nm, kk)

    def build_ext(field, lo_f, hi_f, nm, kk):
        ny, nz = field.shape[1], field.shape[2]
        ext = jnp.concatenate(
            [lo_f, field, jnp.zeros((kk, ny, nz), field.dtype)], 0
        )
        z = jnp.int32(0)
        return lax.dynamic_update_slice(
            ext, hi_f, (jnp.int32(kk) + nm, z, z)
        )

    def kcall(syz_c, rsyz_c, up, uc, sxct_k, kk, with_err, ec2=None):
        nm = nm_scalar()
        lo, hi = ghosts(up, uc, kk)
        ep = build_ext(up, lo[0], hi[0], nm, kk)
        ec = build_ext(uc, lo[1], hi[1], nm, kk)
        return stencil_pallas.fused_kstep_padded(
            ep, ec, nm, syz_c, rsyz_c, sxct_k, k=kk, coeff=coeff,
            inv_h2=problem.inv_h2, ext_c2=ec2, block_x=bx,
            interpret=interpret, with_errors=with_err,
        )

    def layer_rows(syz_c, rsyz_c, u, sxct_row):
        return kfused._layer_rows_local(u, sxct_row, syz_c, rsyz_c, f)

    def local_march(syz_c, rsyz_c, u_prev, u, sxct_loc, first, fld=None):
        rows_d, rows_r = [], []
        nm = nm_scalar()
        ec2_k = field_ext(fld, nm, k)
        ec2_1 = field_ext(fld, nm, 1) if (fld is not None and rem) \
            else None

        def body(carry, nstart):
            u_prev, u = carry
            sxct_k = lax.dynamic_slice(sxct_loc, (nstart + 1, 0), (k, d))
            up, uc, dm, rm = kcall(
                syz_c, rsyz_c, u_prev, u, sxct_k, k, compute_errors,
                ec2_k,
            )
            if not compute_errors:
                dm = rm = jnp.zeros((k, d), f)
            return (up, uc), (dm, rm)

        starts = first + k * jnp.arange(nblocks)
        (u_prev, u), (dmb, rmb) = lax.scan(body, (u_prev, u), starts)
        rows_d.append(dmb.reshape(-1, d))
        rows_r.append(rmb.reshape(-1, d))
        for t in range(rem):
            # == nsteps - rem + 1 + t on the full march (traced-start
            # chunk form, as _make_runner).
            layer = jnp.asarray(first + nblocks * k + 1 + t, jnp.int32)
            sxct_1 = lax.dynamic_slice(
                sxct_loc, (layer, jnp.int32(0)), (1, d)
            )
            u_prev, u, dm, rm = kcall(
                syz_c, rsyz_c, u_prev, u, sxct_1, 1, compute_errors,
                ec2_1,
            )
            if not compute_errors:
                dm = rm = jnp.zeros((1, d), f)
            rows_d.append(dm)
            rows_r.append(rm)
        return u_prev, u, jnp.concatenate(rows_d), jnp.concatenate(rows_r)

    state_spec = P("x")
    rows_spec = P(None, "x")
    plane_spec = P(None, None)

    def assemble(dmax, rmax):
        if compute_errors:
            return _assemble_errors(padded_parts, dmax, rmax)
        z = jnp.zeros((nsteps + 1,), f)
        return z, z

    field_specs = (state_spec,) if has_field else ()

    if chunk_len is not None:
        assert start_step is None

        def local_chunk(u_prev, u, start, sxct_loc, syz_c, rsyz_c,
                        *fargs):
            return local_march(
                syz_c, rsyz_c, u_prev, u, sxct_loc, start,
                fargs[0] if has_field else None,
            )

        local_fn = jax.shard_map(
            local_chunk, mesh=mesh,
            in_specs=(state_spec, state_spec, P(), rows_spec, plane_spec,
                      plane_spec) + field_specs,
            out_specs=(state_spec, state_spec, rows_spec, rows_spec),
            check_vma=False,
        )

        def run_chunk(u_prev, u, start, *fargs):
            u_prev, u, dmax, rmax = local_fn(
                u_prev, u, start, sxct_all, syz, rsyz, *fargs
            )
            if compute_errors:
                ctk = lax.dynamic_slice(ct, (start + 1,), (chunk_len,))
                abs_e, rel_e = kfused._block_errors(
                    dmax, rmax, ctk, xmask_p, inv_absx_p
                )
            else:
                abs_e = rel_e = jnp.zeros((chunk_len,), f)
            return u_prev, u, abs_e, rel_e

        return jax.jit(run_chunk), (dg, pad)

    if start_step is None:

        def local(u0, sxct_loc, syz_c, rsyz_c, *fargs):
            fld = fargs[0] if has_field else None
            _, s0, _, _ = kcall(
                syz_c, rsyz_c, u0, u0, jnp.zeros((1, d), f), 1, False,
                field_ext(fld, nm_scalar(), 1),
            )
            u1 = (0.5 * (u0.astype(f) + s0.astype(f))).astype(dtype)
            if compute_errors:
                d1, r1 = layer_rows(syz_c, rsyz_c, u1, sxct_loc[1])
            else:
                d1 = r1 = jnp.zeros((1, d), f)
            u_prev, u, rows_d, rows_r = local_march(
                syz_c, rsyz_c, u0, u1, sxct_loc, 1, fld
            )
            zero = jnp.zeros((1, d), f)
            return (
                u_prev, u,
                jnp.concatenate([zero, d1, rows_d]),
                jnp.concatenate([zero, r1, rows_r]),
            )

        local_fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(state_spec, rows_spec, plane_spec, plane_spec)
            + field_specs,
            out_specs=(state_spec, state_spec, rows_spec, rows_spec),
            check_vma=False,
        )

        def run(*fargs):
            u0 = jnp.pad(
                leapfrog.initial_layer0(problem, dtype),
                ((0, pad), (0, 0), (0, 0)),
            )
            u0 = lax.with_sharding_constraint(
                u0, NamedSharding(mesh, state_spec)
            )
            u_prev, u, dmax, rmax = local_fn(
                u0, sxct_all, syz, rsyz, *fargs
            )
            abs_e, rel_e = assemble(dmax, rmax)
            return u_prev, u, abs_e, rel_e

        return jax.jit(run), (dg, pad)

    def local_resume(u_prev, u, sxct_loc, syz_c, rsyz_c, *fargs):
        u_prev, u, rows_d, rows_r = local_march(
            syz_c, rsyz_c, u_prev, u, sxct_loc, start_step,
            fargs[0] if has_field else None,
        )
        head = jnp.zeros((start_step + 1, d), f)
        return (
            u_prev, u,
            jnp.concatenate([head, rows_d]),
            jnp.concatenate([head, rows_r]),
        )

    local_fn = jax.shard_map(
        local_resume, mesh=mesh,
        in_specs=(state_spec, state_spec, rows_spec, plane_spec,
                  plane_spec) + field_specs,
        out_specs=(state_spec, state_spec, rows_spec, rows_spec),
        check_vma=False,
    )

    def run(u_prev, u, *fargs):
        u_prev, u, dmax, rmax = local_fn(u_prev, u, sxct_all, syz, rsyz,
                                         *fargs)
        abs_e, rel_e = assemble(dmax, rmax)
        return u_prev, u, abs_e, rel_e

    return jax.jit(run), (dg, pad)


def _to_topology_layout(u, problem: Problem, mesh, n_x: int):
    """Re-place a padded-runner global (MX*D, N, N) field on the standard
    Topology layout (MX*ceil(N/MX) planes, P(x,y,z)-sharded).

    The padded runner's D is kernel-driven (a multiple of bx) and differs
    from Topology's ceil block, so its outputs cannot be checkpointed
    per-shard as-is (slicing to N outside jit collapses the sharding and
    every device would claim shard starts (0,0,0)).  One device_put onto
    the canonical layout makes uneven k-fused results indistinguishable
    from every other sharded result: save_sharded_checkpoint,
    gather_fundamental and resume all consume them unchanged.
    """
    from wavetpu.core.grid import AXIS_NAMES, Topology

    topo = Topology(N=problem.N, mesh_shape=(n_x, 1, 1))
    padx = topo.padded[0] - problem.N
    a = jnp.pad(u[: problem.N], ((0, padx), (0, 0), (0, 0)))
    return jax.device_put(a, NamedSharding(mesh, P(*AXIS_NAMES)))


def _resolve_grid(mesh_shape, n_shards, devices):
    """(n_x, n_y) from an explicit (MX, MY, 1) mesh_shape, the x-only
    n_shards shorthand, or all visible devices."""
    if mesh_shape is not None:
        if len(mesh_shape) != 3 or mesh_shape[2] != 1:
            raise ValueError(
                f"k-fusion supports (MX, MY, 1) meshes, got {mesh_shape}"
            )
        return mesh_shape[0], mesh_shape[1]
    if n_shards is None:
        n_shards = len(devices)
    return n_shards, 1


def solve_sharded_kfused(
    problem: Problem,
    n_shards: Optional[int] = None,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    block_x: Optional[int] = None,
    interpret: Optional[bool] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[Tuple[int, int, int]] = None,
    c2tau2_field=None,
) -> SolveResult:
    """k-fused solve over an (MX, MY, 1) mesh; reference timing phases as
    `leapfrog.solve`.  `n_shards` is the x-only shorthand (MX, 1, 1);
    `mesh_shape` selects a 2D decomposition (defaults to all devices on
    the x axis).  `c2tau2_field` threads the variable-c slab through the
    sharded onion (sharded on the same mesh, k-deep ghost planes
    exchanged once per solve; compute_errors=False required)."""
    if devices is None:
        devices = jax.devices()
    n_x, n_y = _resolve_grid(mesh_shape, n_shards, devices)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _validate(problem, k, n_x, n_y, c2tau2_field, compute_errors)
    nsteps = problem.timesteps if stop_step is None else stop_step
    if not 1 <= nsteps <= problem.timesteps:
        raise ValueError(
            f"stop_step must be in [1, {problem.timesteps}], got {nsteps}"
        )
    mesh = build_mesh((n_x, n_y, 1), devices[: n_x * n_y])
    has_field = c2tau2_field is not None
    f = stencil_ref.compute_dtype(dtype)
    run_params = ()
    if _is_even(problem, k, n_x):
        runner, _ = _make_runner(
            problem, mesh, (n_x, n_y), dtype, k, compute_errors, nsteps,
            None, block_x, interpret, has_field,
        )
        sliced = False
        if has_field:
            run_params = (jax.device_put(
                jnp.asarray(c2tau2_field, dtype=f),
                NamedSharding(mesh, P("x", "y")),
            ),)
    else:
        runner, (dg, _) = _make_padded_runner(
            problem, mesh, n_x, dtype, k, compute_errors, nsteps,
            None, block_x, interpret, has_field,
        )
        sliced = True
        if has_field:
            fld = jnp.pad(
                jnp.asarray(c2tau2_field, dtype=f),
                ((0, dg - problem.N), (0, 0), (0, 0)),
            )
            run_params = (jax.device_put(
                fld, NamedSharding(mesh, P("x"))
            ),)
    (u_prev, u_cur, abs_all, rel_all), init_s, solve_s = (
        leapfrog._timed_compile_run(
            runner, run_params, sync=lambda out: np.asarray(out[2]),
            path="sharded_kfused", scheme="standard", k=k, n=problem.N,
        )
    )
    if sliced:
        u_prev = _to_topology_layout(u_prev, problem, mesh, n_x)
        u_cur = _to_topology_layout(u_cur, problem, mesh, n_x)
    with tracing.span("solve.finish", path="sharded_kfused"):
        result = SolveResult(
            problem=problem,
            u_prev=u_prev,
            u_cur=u_cur,
            abs_errors=np.asarray(abs_all, dtype=np.float64),
            rel_errors=np.asarray(rel_all, dtype=np.float64),
            init_seconds=init_s,
            solve_seconds=solve_s,
            steps_computed=stop_step,
            final_step=(stop_step if stop_step is not None
                        else problem.timesteps),
        )
        obs_metrics.record_solve(
            result, "sharded_kfused", k=k,
            with_field=c2tau2_field is not None, block_x=block_x,
            # Roofline model: the block is chosen against the SHARD depth
            # with ghost buffers in the pipeline, same as the kernel's own
            # chooser call above (ceil covers the pad-and-mask layout).
            depth=-(-problem.N // n_x), ghosts=True,
        )
    return result


def resume_sharded_kfused(
    problem: Problem,
    u_prev,
    u_cur,
    start_step: int,
    n_shards: Optional[int] = None,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: Optional[bool] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[Tuple[int, int, int]] = None,
    c2tau2_field=None,
) -> SolveResult:
    """Re-enter the sharded k-fused march at layer `start_step`.

    `u_prev`/`u_cur` may be global jax.Arrays (a live sharded result) or
    host arrays (a loaded checkpoint); they are placed P("x", "y") on the
    mesh (see `solve_sharded_kfused` for the mesh parameters).  A
    variable-c checkpoint resumes under the same re-passed
    `c2tau2_field` (checkpoints store state, not the field).
    """
    if devices is None:
        devices = jax.devices()
    n_x, n_y = _resolve_grid(mesh_shape, n_shards, devices)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _validate(problem, k, n_x, n_y, c2tau2_field, compute_errors)
    nsteps = problem.timesteps
    if not 1 <= start_step <= nsteps:
        raise ValueError(
            f"start_step must be in [1, {nsteps}], got {start_step}"
        )
    mesh = build_mesh((n_x, n_y, 1), devices[: n_x * n_y])
    sliced = not _is_even(problem, k, n_x)
    has_field = c2tau2_field is not None
    f = stencil_ref.compute_dtype(dtype)
    if not sliced:
        runner, _ = _make_runner(
            problem, mesh, (n_x, n_y), dtype, k, compute_errors, nsteps,
            start_step, block_x, interpret, has_field,
        )
        sharding = NamedSharding(mesh, P("x", "y"))
        args = (
            jax.device_put(jnp.asarray(u_prev, dtype), sharding),
            jax.device_put(jnp.asarray(u_cur, dtype), sharding),
        )
        if has_field:
            args = args + (jax.device_put(
                jnp.asarray(c2tau2_field, dtype=f), sharding
            ),)
    else:
        runner, (dg, _) = _make_padded_runner(
            problem, mesh, n_x, dtype, k, compute_errors, nsteps,
            start_step, block_x, interpret, has_field,
        )
        sharding = NamedSharding(mesh, P("x"))
        padw = ((0, dg - problem.N), (0, 0), (0, 0))
        args = (
            jax.device_put(
                jnp.pad(jnp.asarray(u_prev, dtype)[: problem.N], padw),
                sharding,
            ),
            jax.device_put(
                jnp.pad(jnp.asarray(u_cur, dtype)[: problem.N], padw),
                sharding,
            ),
        )
        if has_field:
            args = args + (jax.device_put(
                jnp.pad(jnp.asarray(c2tau2_field, dtype=f), padw),
                sharding,
            ),)
    (u_p, u_c, abs_all, rel_all), init_s, solve_s = (
        leapfrog._timed_compile_run(
            runner, args, sync=lambda out: np.asarray(out[2]),
            path="sharded_kfused", scheme="standard", k=k, n=problem.N,
        )
    )
    if sliced:
        u_p = _to_topology_layout(u_p, problem, mesh, n_x)
        u_c = _to_topology_layout(u_c, problem, mesh, n_x)
    return SolveResult(
        problem=problem,
        u_prev=u_p,
        u_cur=u_c,
        abs_errors=np.asarray(abs_all, dtype=np.float64),
        rel_errors=np.asarray(rel_all, dtype=np.float64),
        init_seconds=init_s,
        solve_seconds=solve_s,
        steps_computed=nsteps - start_step,
        final_step=nsteps,
    )


def make_chunk_runner(
    problem: Problem,
    mesh,
    grid: Tuple[int, int],
    dtype=jnp.float32,
    length: int = 4,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: Optional[bool] = None,
    has_field: bool = False,
):
    """Fixed-length sharded k-fused re-entry for supervised solves.

    Returns `(runner, layout)` where `runner(u_prev, u_cur, start[,
    field])` marches layers start+1..start+length with a RUNTIME `start`
    (run/supervisor.py's cached chunk program).  On the even
    decomposition `layout` is None and state rides P("x","y") directly;
    on the pad-and-mask path `layout` is `(dg, pad)` and the caller
    feeds/receives the padded (MX*D, N, N) x-sharded globals (see
    `_make_padded_runner`; `_to_topology_layout` converts for
    checkpointing).
    """
    n_x, n_y = grid
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _validate(problem, k, n_x, n_y, None, True)
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length}")
    if _is_even(problem, k, n_x):
        runner, _ = _make_runner(
            problem, mesh, grid, dtype, k, compute_errors, None, None,
            block_x, interpret, has_field, chunk_len=length,
        )
        return runner, None
    runner, layout = _make_padded_runner(
        problem, mesh, n_x, dtype, k, compute_errors, None, None,
        block_x, interpret, has_field, chunk_len=length,
    )
    return runner, layout
