"""Pallas fused leapfrog stencil kernel - the TPU-native hot kernel.

The analog of the reference's CUDA kernel layer (`calculate_layer`,
cuda_sol_kernels.cu:24-47, and the BC/seam handling of `prepare_layer`,
cuda_sol_kernels.cu:230-259) redesigned for the TPU memory system instead of
translated:

 * The grid marches over slabs of `block_x` x-planes.  Each program reads its
   slab of u / u_prev plus exactly TWO single-plane x-halos fetched through
   wrap-around BlockSpec index maps ((i*bx - 1) mod N) - the periodic-x
   topology costs nothing and there is no seam special case (the fundamental
   (N, N, N) domain of `wavetpu.core.problem` has no duplicated plane).
 * y/z neighbours come from in-VMEM cyclic rolls (`pltpu.roll`): the y/z
   wrap delivers the stored zero Dirichlet plane, so one uniform data path
   covers interior + all boundaries, where the reference needs a separate
   boundary kernel with a face bitmask (and shipped a precedence bug in it,
   SURVEY.md section 2.4.1).
 * The Dirichlet re-zeroing of the y=0 / z=0 stored planes is fused as a
   mask on the result - no second kernel, no extra memory pass.
 * The update 2u - u_prev + c*lap and the boundary mask execute in f32 on
   the VPU regardless of the storage dtype, so a bf16 state (BASELINE.md
   stretch config) keeps an f32 update path.

Layout: z is the lane dimension (128), y the sublane dimension (8); an
(N, N) plane of f32 is tile-aligned for any N multiple of 128.  `block_x`
is chosen so the pipeline's working set fits comfortably in VMEM
(~16 MB/core).

Semantics are pinned to `stencil_ref.leapfrog_step` / `taylor_half_step`
(tested in tests/test_pallas.py, interpret mode on CPU plus allclose on
chip): identical inputs must agree to rounding error.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_ref

# Per-core VMEM working-set budget (bytes) used to pick block_x: the
# pipeline double-buffers (3*bx + 2) planes (u slab + u_prev slab + out slab
# + 2 halo planes), and the kernel body needs room again for temporaries
# (ext/lap).  The Mosaic scoped-vmem ceiling is raised to _VMEM_LIMIT
# accordingly (the default 16 MB rejects even a one-plane slab at N=512,
# and the overflow is not graceful: it NaN'd inside lax.scan in testing).
# bx=8 at N=512 measured fastest on v5e (20.3 Gcell/s vs 14.6 at bx=1).
_VMEM_BUDGET = 56 * 1024 * 1024
_VMEM_LIMIT = 100 * 1024 * 1024


def _choose_block_depth(
    depth: int,
    plane_elems: int,
    itemsize: int = 4,
    field_itemsize: Optional[int] = None,
    slabs: int = 3,
) -> int:
    """Largest power-of-two slab depth (<= 8) whose double-buffered pipeline
    working set fits the VMEM budget (and divides `depth`).

    The bx-deep buffers in flight are u_prev + u + out (state `itemsize`
    each) plus, for the variable-c kernel, the field slab at
    `field_itemsize` - the COMPUTE dtype's width (f32), which differs from
    the state width under bf16.  Getting the accounting wrong is a real
    cliff, not a tweak: the var-c kernel at N=512 ran 2.7x slower with the
    constant-kernel choice (bx=8, 68 MB pipeline) than with the correct
    bx=4 (measured 8.1 vs 19.5 Gcell/s on v5e).

    `plane_elems` is the (y, z) plane size in elements - n*n for the full
    fundamental domain, by*bz for a shard block.  `slabs` is the number of
    bx-deep state buffers in flight (3 for the standard kernel, 6 for the
    compensated one: u/v/carry in + out).
    """
    per_bx = slabs * itemsize + (field_itemsize or 0)  # bytes per plane
    halo = 2 * itemsize                             # two 1-plane halos
    bx = 1
    while (
        bx < 8
        and depth % (bx * 2) == 0
        and 2 * (per_bx * (bx * 2) + halo) * plane_elems <= _VMEM_BUDGET
    ):
        bx *= 2
    return bx


def choose_block_x(
    n: int, itemsize: int = 4, field_itemsize: Optional[int] = None
) -> int:
    """Slab depth for the single-device (N, N, N) kernels (see
    `_choose_block_depth`)."""
    return _choose_block_depth(n, n * n, itemsize, field_itemsize)


def _slab_laplacian(c, ulo_ref, uhi_ref, inv_h2, f):
    """7-pt Laplacian of a slab: x-neighbours from the halo-plane refs,
    y/z neighbours from in-VMEM cyclic rolls (the wrap delivers the stored
    zero Dirichlet plane / the periodic value - rolls ARE the BC)."""
    ix, iy, iz = (jnp.asarray(v, f) for v in inv_h2)
    # Halo planes stacked onto the slab (axis 0 is neither lane nor sublane,
    # so this is free of relayouts).
    ext = jnp.concatenate([ulo_ref[:].astype(f), c, uhi_ref[:].astype(f)], 0)
    lap = (ext[:-2] + ext[2:] - 2.0 * c) * ix
    # pltpu.roll wants non-negative shifts: roll by size-1 == roll by -1.
    ny, nz = c.shape[1], c.shape[2]
    lap = lap + (pltpu.roll(c, 1, 1) + pltpu.roll(c, ny - 1, 1) - 2.0 * c) * iy
    lap = lap + (pltpu.roll(c, 1, 2) + pltpu.roll(c, nz - 1, 2) - 2.0 * c) * iz
    return lap


def _finish_update(u_next, out_ref, f):
    """Fused Dirichlet mask + store: zero the stored y=0 / z=0 planes (the
    reference's whole `prepare_layer` pass, openmp_sol.cpp:104-112)."""
    shape = u_next.shape
    ym = lax.broadcasted_iota(jnp.int32, shape, 1) != 0
    zm = lax.broadcasted_iota(jnp.int32, shape, 2) != 0
    out_ref[:] = jnp.where(
        ym & zm, u_next, jnp.asarray(0.0, f)
    ).astype(out_ref.dtype)


def _step_kernel(uprev_ref, uc_ref, ulo_ref, uhi_ref, out_ref,
                 *, alpha, beta, coeff, inv_h2, compute_dtype):
    """One fused update slab: out = alpha*u - beta*u_prev + coeff*lap(u).

    (alpha, beta, coeff) = (2, 1, a2tau2)  -> leapfrog (openmp_sol.cpp:160)
    (alpha, beta, coeff) = (1, 0, a2tau2/2) -> layer-1 Taylor half-step
                                               (openmp_sol.cpp:137-144)
    """
    f = compute_dtype
    c = uc_ref[:].astype(f)
    lap = _slab_laplacian(c, ulo_ref, uhi_ref, inv_h2, f)
    u_next = jnp.asarray(alpha, f) * c + jnp.asarray(coeff, f) * lap
    if beta:
        u_next = u_next - jnp.asarray(beta, f) * uprev_ref[:].astype(f)
    _finish_update(u_next, out_ref, f)


def _var_step_kernel(c2_ref, uprev_ref, uc_ref, ulo_ref, uhi_ref, out_ref,
                     *, inv_h2, compute_dtype):
    """Variable-speed leapfrog slab: out = 2u + tau^2 c^2(x) lap(u) - u_prev.

    The c^2 tau^2 field rides its own slab input - the capability extension
    over the reference's hardcoded __constant__ a2 (cuda_sol_kernels.cu:3).
    The summation order (2u + coeff*lap) - u_prev matches `_sharded_kernel`'s
    field path and the k-step onion's variable-c substep, so variable-c
    layers are op-identical across the 1-step, sharded, and k-fused paths
    (the same bitwise-mixing contract as the constant-c kernels)."""
    f = compute_dtype
    c = uc_ref[:].astype(f)
    lap = _slab_laplacian(c, ulo_ref, uhi_ref, inv_h2, f)
    u_next = 2.0 * c + c2_ref[:].astype(f) * lap - uprev_ref[:].astype(f)
    _finish_update(u_next, out_ref, f)


def _specs(n: int, bx: int):
    """Slab + wrap-around halo BlockSpecs for an (N, N, N) field.

    Single-plane halos via wrap-around maps: with block shape (1, N, N)
    the x block index IS the plane index, so these express the cyclic
    neighbour relation directly (jnp mod is floor-mod: (0-1) % N = N-1).
    """
    slab = pl.BlockSpec((bx, n, n), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    lo = pl.BlockSpec((1, n, n), lambda i: ((i * bx - 1) % n, 0, 0),
                      memory_space=pltpu.VMEM)
    hi = pl.BlockSpec((1, n, n), lambda i: (((i + 1) * bx) % n, 0, 0),
                      memory_space=pltpu.VMEM)
    return slab, lo, hi


def _fused_step(u_prev, u, *, inv_h2, alpha=2.0, beta=1.0, coeff=None,
                c2tau2_field=None, block_x=None, interpret=False,
                compute_dtype=None):
    """Shared pallas_call wrapper for the constant- and variable-speed
    kernels; `c2tau2_field` selects the variable kernel (its slab is
    prepended as an extra input)."""
    n = u.shape[0]
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u.dtype)
    field_itemsize = (
        None if c2tau2_field is None else jnp.dtype(compute_dtype).itemsize
    )
    bx = block_x or choose_block_x(n, u.dtype.itemsize, field_itemsize)
    if n % bx:
        raise ValueError(f"block_x={bx} must divide N={n}")
    slab, lo, hi = _specs(n, bx)
    if c2tau2_field is None:
        kernel = functools.partial(
            _step_kernel, alpha=alpha, beta=beta, coeff=coeff,
            inv_h2=inv_h2, compute_dtype=compute_dtype,
        )
        in_specs, operands = [slab, slab, lo, hi], (u_prev, u, u, u)
    else:
        kernel = functools.partial(
            _var_step_kernel, inv_h2=inv_h2, compute_dtype=compute_dtype,
        )
        field = jnp.asarray(c2tau2_field, dtype=compute_dtype)
        in_specs = [slab, slab, slab, lo, hi]
        operands = (field, u_prev, u, u, u)
    return pl.pallas_call(
        kernel,
        grid=(n // bx,),
        in_specs=in_specs,
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="fused_step",
        interpret=interpret,
    )(*operands)


def leapfrog_step(u_prev, u, problem: Problem, *,
                  block_x=None, interpret=False):
    """Fused u_next = 2u - u_prev + a2tau2*lap(u) with Dirichlet re-imposed.

    Drop-in for `stencil_ref.leapfrog_step` (`make_solver(step_fn=...)`).
    """
    return _fused_step(
        u_prev, u, alpha=2.0, beta=1.0, coeff=problem.a2tau2,
        inv_h2=problem.inv_h2, block_x=block_x, interpret=interpret,
    )


def taylor_half_step(u0, problem: Problem, *, block_x=None, interpret=False):
    """Fused layer-1 bootstrap u1 = u0 + (a2tau2/2)*lap(u0).

    Drop-in for `stencil_ref.taylor_half_step`.
    """
    return _fused_step(
        u0, u0, alpha=1.0, beta=0.0, coeff=0.5 * problem.a2tau2,
        inv_h2=problem.inv_h2, block_x=block_x, interpret=interpret,
    )


def _ghost_lap(c, ulo_ref, uhi_ref, ghost_refs, need, inv_h2, f):
    """7-pt Laplacian of a shard slab with statically-specialized ghost
    handling (see `_sharded_kernel` for the per-axis semantics).

    `ghost_refs` is (xlo, xhi, ylo, yhi, zlo, zhi) with None entries on
    axes whose mesh dim is 1 (`need[a]` False).
    """
    xlo_ref, xhi_ref, ylo_ref, yhi_ref, zlo_ref, zhi_ref = ghost_refs
    shape = c.shape
    ix, iy, iz = (jnp.asarray(v, f) for v in inv_h2)
    i = pl.program_id(0)

    # x neighbours: slab halo planes, ghost-overridden at the grid edges.
    lo = ulo_ref[:].astype(f)
    hi = uhi_ref[:].astype(f)
    if need[0]:
        last = pl.num_programs(0) - 1
        lo = jnp.where(i == 0, xlo_ref[:].astype(f), lo)
        hi = jnp.where(i == last, xhi_ref[:].astype(f), hi)
    ext = jnp.concatenate([lo, c, hi], 0)
    lap = (ext[:-2] + ext[2:] - 2.0 * c) * ix

    # y/z neighbours: in-VMEM cyclic rolls (pltpu.roll wants non-negative
    # shifts: roll by size-1 == roll by -1), ghost-overridden at the wrap.
    ny, nz = shape[1], shape[2]
    dn, up = pltpu.roll(c, 1, 1), pltpu.roll(c, ny - 1, 1)
    if need[1]:
        iota_y = lax.broadcasted_iota(jnp.int32, shape, 1)
        dn = jnp.where(iota_y == 0, ylo_ref[:].astype(f), dn)
        up = jnp.where(iota_y == ny - 1, yhi_ref[:].astype(f), up)
    lap = lap + (dn + up - 2.0 * c) * iy
    dn, up = pltpu.roll(c, 1, 2), pltpu.roll(c, nz - 1, 2)
    if need[2]:
        iota_z = lax.broadcasted_iota(jnp.int32, shape, 2)
        dn = jnp.where(iota_z == 0, zlo_ref[:].astype(f), dn)
        up = jnp.where(iota_z == nz - 1, zhi_ref[:].astype(f), up)
    return lap + (dn + up - 2.0 * c) * iz


def _global_mask(off_ref, shape, pad, n_global, block_x):
    """Fused boundary/pad mask (reference: the whole prepare_layer pass,
    openmp_sol.cpp:104-112, plus pad-cell re-zeroing): the y/z Dirichlet
    zeroing (global index != 0) always, the global-index < N pad component
    only on axes that actually carry pad planes."""
    gy = off_ref[1] + lax.broadcasted_iota(jnp.int32, shape, 1)
    gz = off_ref[2] + lax.broadcasted_iota(jnp.int32, shape, 2)
    mask = (gy != 0) & (gz != 0)
    if pad[0]:
        gx = (
            off_ref[0] + pl.program_id(0) * block_x
            + lax.broadcasted_iota(jnp.int32, shape, 0)
        )
        mask &= gx < n_global
    if pad[1]:
        mask &= gy < n_global
    if pad[2]:
        mask &= gz < n_global
    return mask


def _take_ghost_refs(it, need):
    """Pull the present ghost refs off the operand iterator, None-filling
    the axes that need none (mesh dim 1)."""
    refs = []
    for a in range(3):
        if need[a]:
            refs.append(next(it))
            refs.append(next(it))
        else:
            refs.extend((None, None))
    return tuple(refs)


def _sharded_kernel(*refs, alpha, beta, coeff, has_field, need, pad,
                    n_global, block_x, inv_h2, compute_dtype):
    """Per-shard fused update slab - the distributed counterpart of
    `_step_kernel`, the analog of the reference's per-rank CUDA kernel
    launch (cuda_sol.cpp:381-443 driving calculate_layer,
    cuda_sol_kernels.cu:24-47).

    Statically specialized per axis on the mesh shape:

     * `need[a]` (mesh dim > 1): the axis's shard-boundary neighbours come
       from ppermute'd ghost operands - the x halo overrides the wraparound
       BlockSpec planes at the grid edges, y/z ghosts override the wrapped
       row/lane of the in-VMEM roll via an iota select.  On a 1-shard axis
       the in-shard wrap IS the global neighbour (periodic x / stored zero
       Dirichlet plane in y/z), so no ghost operands and no selects exist
       at all - a (1,1,1) mesh compiles to the single-device kernel's data
       path.
     * `pad[a]` (uneven shards): the global-index < N mask component only
       exists on axes that actually carry pad planes.

    All masking stays fused in the store: no HBM traffic.
    """
    f = compute_dtype
    it = iter(refs[:-1])
    out_ref = refs[-1]
    off_ref = next(it)
    c2_ref = next(it) if has_field else None
    uprev_ref = next(it)
    uc_ref = next(it)
    ulo_ref = next(it)
    uhi_ref = next(it)
    ghost_refs = _take_ghost_refs(it, need)

    c = uc_ref[:].astype(f)
    lap = _ghost_lap(c, ulo_ref, uhi_ref, ghost_refs, need, inv_h2, f)
    if has_field:
        u_next = jnp.asarray(alpha, f) * c + c2_ref[:].astype(f) * lap
    else:
        u_next = jnp.asarray(alpha, f) * c + jnp.asarray(coeff, f) * lap
    if beta:
        u_next = u_next - jnp.asarray(beta, f) * uprev_ref[:].astype(f)

    mask = _global_mask(off_ref, u_next.shape, pad, n_global, block_x)
    out_ref[:] = jnp.where(mask, u_next, jnp.asarray(0.0, f)).astype(
        out_ref.dtype
    )


def _sharded_comp_kernel(*refs, coeff, need, pad, n_global, block_x,
                         inv_h2, compute_dtype):
    """Per-shard fused compensated (Kahan) leapfrog slab - `_comp_step_kernel`
    with the sharded ghost handling and global mask of `_sharded_kernel`.
    Reads v/carry/u (+ghosts), writes u'/v'/carry' in one HBM pass."""
    f = compute_dtype
    it = iter(refs[:-3])
    u_out, v_out, carry_out = refs[-3:]
    off_ref = next(it)
    v_ref = next(it)
    carry_ref = next(it)
    uc_ref = next(it)
    ulo_ref = next(it)
    uhi_ref = next(it)
    ghost_refs = _take_ghost_refs(it, need)

    c = uc_ref[:].astype(f)
    lap = _ghost_lap(c, ulo_ref, uhi_ref, ghost_refs, need, inv_h2, f)
    d = jnp.asarray(coeff, f) * lap
    # Mask the increment (u/v/carry start masked and sums of masked fields
    # stay masked, stencil_ref.compensated_step) AND the stored u: the pad
    # plane of the input block holds the absorbed hi ghost on uneven axes
    # (halo.absorb_hi_ghosts) and must not leak into the carry state.  At
    # masked cells y = 0, so carry_next there is 0 regardless.
    mask = _global_mask(off_ref, d.shape, pad, n_global, block_x)
    d = jnp.where(mask, d, jnp.asarray(0.0, f))
    v_next = v_ref[:].astype(f) + d
    y = v_next - carry_ref[:].astype(f)
    t = c + y
    carry_next = (t - c) - y
    u_out[:] = jnp.where(mask, t, jnp.asarray(0.0, f)).astype(u_out.dtype)
    v_out[:] = v_next.astype(v_out.dtype)
    carry_out[:] = carry_next.astype(carry_out.dtype)


def _sharded_geometry(u, bx, mesh_shape, r_last):
    """BlockSpecs and per-axis static flags shared by the sharded kernels."""
    bx_tot, by, bz = u.shape
    need = tuple(m > 1 for m in mesh_shape)
    if r_last is None:
        pads = (False, False, False)
    else:
        pads = tuple(r != b for r, b in zip(r_last, u.shape))
    specs = dict(
        slab=pl.BlockSpec((bx, by, bz), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM),
        lo=pl.BlockSpec((1, by, bz),
                        lambda i: ((i * bx - 1) % bx_tot, 0, 0),
                        memory_space=pltpu.VMEM),
        hi=pl.BlockSpec((1, by, bz),
                        lambda i: (((i + 1) * bx) % bx_tot, 0, 0),
                        memory_space=pltpu.VMEM),
        gx=pl.BlockSpec((1, by, bz), lambda i: (0, 0, 0),
                        memory_space=pltpu.VMEM),
        gy=pl.BlockSpec((bx, 1, bz), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM),
        gz=pl.BlockSpec((bx, by, 1), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM),
        smem=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return need, pads, specs


def _append_ghosts(in_specs, operands, specs, need, ghosts):
    for needed, spec_name, (g_lo, g_hi) in zip(
        need, ("gx", "gy", "gz"), ghosts
    ):
        if needed:
            in_specs += [specs[spec_name], specs[spec_name]]
            operands += [g_lo, g_hi]


def _out_struct(u, shape=None, dtype=None):
    """Output aval matching the state it replaces (or the given
    shape/dtype override); under shard_map with check_vma it must declare
    which mesh axes it varies over."""
    shape = u.shape if shape is None else shape
    dtype = u.dtype if dtype is None else dtype
    vma = getattr(getattr(u, "aval", None), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def sharded_fused_step(u_prev, u, ghosts, offsets, n_global, *, inv_h2,
                       mesh_shape, r_last=None,
                       alpha=2.0, beta=1.0, coeff=None, c2tau2_block=None,
                       block_x=None, interpret=False, compute_dtype=None):
    """One fused leapfrog-form update of a shard block with pre-exchanged
    ghosts - the Pallas hot kernel of the distributed solver.

    Must run inside `shard_map`.  `ghosts` is `comm.halo.collect_ghosts`
    output ((xlo, xhi), (ylo, yhi), (zlo, zhi)); for an unevenly sharded
    axis the hi ghost must additionally be absorbed into the block first
    (`comm.halo.absorb_hi_ghosts`).  `offsets` is an int32 (3,) array of
    the shard's global cell offsets; `n_global` the fundamental N.
    `mesh_shape` / `r_last` drive the static per-axis specialization (see
    `_sharded_kernel`).  With `c2tau2_block` (this shard's slice of the
    tau^2 c^2 field) the variable-speed kernel runs and `coeff` is ignored.
    """
    bx_tot, by, bz = u.shape
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u.dtype)
    has_field = c2tau2_block is not None
    field_itemsize = (
        None if not has_field else jnp.dtype(compute_dtype).itemsize
    )
    bx = block_x or _choose_block_depth(
        bx_tot, by * bz, u.dtype.itemsize, field_itemsize
    )
    if bx_tot % bx:
        raise ValueError(f"block_x={bx} must divide shard depth {bx_tot}")
    need, pads, specs = _sharded_geometry(u, bx, mesh_shape, r_last)
    slab, lo, hi = specs["slab"], specs["lo"], specs["hi"]

    in_specs = [specs["smem"]]
    operands = [jnp.asarray(offsets, jnp.int32)]
    if has_field:
        in_specs.append(slab)
        operands.append(jnp.asarray(c2tau2_block, dtype=compute_dtype))
    in_specs += [slab, slab, lo, hi]
    operands += [u_prev, u, u, u]
    _append_ghosts(in_specs, operands, specs, need, ghosts)

    kernel = functools.partial(
        _sharded_kernel,
        alpha=alpha, beta=beta, coeff=coeff, has_field=has_field,
        need=need, pad=pads, n_global=n_global, block_x=bx,
        inv_h2=inv_h2, compute_dtype=compute_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=(bx_tot // bx,),
        in_specs=in_specs,
        out_specs=slab,
        out_shape=_out_struct(u),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="sharded_fused_step",
        interpret=interpret,
    )(*operands)


def sharded_compensated_step(u, v, carry, ghosts, offsets, n_global, *,
                             inv_h2, mesh_shape, r_last=None, coeff,
                             block_x=None, interpret=False,
                             compute_dtype=None):
    """Fused compensated (Kahan) leapfrog step of a shard block - the
    sharded counterpart of `compensated_step`, with ghosts/masking as in
    `sharded_fused_step`.  Returns (u', v', carry')."""
    bx_tot, by, bz = u.shape
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u.dtype)
    bx = block_x or _choose_block_depth(
        bx_tot, by * bz, u.dtype.itemsize, slabs=6
    )
    if bx_tot % bx:
        raise ValueError(f"block_x={bx} must divide shard depth {bx_tot}")
    need, pads, specs = _sharded_geometry(u, bx, mesh_shape, r_last)
    slab, lo, hi = specs["slab"], specs["lo"], specs["hi"]

    in_specs = [specs["smem"], slab, slab, slab, lo, hi]
    operands = [jnp.asarray(offsets, jnp.int32), v, carry, u, u, u]
    _append_ghosts(in_specs, operands, specs, need, ghosts)

    kernel = functools.partial(
        _sharded_comp_kernel,
        coeff=coeff, need=need, pad=pads, n_global=n_global, block_x=bx,
        inv_h2=inv_h2, compute_dtype=compute_dtype,
    )
    out = _out_struct(u)
    return pl.pallas_call(
        kernel,
        grid=(bx_tot // bx,),
        in_specs=in_specs,
        out_specs=[slab, slab, slab],
        out_shape=[out, out, out],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="sharded_compensated_step",
        interpret=interpret,
    )(*operands)


def _comp_step_kernel(v_ref, carry_ref, uc_ref, ulo_ref, uhi_ref,
                      u_out, v_out, carry_out,
                      *, coeff, inv_h2, compute_dtype):
    """Fused compensated (Kahan) incremental leapfrog slab.

    Semantics pinned to `stencil_ref.compensated_step`: the increment
    C*lap(u) accumulates in its own buffer and the u addition runs through
    a two-sum carry, keeping f32 rounding at the representation level (see
    that docstring for the measured numbers).  One kernel reads u (+2 halo
    planes), v, carry and writes all three successors - the whole step in
    a single HBM pass, where an unfused formulation would pay a second
    elementwise pass over four fields.
    """
    f = compute_dtype
    c = uc_ref[:].astype(f)
    lap = _slab_laplacian(c, ulo_ref, uhi_ref, inv_h2, f)
    d = jnp.asarray(coeff, f) * lap
    # Dirichlet mask on the increment only: u/v/carry start masked and
    # sums of masked fields stay masked (stencil_ref.compensated_step).
    ym = lax.broadcasted_iota(jnp.int32, d.shape, 1) != 0
    zm = lax.broadcasted_iota(jnp.int32, d.shape, 2) != 0
    d = jnp.where(ym & zm, d, jnp.asarray(0.0, f))
    v_next = v_ref[:].astype(f) + d
    y = v_next - carry_ref[:].astype(f)
    t = c + y
    carry_next = (t - c) - y
    u_out[:] = t.astype(u_out.dtype)
    v_out[:] = v_next.astype(v_out.dtype)
    carry_out[:] = carry_next.astype(carry_out.dtype)


def compensated_step(u, v, carry, problem: Problem, coeff=None, *,
                     block_x=None, interpret=False):
    """Fused (u, v, carry) -> (u', v', carry') compensated leapfrog step.

    Drop-in for `stencil_ref.compensated_step` (same signature semantics);
    `coeff` defaults to a2tau2, the layer-1 bootstrap passes a2tau2/2 with
    v = carry = 0.
    """
    n = u.shape[0]
    f = stencil_ref.compute_dtype(u.dtype)
    bx = block_x or _choose_block_depth(n, n * n, u.dtype.itemsize, slabs=6)
    if n % bx:
        raise ValueError(f"block_x={bx} must divide N={n}")
    slab, lo, hi = _specs(n, bx)
    kernel = functools.partial(
        _comp_step_kernel,
        coeff=problem.a2tau2 if coeff is None else coeff,
        inv_h2=problem.inv_h2, compute_dtype=f,
    )
    out = jax.ShapeDtypeStruct(u.shape, u.dtype)
    return pl.pallas_call(
        kernel,
        grid=(n // bx,),
        in_specs=[slab, slab, slab, lo, hi],
        out_specs=[slab, slab, slab],
        out_shape=[out, out, out],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="compensated_step",
        interpret=interpret,
    )(v, carry, u, u, u)


def make_compensated_step_fn(block_x=None, interpret=False):
    """A `(u, v, carry, problem, coeff) -> (u', v', carry')` closure for
    `leapfrog.make_compensated_solver(comp_step_fn=...)`."""

    def step(u, v, carry, problem, coeff=None):
        return compensated_step(
            u, v, carry, problem, coeff,
            block_x=block_x, interpret=interpret,
        )

    return step


# --------------------------------------------------------------------------
# Temporally fused k-step kernel.
#
# The 1-step kernel above is HBM-streaming-bound: one step reads u_prev + u
# and writes u_next (~1.75 GB at N=512 f32), and measured pure-copy pallas
# pipelines on this v5e sustain only ~250 GB/s, so ~7 ms/step is the wall
# for ANY 1-step formulation (measured: the jnp-roll step, the fused kernel,
# and a bare out=2u-uprev axpy all land within 15% of it).  The classical
# stencil answer is temporal blocking: march k substeps per HBM pass on a
# slab "onion" held in VMEM, reading k-plane halos and writing only the last
# two layers - traffic per step drops from 3 field-streams to (2 + 2 + 4k/bx)
# / k.  Measured on v5e at N=512/1000 steps, per-layer errors on:
# 20.3 Gcell/s (k=1) -> 35.8 (k=2, bx=8) -> 43.8 (k=4, bx=4).
#
# The reference has no analog (its CUDA kernel is one-layer-per-launch,
# cuda_sol_kernels.cu:24-47, with a device-wide sync between layers); this
# is a TPU-first redesign enabled by the 128 MB VMEM and the sequential
# pallas grid.
#
# Variable c(x, y, z) rides the onion too (round 6): the c^2tau^2 field
# is time-invariant, so it enters as ONE onion-extent operand (slab +
# k-plane halos; ghost-overridden at shard edges like the state) and each
# substep s multiplies the Laplacian by the static slice C2[s : L0 - s] -
# the planes the shrinking update still writes.  Same summation order as
# the 1-step `_var_step_kernel`, so variable-c layers keep the bitwise
# mixing contract.  The field onion costs (bx + 2k) extra f32 planes in
# the pipeline plus one onion temp, which is what caps the block choice
# (`choose_kstep_block(field=True)`).
#
# Per-layer L-inf errors stay EXACTLY as observable as the reference's
# (mpi_new.cpp:335-345) even though intermediate layers never reach HBM:
# the analytic solution is separable (verify/oracle.py), so
#   abs_layer = max_x [ max_{y,z} |u - sxct[x]*syz| ]          (x != 0)
#   rel_layer = max_x [ max_{y,z} |u - f| / |syz| ] / |sx[x]*ct|
# and the kernel only needs per-x-plane maxes of diff and diff/|syz| -
# two SMEM scalar rows per substep, the tiny per-plane rescale happens
# outside.  (1/|syz| rides in as a precomputed plane with 0 at syz==0:
# those cells have u = f = 0 exactly, contributing 0 like the reference's
# NaN-skip, oracle.layer_errors.)
# --------------------------------------------------------------------------

_KSTEP_VMEM_LIMIT = 127 * 1024 * 1024
_KSTEP_VMEM_BUDGET = 122 * 1024 * 1024
# The comp (velocity-form) onion at N=512 k=4 bx=4 f32 needs 127.72 MB -
# 728 KB over the standard onion ceiling but still inside the v5e's
# 128 MiB physical VMEM; Mosaic accepts it with the ceiling at 127.9 MB
# (measured on chip; 33.1 Gcell/s, no spill cliff).
_KSTEP_COMP_VMEM_LIMIT = int(127.9 * 1024 * 1024)


def choose_kstep_block(
    n: int, k: int, itemsize: int = 4, depth: Optional[int] = None,
    ghosts: bool = False, plane_elems: Optional[int] = None,
    field: bool = False,
) -> Optional[int]:
    """Largest slab depth bx (multiple of k, power-of-two steps, <= 8,
    dividing `depth`) whose k-step pipeline fits VMEM; None if even bx=k
    does not.  `n` sets the (y, z) plane size; `depth` the x extent being
    blocked (= n single-device, the shard depth N/P sharded); `ghosts`
    adds the sharded variant's 4 single-fetched k-plane ghost buffers.

    Working-set model (validated against Mosaic's scoped-vmem accounting at
    N=512: est 120 MB vs actual 114 MB for k=2/bx=8): the double-buffered
    pipeline holds 2 state slabs in + 4 k-plane halos + 2 slabs out, the
    kernel body another ~3 onion-sized f32 temporaries, plus the two
    (N,N) oracle planes.

    `field=True` adds the variable-c working set: the c^2tau^2 onion rides
    as its own slab + k-plane halo fetch (f32 - the COMPUTE width, like the
    1-step field slab) plus one onion-sized concat temp in the body.  At
    N=512 f32 that admits k=2/bx=4 under the calibrated budget; k=4/bx=4
    models at ~134 MB against the 128 MiB physical - outside what this
    model will bless, but close enough to the measured ~5% overestimate
    that `block_x=4` stays exposed for explicit on-chip attempts.
    """
    if depth is None:
        depth = n
    if plane_elems is None:
        plane_elems = n * n
    pb_state = plane_elems * itemsize
    pb_f32 = plane_elems * 4
    best = None
    bx = k
    while bx <= 8 and bx <= depth:
        if depth % bx == 0:
            pipeline = 2 * (4 * bx + 4 * k) * pb_state
            if ghosts:
                pipeline += 4 * k * pb_state
            planes = 4 * pb_f32
            temps = 3 * (bx + 2 * k) * pb_f32
            if field:
                pipeline += 2 * (bx + 2 * k) * pb_f32
                if ghosts:
                    pipeline += 2 * k * pb_f32
                temps += (bx + 2 * k) * pb_f32
            if pipeline + planes + temps <= _KSTEP_VMEM_BUDGET:
                best = bx
        bx *= 2
    return best


def _field_onion(it, f, has_field):
    """Assemble the c^2tau^2 onion from the next three refs (slab + the two
    k-plane wraparound halos) when a field rides this call; None otherwise.

    The field is time-invariant, so unlike prev/cur its onion never
    shrinks: substep s reads the static slice C2[s : L0 - s] (the planes
    the shrinking update still writes).
    """
    if not has_field:
        return None
    c2_ref, c2lo_ref, c2hi_ref = next(it), next(it), next(it)
    return jnp.concatenate(
        [c2lo_ref[:].astype(f), c2_ref[:].astype(f), c2hi_ref[:].astype(f)],
        0)


def _substep_coeff(c2_onion, coeff, s, f):
    """Per-substep Laplacian coefficient: the matching field-onion slice,
    or the scalar a^2tau^2."""
    if c2_onion is None:
        return jnp.asarray(coeff, f)
    return c2_onion[s: c2_onion.shape[0] - s]


def _kstep_kernel(*refs, k, bx, coeff, inv_h2, compute_dtype, with_errors,
                  has_field=False):
    """March k leapfrog substeps on a slab onion held in VMEM.

    The prev/cur onions start at bx+2k planes (slab + k-plane wraparound
    halos, periodic x) and shrink by one plane per side per substep -
    after k substeps exactly the central slab remains.  Each substep is
    op-for-op the 1-step `_step_kernel` update (same laplacian summation
    order, same fused y/z Dirichlet mask), so a k-fused solve is bitwise
    identical to the 1-step pallas solve and the two can be mixed freely
    across checkpoint/resume boundaries (tests/test_kfused.py).

    With `has_field` the c^2tau^2 onion rides three extra input refs and
    each substep multiplies the Laplacian by its slice of the field
    instead of the scalar coefficient - the same summation order as the
    1-step `_var_step_kernel`, so variable-c layers keep the bitwise
    mixing contract (tests/test_kfused_varc.py).

    With `with_errors`, per-substep per-x-plane error maxes are stored as
    SMEM scalars (see the section comment for the factorization).
    """
    it = iter(refs)
    sxct_ref = next(it)
    f = compute_dtype
    c2_onion = _field_onion(it, f, has_field)
    uprev_ref, uc_ref = next(it), next(it)
    plo_ref, phi_ref = next(it), next(it)
    lo_ref, hi_ref = next(it), next(it)
    syz_ref, rsyz_ref = next(it), next(it)
    out_refs = list(it)
    if with_errors:
        out_prev_ref, out_ref, dmax_ref, rmax_ref = out_refs
    else:
        out_prev_ref, out_ref = out_refs
    i = pl.program_id(0)
    ix, iy, iz = (jnp.asarray(v, f) for v in inv_h2)
    prev = jnp.concatenate(
        [plo_ref[:].astype(f), uprev_ref[:].astype(f), phi_ref[:].astype(f)],
        0)
    cur = jnp.concatenate(
        [lo_ref[:].astype(f), uc_ref[:].astype(f), hi_ref[:].astype(f)], 0)
    syz = syz_ref[:]
    rsyz = rsyz_ref[:]
    ny, nz = syz.shape

    ym = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 1) != 0
    zm = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 2) != 0
    mask = ym & zm

    for s in range(1, k + 1):
        c = cur[1:-1]
        lap = (cur[:-2] + cur[2:] - 2.0 * c) * ix
        lap = lap + (
            pltpu.roll(c, 1, 1) + pltpu.roll(c, ny - 1, 1) - 2.0 * c
        ) * iy
        lap = lap + (
            pltpu.roll(c, 1, 2) + pltpu.roll(c, nz - 1, 2) - 2.0 * c
        ) * iz
        new = 2.0 * c + _substep_coeff(c2_onion, coeff, s, f) * lap \
            - prev[1:-1]
        new = jnp.where(mask, new, jnp.asarray(0.0, f))
        if out_ref.dtype != f:
            # A narrower state dtype (bf16) quantizes every stored layer on
            # the 1-step path; round-trip each substep so the k-fused
            # dynamics (and the observed errors) stay bitwise identical.
            new = new.astype(out_ref.dtype).astype(f)
        if with_errors:
            # Central bx planes of substep s sit at onion offset k - s.
            ctr = new[k - s: k - s + bx]
            for j in range(bx):
                diff = jnp.abs(ctr[j] - sxct_ref[s - 1, i * bx + j] * syz)
                dmax_ref[s - 1, i * bx + j] = jnp.max(diff)
                rmax_ref[s - 1, i * bx + j] = jnp.max(diff * rsyz)
        prev, cur = c, new

    out_prev_ref[:] = prev.astype(out_prev_ref.dtype)
    out_ref[:] = cur.astype(out_ref.dtype)


def fused_kstep(u_prev, u, syz, rsyz, sxct, *, k, coeff, inv_h2,
                c2tau2_field=None, block_x=None, interpret=False,
                with_errors=True, compute_dtype=None):
    """k temporally fused leapfrog steps of the full (N,N,N) state.

    Returns `(u_{n+k-1}, u_{n+k}, dmax, rmax)` where dmax/rmax are (k, N)
    per-substep per-x-plane error maxes (None, None without `with_errors`).
    `syz`/`rsyz` are the (N, N) oracle planes sy*sz and 1/|sy*sz| (0 at 0);
    `sxct` the (k, N) per-substep sx*ct row (any (k, N) f32 array when
    errors are off).  Requires N % k == 0 (wraparound halo blocks).

    With `c2tau2_field` (an (N,N,N) tau^2 c^2(x,y,z) array) the variable-c
    substep runs and `coeff` is ignored; the field rides its own slab +
    k-plane wraparound halos, matching the state onions' x extent.  Pair
    it with with_errors=False (the analytic oracle is constant-c only).
    """
    n = u.shape[0]
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u.dtype)
    if n % k:
        raise ValueError(f"k={k} must divide N={n}")
    has_field = c2tau2_field is not None
    bx = block_x or choose_kstep_block(
        n, k, u.dtype.itemsize, field=has_field
    )
    if bx is None:
        raise ValueError(
            f"k={k} does not fit VMEM at N={n} (choose_kstep_block)"
        )
    if n % bx or bx % k:
        raise ValueError(f"block_x={bx} must divide N={n} and be a "
                         f"multiple of k={k}")
    slab = pl.BlockSpec((bx, n, n), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    # k-plane wraparound halos, indexed in units of k planes: the lower
    # halo starts at plane i*bx - k = k*(i*bx/k - 1), the upper at
    # (i+1)*bx; both divisible by k because k | bx.
    nb = n // k
    lo = pl.BlockSpec((k, n, n),
                      lambda i, _bk=bx // k, _nb=nb:
                      ((i * _bk - 1) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    hi = pl.BlockSpec((k, n, n),
                      lambda i, _bk=bx // k, _nb=nb:
                      (((i + 1) * _bk) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((n, n), lambda i: (0, 0), memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kstep_kernel, k=k, bx=bx, coeff=coeff, inv_h2=inv_h2,
        compute_dtype=compute_dtype, with_errors=with_errors,
        has_field=has_field,
    )
    in_specs = [smem]
    operands = [sxct]
    if has_field:
        fld = jnp.asarray(c2tau2_field, dtype=compute_dtype)
        in_specs += [slab, lo, hi]
        operands += [fld, fld, fld]
    in_specs += [slab, slab, lo, hi, lo, hi, plane, plane]
    operands += [u_prev, u, u_prev, u_prev, u, u, syz, rsyz]
    state = jax.ShapeDtypeStruct(u.shape, u.dtype)
    out_specs = [slab, slab]
    out_shape = [state, state]
    if with_errors:
        out_specs += [smem, smem]
        out_shape += [jax.ShapeDtypeStruct((k, n), jnp.float32)] * 2
    out = pl.pallas_call(
        kern,
        grid=(n // bx,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KSTEP_VMEM_LIMIT
        ),
        name="fused_kstep",
        interpret=interpret,
    )(*operands)
    if with_errors:
        return out
    return out[0], out[1], None, None


def choose_kstep_comp_block(
    n: int, k: int, u_itemsize: int = 4, v_itemsize: int = 4,
    carry_itemsize: Optional[int] = 4, depth: Optional[int] = None,
    ghosts: bool = False, plane_elems: Optional[int] = None,
    field: bool = False,
) -> Optional[int]:
    """Slab depth for the compensated/velocity-form k-step kernel.

    Same shape as `choose_kstep_block` with the comp kernel's working set:
    u and v onions ride with k-plane halos (each at its own storage
    itemsize), the carry (when present) slab-only in and out, and the body
    holds ~3.2 onion-sized f32 temporaries regardless of carry (Mosaic
    recycles the U/V/C/lap/Kahan buffers down to that; calibrated on v5e
    against two measured programs: all-f32 carry k=4 bx=4 N=512 actual
    127.72 MB, and carry-less f32+bf16 k=4 bx=8 actual 134.91 MB - the
    latter is why bx=8 must be rejected there).  The carry-less
    coefficient carries an extra safety margin (3.4) because its
    rejection boundary was measured, not its acceptance.

    `depth` is the x extent being blocked (the shard depth for the
    sharded variant, default n); `ghosts=True` adds the sharded
    variant's 4 k-plane ghost buffers (u/v lo+hi; measured cost on v5e
    at N=512 k=4 bx=4: +20.9 MB over the ghost-less 127.72, i.e.
    ~1.25x the naive 2*k*state estimate - Mosaic double-buffers part of
    the constant-index fetches).  At N=512 that correctly rejects k=4
    for the sharded comp kernel (148.6 MB measured > 128); k=2 fits.

    `field=True` adds the variable-c onion (f32 slab + k-plane halos in
    the pipeline, one onion concat temp in the body; ghost fetches carry
    the same 1.25x factor as the state ghosts).  At N=512 the carry-less
    f32+bf16 increment form then fits k=2 (bx=4); k=4 models over the
    ceiling, as for the standard field onion (`choose_kstep_block`).
    """
    if depth is None:
        depth = n
    if plane_elems is None:
        plane_elems = n * n
    pb_f32 = plane_elems * 4
    state = u_itemsize + v_itemsize
    has_carry = carry_itemsize is not None
    best = None
    bx = k
    while bx <= 8 and bx <= depth:
        if depth % bx == 0:
            onion = bx + 2 * k
            pipeline = 2 * (onion + bx) * state * plane_elems
            if has_carry:
                pipeline += 2 * 2 * bx * carry_itemsize * plane_elems
            if ghosts:
                pipeline += 5 * k * state * plane_elems // 2
            planes = 4 * pb_f32
            temps = (315 if has_carry else 340) * onion * pb_f32 // 100
            if field:
                pipeline += 2 * onion * pb_f32
                if ghosts:
                    pipeline += 5 * k * pb_f32 // 2
                temps += onion * pb_f32
            if pipeline + planes + temps <= _KSTEP_COMP_VMEM_LIMIT:
                best = bx
        bx *= 2
    return best


def _kstep_comp_kernel(*refs, k, bx, coeff, inv_h2, compute_dtype,
                       with_errors, has_carry, has_field=False):
    """March k compensated (velocity-form) leapfrog substeps on a VMEM
    slab onion.

    Each substep is the Kahan two-sum update of `_comp_step_kernel`
    (semantics: stencil_ref.compensated_step): the increment
    v' = v + C*lap(u) accumulates in its own small-magnitude onion and
    u' = u + v' runs through the carry.  u and v march as shrinking
    onions exactly like `_kstep_kernel`; the carry rides slab-only with
    its halo planes seeded to ZERO - the halo-cone planes are discarded
    after the block, and their missing compensation re-enters the kept
    central planes only through coeff*lap of an ~ulp-sized smooth field
    (measured: no observable error delta vs the 1-step compensated path
    at N=512/1000 on v5e, both ~5.7e-6).  That approximation is the whole
    reason this fits VMEM where a 3-field full-onion Kahan scheme does
    not (solver/kfused.py's round-4 dead-end note).

    `has_carry=False` drops the carry entirely (plain increment form):
    the mode for a bf16 increment stream, where bf16 quantization of v
    dwarfs what a carry would recover.

    `has_field` threads the c^2tau^2 onion through the increment:
    v' = v + c^2tau^2(x,y,z)*lap(u) - the field coefficient enters the
    velocity form at exactly one multiply, so variable-c composes with
    the carry AND the bf16-increment mode unchanged.

    No bitwise parity with the 1-step path is claimed (unlike
    `_kstep_kernel`): intermediate layers skip the storage-dtype
    round-trip and halo carries differ - the contract is tolerance parity
    vs f64 (tests/test_kfused_comp.py).
    """
    it = iter(refs)
    sxct_ref = next(it)
    c2_onion = _field_onion(it, compute_dtype, has_field)
    u_ref, ulo_ref, uhi_ref = next(it), next(it), next(it)
    v_ref, vlo_ref, vhi_ref = next(it), next(it), next(it)
    carry_ref = next(it) if has_carry else None
    syz_ref, rsyz_ref = next(it), next(it)
    out = list(it)
    u_out, v_out = out[0], out[1]
    carry_out = out[2] if has_carry else None
    if with_errors:
        dmax_ref, rmax_ref = out[-2], out[-1]

    i = pl.program_id(0)
    f = compute_dtype
    ix, iy, iz = (jnp.asarray(val, f) for val in inv_h2)
    U = jnp.concatenate(
        [ulo_ref[:].astype(f), u_ref[:].astype(f), uhi_ref[:].astype(f)], 0)
    V = jnp.concatenate(
        [vlo_ref[:].astype(f), v_ref[:].astype(f), vhi_ref[:].astype(f)], 0)
    ny, nz = U.shape[1], U.shape[2]
    if has_carry:
        zpad = jnp.zeros((k, ny, nz), f)
        C = jnp.concatenate([zpad, carry_ref[:].astype(f), zpad], 0)

    ym = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 1) != 0
    zm = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 2) != 0
    mask = ym & zm

    syz = syz_ref[:]
    rsyz = rsyz_ref[:]

    for s in range(1, k + 1):
        uc = U[1:-1]
        lap = (U[:-2] + U[2:] - 2.0 * uc) * ix
        lap = lap + (
            pltpu.roll(uc, 1, 1) + pltpu.roll(uc, ny - 1, 1) - 2.0 * uc
        ) * iy
        lap = lap + (
            pltpu.roll(uc, 1, 2) + pltpu.roll(uc, nz - 1, 2) - 2.0 * uc
        ) * iz
        d = jnp.where(mask, _substep_coeff(c2_onion, coeff, s, f) * lap,
                      jnp.asarray(0.0, f))
        vn = V[1:-1] + d
        if has_carry:
            y = vn - C[1:-1]
        else:
            y = vn
        t = uc + y
        if has_carry:
            C = (t - uc) - y
        if with_errors:
            ctr = t[k - s: k - s + bx]
            for j in range(bx):
                diff = jnp.abs(ctr[j] - sxct_ref[s - 1, i * bx + j] * syz)
                # Error rows are f32 diagnostics regardless of the state
                # dtype (an f64 run's ~1e-13 errors round at 1e-7 relative).
                dmax_ref[s - 1, i * bx + j] = jnp.max(diff).astype(
                    jnp.float32)
                rmax_ref[s - 1, i * bx + j] = jnp.max(diff * rsyz).astype(
                    jnp.float32)
        U, V = t, vn

    u_out[:] = U.astype(u_out.dtype)
    v_out[:] = V.astype(v_out.dtype)
    if has_carry:
        carry_out[:] = C.astype(carry_out.dtype)


def fused_kstep_comp(u, v, carry, syz, rsyz, sxct, *, k, coeff, inv_h2,
                     c2tau2_field=None, block_x=None, interpret=False,
                     with_errors=True, compute_dtype=None):
    """k temporally fused compensated (velocity-form) leapfrog steps.

    State is `(u_n, v_n = u_n - u_{n-1}, carry_n)` as in
    `stencil_ref.compensated_step`; `carry=None` runs the carry-less
    increment form (e.g. bf16 v with f32 u).  Each field keeps its own
    storage dtype; compute is f32.  Returns `(u_{n+k}, v_{n+k},
    carry_{n+k} | None, dmax, rmax)` with the same (k, N) per-substep
    per-x-plane error rows as `fused_kstep`.  Requires N % k == 0.

    With `c2tau2_field` the increment uses the spatially varying
    coefficient (v' = v + c^2tau^2(x)*lap(u)) and `coeff` is ignored;
    pair it with with_errors=False (no analytic oracle).
    """
    n = u.shape[0]
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u.dtype)
    if n % k:
        raise ValueError(f"k={k} must divide N={n}")
    has_carry = carry is not None
    has_field = c2tau2_field is not None
    bx = block_x or choose_kstep_comp_block(
        n, k, u.dtype.itemsize, v.dtype.itemsize,
        carry.dtype.itemsize if has_carry else None, field=has_field,
    )
    if bx is None:
        raise ValueError(
            f"k={k} does not fit VMEM at N={n} (choose_kstep_comp_block)"
        )
    if n % bx or bx % k:
        raise ValueError(f"block_x={bx} must divide N={n} and be a "
                         f"multiple of k={k}")
    slab = pl.BlockSpec((bx, n, n), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    nb = n // k
    lo = pl.BlockSpec((k, n, n),
                      lambda i, _bk=bx // k, _nb=nb:
                      ((i * _bk - 1) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    hi = pl.BlockSpec((k, n, n),
                      lambda i, _bk=bx // k, _nb=nb:
                      (((i + 1) * _bk) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((n, n), lambda i: (0, 0), memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kstep_comp_kernel, k=k, bx=bx, coeff=coeff, inv_h2=inv_h2,
        compute_dtype=compute_dtype, with_errors=with_errors,
        has_carry=has_carry, has_field=has_field,
    )
    in_specs = [smem]
    operands = [sxct]
    if has_field:
        fld = jnp.asarray(c2tau2_field, dtype=compute_dtype)
        in_specs += [slab, lo, hi]
        operands += [fld, fld, fld]
    in_specs += [slab, lo, hi, slab, lo, hi]
    operands += [u, u, u, v, v, v]
    if has_carry:
        in_specs.append(slab)
        operands.append(carry)
    in_specs += [plane, plane]
    operands += [syz, rsyz]
    out_specs = [slab, slab]
    out_shape = [jax.ShapeDtypeStruct(u.shape, u.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if has_carry:
        out_specs.append(slab)
        out_shape.append(jax.ShapeDtypeStruct(carry.shape, carry.dtype))
    if with_errors:
        out_specs += [smem, smem]
        out_shape += [jax.ShapeDtypeStruct((k, n), jnp.float32)] * 2
    out = pl.pallas_call(
        kern,
        grid=(n // bx,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KSTEP_COMP_VMEM_LIMIT
        ),
        name="fused_kstep_comp",
        interpret=interpret,
    )(*operands)
    u_o, v_o = out[0], out[1]
    c_o = out[2] if has_carry else None
    if with_errors:
        return u_o, v_o, c_o, out[-2], out[-1]
    return u_o, v_o, c_o, None, None


def _kstep_comp_sharded_kernel(*refs, k, bx, coeff, inv_h2,
                               compute_dtype, with_errors, has_carry,
                               has_field=False):
    """`_kstep_comp_kernel` for an x-sharded block: the k-plane u/v halos
    of the block's EDGE programs come from ppermute'd ghost operands
    instead of the in-block wraparound (the `pick` of
    `_kstep_sharded_kernel`).  Carry stays slab-only with zero-seeded
    halos - the same approximation as the single-device comp onion, so
    for a shared block_x the per-plane op sequence is identical across
    mesh shapes.  NO strict bitwise pin is claimed (unlike the standard
    sharded onion): sub-f32-ulp value noise at the representation-zero
    sx plane can flip rounding ties, so cross-mesh agreement is
    ulp-level, pinned at tolerance with bitwise-equal error rows
    (tests/test_kfused_comp.py) - within the scheme's tolerance-vs-f64
    contract."""
    it = iter(refs)
    sxct_ref = next(it)
    c2_refs = (
        [next(it) for _ in range(5)] if has_field else None
    )
    u_ref, ulo_ref, uhi_ref = next(it), next(it), next(it)
    uglo_ref, ughi_ref = next(it), next(it)
    v_ref, vlo_ref, vhi_ref = next(it), next(it), next(it)
    vglo_ref, vghi_ref = next(it), next(it)
    carry_ref = next(it) if has_carry else None
    syz_ref, rsyz_ref = next(it), next(it)
    out = list(it)
    u_out, v_out = out[0], out[1]
    carry_out = out[2] if has_carry else None
    if with_errors:
        dmax_ref, rmax_ref = out[-2], out[-1]

    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    f = compute_dtype
    ix, iy, iz = (jnp.asarray(val, f) for val in inv_h2)

    def pick(edge_is_lo, ghost_ref, wrap_ref):
        at_edge = (i == 0) if edge_is_lo else (i == last)
        return jnp.where(
            at_edge, ghost_ref[:].astype(f), wrap_ref[:].astype(f)
        )

    c2_onion = _sharded_field_onion(iter(c2_refs), pick, f, has_field) \
        if has_field else None
    U = jnp.concatenate([
        pick(True, uglo_ref, ulo_ref),
        u_ref[:].astype(f),
        pick(False, ughi_ref, uhi_ref),
    ], 0)
    V = jnp.concatenate([
        pick(True, vglo_ref, vlo_ref),
        v_ref[:].astype(f),
        pick(False, vghi_ref, vhi_ref),
    ], 0)
    ny, nz = U.shape[1], U.shape[2]
    if has_carry:
        zpad = jnp.zeros((k, ny, nz), f)
        C = jnp.concatenate([zpad, carry_ref[:].astype(f), zpad], 0)

    ym = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 1) != 0
    zm = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 2) != 0
    mask = ym & zm
    syz = syz_ref[:]
    rsyz = rsyz_ref[:]

    for s in range(1, k + 1):
        uc = U[1:-1]
        lap = (U[:-2] + U[2:] - 2.0 * uc) * ix
        lap = lap + (
            pltpu.roll(uc, 1, 1) + pltpu.roll(uc, ny - 1, 1) - 2.0 * uc
        ) * iy
        lap = lap + (
            pltpu.roll(uc, 1, 2) + pltpu.roll(uc, nz - 1, 2) - 2.0 * uc
        ) * iz
        d = jnp.where(mask, _substep_coeff(c2_onion, coeff, s, f) * lap,
                      jnp.asarray(0.0, f))
        vn = V[1:-1] + d
        if has_carry:
            y = vn - C[1:-1]
        else:
            y = vn
        t = uc + y
        if has_carry:
            C = (t - uc) - y
        if with_errors:
            ctr = t[k - s: k - s + bx]
            for j in range(bx):
                diff = jnp.abs(ctr[j] - sxct_ref[s - 1, i * bx + j] * syz)
                dmax_ref[s - 1, i * bx + j] = jnp.max(diff).astype(
                    jnp.float32)
                rmax_ref[s - 1, i * bx + j] = jnp.max(diff * rsyz).astype(
                    jnp.float32)
        U, V = t, vn

    u_out[:] = U.astype(u_out.dtype)
    v_out[:] = V.astype(v_out.dtype)
    if has_carry:
        carry_out[:] = C.astype(carry_out.dtype)


def fused_kstep_comp_sharded(u, v, carry, u_ghosts, v_ghosts, syz, rsyz,
                             sxct, *, k, coeff, inv_h2, c2tau2_block=None,
                             c2_ghosts=None, block_x=None,
                             interpret=False, with_errors=True,
                             compute_dtype=None):
    """k fused compensated (velocity-form) leapfrog steps of one
    x-sharded block - the distributed flagship scheme.

    Must run inside `shard_map` on a (P, 1, 1) mesh.  `u`/`v`/`carry`
    are local (N/P, N, N) blocks (carry=None for the carry-less
    increment form); `u_ghosts`/`v_ghosts` are ((k, N, N) lo, hi) pairs
    ppermute'd from the cyclic x-neighbours BEFORE the call, exactly as
    `fused_kstep_sharded`.  `sxct` is this shard's (k, N/P) oracle row
    slice.  Returns `(u', v', carry'|None, dmax, rmax)` with (k, N/P)
    local error rows.

    `c2tau2_block`/`c2_ghosts` thread this shard's tau^2 c^2 slice (and
    its once-per-solve k-plane ghost pair) through the increment, as
    `fused_kstep_sharded`.
    """
    nl = u.shape[0]
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u.dtype)
    if nl % k:
        raise ValueError(f"k={k} must divide the shard depth {nl}")
    has_carry = carry is not None
    has_field = c2tau2_block is not None
    bx = block_x or choose_kstep_comp_block(
        u.shape[1], k, u.dtype.itemsize, v.dtype.itemsize,
        carry.dtype.itemsize if has_carry else None,
        depth=nl, ghosts=True, field=has_field,
    )
    if bx is None:
        raise ValueError(
            f"k={k} does not fit VMEM for {u.shape} shards "
            f"(choose_kstep_comp_block)"
        )
    if nl % bx or bx % k:
        raise ValueError(f"block_x={bx} must divide the shard depth {nl} "
                         f"and be a multiple of k={k}")
    ny, nz = u.shape[1], u.shape[2]
    slab = pl.BlockSpec((bx, ny, nz), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    nb = nl // k
    lo = pl.BlockSpec((k, ny, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      ((i * _bk - 1) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    hi = pl.BlockSpec((k, ny, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      (((i + 1) * _bk) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    ghost = pl.BlockSpec((k, ny, nz), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((ny, nz), lambda i: (0, 0),
                         memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kstep_comp_sharded_kernel, k=k, bx=bx, coeff=coeff,
        inv_h2=inv_h2, compute_dtype=compute_dtype,
        with_errors=with_errors, has_carry=has_carry,
        has_field=has_field,
    )
    in_specs = [smem]
    operands = [sxct]
    if has_field:
        fld = jnp.asarray(c2tau2_block, dtype=compute_dtype)
        in_specs += [slab, lo, hi, ghost, ghost]
        operands += [fld, fld, fld, c2_ghosts[0], c2_ghosts[1]]
    in_specs += [slab, lo, hi, ghost, ghost,
                 slab, lo, hi, ghost, ghost]
    operands += [u, u, u, u_ghosts[0], u_ghosts[1],
                 v, v, v, v_ghosts[0], v_ghosts[1]]
    if has_carry:
        in_specs.append(slab)
        operands.append(carry)
    in_specs += [plane, plane]
    operands += [syz, rsyz]
    out_specs = [slab, slab]
    out_shape = [_out_struct(u), _out_struct(v)]
    if has_carry:
        out_specs.append(slab)
        out_shape.append(_out_struct(carry))
    if with_errors:
        err = _out_struct(u, shape=(k, nl), dtype=jnp.float32)
        out_specs += [smem, smem]
        out_shape += [err, err]
    out = pl.pallas_call(
        kern,
        grid=(nl // bx,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KSTEP_COMP_VMEM_LIMIT
        ),
        name="fused_kstep_comp_sharded",
        interpret=interpret,
    )(*operands)
    u_o, v_o = out[0], out[1]
    c_o = out[2] if has_carry else None
    if with_errors:
        return u_o, v_o, c_o, out[-2], out[-1]
    return u_o, v_o, c_o, None, None


def _kstep_comp_sharded_xy_kernel(*refs, k, bx, nl_y, n_global, coeff,
                                  inv_h2, compute_dtype, with_errors,
                                  has_carry, has_field=False):
    """`_kstep_comp_sharded_kernel` for blocks ALSO sharded along y.

    u and v arrive pre-extended with k ghost ROWS per side (width
    W = nl_y + 2k) and their x ghosts are ppermute'd FROM the extended
    blocks (corner data rides the sequencing, as in
    `_kstep_sharded_xy_kernel`); the carry stays central (nl_y rows),
    zero-seeded in both the x halo planes and the y ghost rows.  The
    increment mask tests the WRAPPED global row index ((y0 - k + row)
    mod N != 0) so evolved ghost copies of the global y=0 stored zero
    plane never leak nonzero increments.  Outputs and error rows slice
    the central y rows (callers pmax rows over the y mesh axis).
    """
    it = iter(refs)
    y0_ref = next(it)
    sxct_ref = next(it)
    c2_refs = (
        [next(it) for _ in range(5)] if has_field else None
    )
    u_ref, ulo_ref, uhi_ref = next(it), next(it), next(it)
    uglo_ref, ughi_ref = next(it), next(it)
    v_ref, vlo_ref, vhi_ref = next(it), next(it), next(it)
    vglo_ref, vghi_ref = next(it), next(it)
    carry_ref = next(it) if has_carry else None
    syzc_ref, rsyzc_ref = next(it), next(it)
    out = list(it)
    u_out, v_out = out[0], out[1]
    carry_out = out[2] if has_carry else None
    if with_errors:
        dmax_ref, rmax_ref = out[-2], out[-1]

    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    f = compute_dtype
    ix, iy, iz = (jnp.asarray(val, f) for val in inv_h2)

    def pick(edge_is_lo, ghost_ref, wrap_ref):
        at_edge = (i == 0) if edge_is_lo else (i == last)
        return jnp.where(
            at_edge, ghost_ref[:].astype(f), wrap_ref[:].astype(f)
        )

    c2_onion = _sharded_field_onion(iter(c2_refs), pick, f, has_field) \
        if has_field else None
    U = jnp.concatenate([
        pick(True, uglo_ref, ulo_ref),
        u_ref[:].astype(f),
        pick(False, ughi_ref, uhi_ref),
    ], 0)
    V = jnp.concatenate([
        pick(True, vglo_ref, vlo_ref),
        v_ref[:].astype(f),
        pick(False, vghi_ref, vhi_ref),
    ], 0)
    w, nz = U.shape[1], U.shape[2]
    if has_carry:
        cpad_x = jnp.zeros((k, w, nz), f)
        cc = carry_ref[:].astype(f)
        cpad_y = jnp.zeros((cc.shape[0], k, nz), f)
        C = jnp.concatenate([
            cpad_x,
            jnp.concatenate([cpad_y, cc, cpad_y], 1),
            cpad_x,
        ], 0)

    gy = (y0_ref[0] - k + lax.broadcasted_iota(jnp.int32, (1, w, nz), 1))
    gy = gy % n_global
    zm = lax.broadcasted_iota(jnp.int32, (1, w, nz), 2) != 0
    mask = (gy != 0) & zm

    for s in range(1, k + 1):
        uc = U[1:-1]
        lap = (U[:-2] + U[2:] - 2.0 * uc) * ix
        lap = lap + (
            pltpu.roll(uc, 1, 1) + pltpu.roll(uc, w - 1, 1) - 2.0 * uc
        ) * iy
        lap = lap + (
            pltpu.roll(uc, 1, 2) + pltpu.roll(uc, nz - 1, 2) - 2.0 * uc
        ) * iz
        d = jnp.where(mask, _substep_coeff(c2_onion, coeff, s, f) * lap,
                      jnp.asarray(0.0, f))
        vn = V[1:-1] + d
        if has_carry:
            y = vn - C[1:-1]
        else:
            y = vn
        t = uc + y
        if has_carry:
            C = (t - uc) - y
        if with_errors:
            ctr = t[k - s: k - s + bx, k: k + nl_y]
            syz = syzc_ref[:]
            rsyz = rsyzc_ref[:]
            for j in range(bx):
                diff = jnp.abs(ctr[j] - sxct_ref[s - 1, i * bx + j] * syz)
                dmax_ref[s - 1, i * bx + j] = jnp.max(diff).astype(
                    jnp.float32)
                rmax_ref[s - 1, i * bx + j] = jnp.max(diff * rsyz).astype(
                    jnp.float32)
        U, V = t, vn

    u_out[:] = U[:, k: k + nl_y].astype(u_out.dtype)
    v_out[:] = V[:, k: k + nl_y].astype(v_out.dtype)
    if has_carry:
        carry_out[:] = C[:, k: k + nl_y].astype(carry_out.dtype)


def fused_kstep_comp_sharded_xy(u_ext, v_ext, carry, u_ghosts, v_ghosts,
                                syz_c, rsyz_c, sxct, y0, n_global, *,
                                k, nl_y, coeff, inv_h2, c2tau2_ext=None,
                                c2_ghosts=None, block_x=None,
                                interpret=False, with_errors=True,
                                compute_dtype=None):
    """k fused compensated (velocity-form) steps of an (x, y)-sharded
    block - the distributed flagship on 2D meshes.

    Must run inside `shard_map` on a (P, Q, 1) mesh.  `u_ext`/`v_ext`
    are local blocks pre-extended with k ghost rows per y side;
    `carry` is the CENTRAL (nl_x, nl_y, nz) block (or None for the
    increment form); `u_ghosts`/`v_ghosts` are ((k, W, nz) lo, hi)
    x-ghost pairs ppermute'd from the extended blocks.  Returns central
    (nl_x, nl_y, nz) state + (k, nl_x) error rows (max over this
    shard's y range; callers pmax over the y axis).  y-sharding shrinks
    every VMEM plane by Q, which is what lets k=4 fit at N=512 where
    the x-only variant is VMEM-bound at k=2.

    `c2tau2_ext`/`c2_ghosts` thread the y-extended field block and its
    once-per-solve x-ghost pair through the increment
    (`fused_kstep_sharded_xy` semantics).
    """
    nl_x, w, nz = u_ext.shape
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u_ext.dtype)
    if w != nl_y + 2 * k:
        raise ValueError(
            f"extended y width {w} != nl_y + 2k = {nl_y + 2 * k}"
        )
    if nl_x % k:
        raise ValueError(f"k={k} must divide the shard depth {nl_x}")
    has_carry = carry is not None
    has_field = c2tau2_ext is not None
    bx = block_x or choose_kstep_comp_block(
        nz, k, u_ext.dtype.itemsize, v_ext.dtype.itemsize,
        carry.dtype.itemsize if has_carry else None,
        depth=nl_x, ghosts=True, plane_elems=w * nz, field=has_field,
    )
    if bx is None:
        raise ValueError(
            f"k={k} does not fit VMEM for {u_ext.shape} blocks"
        )
    if nl_x % bx or bx % k:
        raise ValueError(f"block_x={bx} must divide the shard depth "
                         f"{nl_x} and be a multiple of k={k}")
    slab = pl.BlockSpec((bx, w, nz), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    nb = nl_x // k
    lo = pl.BlockSpec((k, w, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      ((i * _bk - 1) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    hi = pl.BlockSpec((k, w, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      (((i + 1) * _bk) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    ghost = pl.BlockSpec((k, w, nz), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    cslab = pl.BlockSpec((bx, nl_y, nz), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((nl_y, nz), lambda i: (0, 0),
                         memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kstep_comp_sharded_xy_kernel, k=k, bx=bx, nl_y=nl_y,
        n_global=n_global, coeff=coeff, inv_h2=inv_h2,
        compute_dtype=compute_dtype, with_errors=with_errors,
        has_carry=has_carry, has_field=has_field,
    )
    in_specs = [smem, smem]
    operands = [jnp.asarray(y0, jnp.int32).reshape(1), sxct]
    if has_field:
        fld = jnp.asarray(c2tau2_ext, dtype=compute_dtype)
        in_specs += [slab, lo, hi, ghost, ghost]
        operands += [fld, fld, fld, c2_ghosts[0], c2_ghosts[1]]
    in_specs += [slab, lo, hi, ghost, ghost,
                 slab, lo, hi, ghost, ghost]
    operands += [u_ext, u_ext, u_ext, u_ghosts[0], u_ghosts[1],
                 v_ext, v_ext, v_ext, v_ghosts[0], v_ghosts[1]]
    if has_carry:
        in_specs.append(cslab)
        operands.append(carry)
    in_specs += [plane, plane]
    operands += [syz_c, rsyz_c]
    state = _out_struct(u_ext, shape=(nl_x, nl_y, nz))
    vstate = _out_struct(v_ext, shape=(nl_x, nl_y, nz),
                         dtype=v_ext.dtype)
    out_specs = [cslab, cslab]
    out_shape = [state, vstate]
    if has_carry:
        out_specs.append(cslab)
        out_shape.append(_out_struct(carry))
    if with_errors:
        err = _out_struct(u_ext, shape=(k, nl_x), dtype=jnp.float32)
        out_specs += [smem, smem]
        out_shape += [err, err]
    out = pl.pallas_call(
        kern,
        grid=(nl_x // bx,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KSTEP_COMP_VMEM_LIMIT
        ),
        name="fused_kstep_comp_sharded_xy",
        interpret=interpret,
    )(*operands)
    u_o, v_o = out[0], out[1]
    c_o = out[2] if has_carry else None
    if with_errors:
        return u_o, v_o, c_o, out[-2], out[-1]
    return u_o, v_o, c_o, None, None


def _sharded_field_onion(it, pick, f, has_field):
    """Assemble the c^2tau^2 onion for a sharded onion kernel from the
    next five refs (slab, wraparound lo/hi, ghost lo/hi), with the edge
    programs' halos ghost-overridden exactly like the state onions."""
    if not has_field:
        return None
    c2_ref = next(it)
    c2lo_ref, c2hi_ref = next(it), next(it)
    c2glo_ref, c2ghi_ref = next(it), next(it)
    return jnp.concatenate([
        pick(True, c2glo_ref, c2lo_ref),
        c2_ref[:].astype(f),
        pick(False, c2ghi_ref, c2hi_ref),
    ], 0)


def _kstep_sharded_kernel(*refs, k, bx, coeff, inv_h2, compute_dtype,
                          with_errors, has_field=False):
    """`_kstep_kernel` for an x-sharded block: the k-plane halos of the
    block's EDGE programs come from the ppermute'd ghost operands (the
    neighbouring shard's boundary planes) instead of the in-block
    wraparound - interior programs are untouched, so a 1-shard mesh
    compiles to the single-device onion's data path.  y/z stay full-domain
    per shard (x-only decomposition), so the in-VMEM rolls and the fused
    Dirichlet mask are exactly the single-device kernel's.  `has_field`
    adds the c^2tau^2 onion (slab + wraparound halos + edge ghosts) as in
    `_kstep_kernel`."""
    it = iter(refs)
    sxct_ref = next(it)
    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    f = compute_dtype
    ix, iy, iz = (jnp.asarray(v, f) for v in inv_h2)

    def pick(edge_is_lo, ghost_ref, wrap_ref):
        at_edge = (i == 0) if edge_is_lo else (i == last)
        return jnp.where(
            at_edge, ghost_ref[:].astype(f), wrap_ref[:].astype(f)
        )

    c2_onion = _sharded_field_onion(it, pick, f, has_field)
    uprev_ref, uc_ref = next(it), next(it)
    plo_ref, phi_ref = next(it), next(it)
    lo_ref, hi_ref = next(it), next(it)
    pglo_ref, pghi_ref = next(it), next(it)
    glo_ref, ghi_ref = next(it), next(it)
    syz_ref, rsyz_ref = next(it), next(it)
    out_refs = list(it)
    if with_errors:
        out_prev_ref, out_ref, dmax_ref, rmax_ref = out_refs
    else:
        out_prev_ref, out_ref = out_refs

    prev = jnp.concatenate([
        pick(True, pglo_ref, plo_ref),
        uprev_ref[:].astype(f),
        pick(False, pghi_ref, phi_ref),
    ], 0)
    cur = jnp.concatenate([
        pick(True, glo_ref, lo_ref),
        uc_ref[:].astype(f),
        pick(False, ghi_ref, hi_ref),
    ], 0)
    syz = syz_ref[:]
    rsyz = rsyz_ref[:]
    ny, nz = syz.shape

    ym = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 1) != 0
    zm = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 2) != 0
    mask = ym & zm

    for s in range(1, k + 1):
        c = cur[1:-1]
        lap = (cur[:-2] + cur[2:] - 2.0 * c) * ix
        lap = lap + (
            pltpu.roll(c, 1, 1) + pltpu.roll(c, ny - 1, 1) - 2.0 * c
        ) * iy
        lap = lap + (
            pltpu.roll(c, 1, 2) + pltpu.roll(c, nz - 1, 2) - 2.0 * c
        ) * iz
        new = 2.0 * c + _substep_coeff(c2_onion, coeff, s, f) * lap \
            - prev[1:-1]
        new = jnp.where(mask, new, jnp.asarray(0.0, f))
        if out_ref.dtype != f:
            new = new.astype(out_ref.dtype).astype(f)
        if with_errors:
            ctr = new[k - s: k - s + bx]
            for j in range(bx):
                diff = jnp.abs(ctr[j] - sxct_ref[s - 1, i * bx + j] * syz)
                dmax_ref[s - 1, i * bx + j] = jnp.max(diff)
                rmax_ref[s - 1, i * bx + j] = jnp.max(diff * rsyz)
        prev, cur = c, new

    out_prev_ref[:] = prev.astype(out_prev_ref.dtype)
    out_ref[:] = cur.astype(out_ref.dtype)


def fused_kstep_sharded(u_prev, u, prev_ghosts, cur_ghosts, syz, rsyz, sxct,
                        *, k, coeff, inv_h2, c2tau2_block=None,
                        c2_ghosts=None, block_x=None, interpret=False,
                        with_errors=True, compute_dtype=None):
    """k temporally fused leapfrog steps of one x-sharded block.

    Must run inside `shard_map` on a (P, 1, 1) mesh.  `u_prev`/`u` are the
    local (N/P, N, N) block; `prev_ghosts`/`cur_ghosts` are ((k, N, N)
    lo, hi) pairs ppermute'd from the cyclic x-neighbours BEFORE the call
    (the reference's per-rank exchange-then-kernel shape,
    mpi_new.cpp:327-352, with the exchange amortized over k layers).
    `sxct` is this shard's (k, N/P) oracle row slice.  Returns the same
    tuple as `fused_kstep` with (k, N/P)-local error rows.

    With `c2tau2_block` (this shard's tau^2 c^2 slice) and `c2_ghosts`
    (its (lo, hi) k-plane ghost pair - the field is time-invariant, so the
    solver exchanges these ONCE per solve, not per block) the variable-c
    substep runs and `coeff` is ignored.
    """
    nl = u.shape[0]
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u.dtype)
    if nl % k:
        raise ValueError(f"k={k} must divide the shard depth {nl}")
    has_field = c2tau2_block is not None
    bx = block_x or choose_kstep_block(
        u.shape[1], k, u.dtype.itemsize, depth=nl, ghosts=True,
        field=has_field,
    )
    if bx is None:
        raise ValueError(
            f"k={k} does not fit VMEM for {u.shape} shards"
        )
    if nl % bx or bx % k:
        raise ValueError(f"block_x={bx} must divide the shard depth {nl} "
                         f"and be a multiple of k={k}")
    ny, nz = u.shape[1], u.shape[2]
    slab = pl.BlockSpec((bx, ny, nz), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    nb = nl // k
    lo = pl.BlockSpec((k, ny, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      ((i * _bk - 1) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    hi = pl.BlockSpec((k, ny, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      (((i + 1) * _bk) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    # Ghost operands: constant index map, so the pipeline fetches them once.
    ghost = pl.BlockSpec((k, ny, nz), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((ny, nz), lambda i: (0, 0), memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kstep_sharded_kernel, k=k, bx=bx, coeff=coeff, inv_h2=inv_h2,
        compute_dtype=compute_dtype, with_errors=with_errors,
        has_field=has_field,
    )
    in_specs = [smem]
    operands = [sxct]
    if has_field:
        fld = jnp.asarray(c2tau2_block, dtype=compute_dtype)
        in_specs += [slab, lo, hi, ghost, ghost]
        operands += [fld, fld, fld, c2_ghosts[0], c2_ghosts[1]]
    in_specs += [slab, slab, lo, hi, lo, hi, ghost, ghost, ghost, ghost,
                 plane, plane]
    operands += [u_prev, u, u_prev, u_prev, u, u,
                 prev_ghosts[0], prev_ghosts[1],
                 cur_ghosts[0], cur_ghosts[1], syz, rsyz]
    state = _out_struct(u)
    out_specs = [slab, slab]
    out_shape = [state, state]
    if with_errors:
        err = _out_struct(u, shape=(k, nl), dtype=jnp.float32)
        out_specs += [smem, smem]
        out_shape += [err, err]
    out = pl.pallas_call(
        kern,
        grid=(nl // bx,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KSTEP_VMEM_LIMIT
        ),
        name="fused_kstep_sharded",
        interpret=interpret,
    )(*operands)
    if with_errors:
        return out
    return out[0], out[1], None, None


def _kstep_padded_kernel(*refs, k, bx, bk, coeff, inv_h2, compute_dtype,
                         with_errors, has_field=False):
    """k leapfrog substeps of an x-sharded block with UNEVEN real depth.

    Operands are pre-assembled extended arrays (see
    `fused_kstep_padded`): ext = [k lo-ghost planes | D local planes |
    k junk planes], with the k hi-ghost planes written INTO the array at
    offset k + n_real - so the x-neighbour chain of every real plane is
    gap-free (the pad planes that would sit between the last real plane
    and the ghosts in HBM layout are displaced past the ghosts, where no
    real plane's k-cone reaches; junk beyond k + n_real + k is never
    consumed).  Each program fetches its onion window as bk + 2
    contiguous k-plane blocks of ext per field.

    Consequences vs `_kstep_sharded_kernel`: no edge `pick` (ghosts are
    baked into ext), no mid-onion x-mask (ghost slots hold REAL planes
    that must keep evolving; the junk zone is never read by real cones),
    and the store masks pad planes (local index >= n_real) to keep the
    zero-pad carry invariant.  Per-plane op order is identical to
    `_kstep_kernel`, so real planes stay bitwise equal to the 1-step
    pallas path (tests/test_sharded_kfused.py uneven cases).

    `has_field` adds bk+2 c^2tau^2 parts assembled IDENTICALLY to the
    state ext (lo ghosts | D planes | hi spliced at the real boundary,
    zero junk - a zero coefficient keeps the junk zone finite), read as
    the static per-substep onion slice.
    """
    it = iter(refs)
    nreal_ref = next(it)                       # SMEM (1,) int32
    sxct_ref = next(it)                        # SMEM (k, D)
    prev_parts = [next(it) for _ in range(bk + 2)]
    cur_parts = [next(it) for _ in range(bk + 2)]
    f = compute_dtype
    if has_field:
        c2_onion = jnp.concatenate(
            [next(it)[:].astype(f) for _ in range(bk + 2)], 0
        )
    else:
        c2_onion = None
    syz_ref, rsyz_ref = next(it), next(it)
    out = list(it)
    out_prev_ref, out_ref = out[0], out[1]
    if with_errors:
        dmax_ref, rmax_ref = out[2], out[3]

    i = pl.program_id(0)
    n_real = nreal_ref[0]
    ix, iy, iz = (jnp.asarray(v, f) for v in inv_h2)
    prev = jnp.concatenate([p[:].astype(f) for p in prev_parts], 0)
    cur = jnp.concatenate([p[:].astype(f) for p in cur_parts], 0)
    ny, nz = cur.shape[1], cur.shape[2]
    syz = syz_ref[:]
    rsyz = rsyz_ref[:]

    ym = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 1) != 0
    zm = lax.broadcasted_iota(jnp.int32, (1, ny, nz), 2) != 0
    mask = ym & zm

    for s in range(1, k + 1):
        c = cur[1:-1]
        lap = (cur[:-2] + cur[2:] - 2.0 * c) * ix
        lap = lap + (
            pltpu.roll(c, 1, 1) + pltpu.roll(c, ny - 1, 1) - 2.0 * c
        ) * iy
        lap = lap + (
            pltpu.roll(c, 1, 2) + pltpu.roll(c, nz - 1, 2) - 2.0 * c
        ) * iz
        new = 2.0 * c + _substep_coeff(c2_onion, coeff, s, f) * lap \
            - prev[1:-1]
        new = jnp.where(mask, new, jnp.asarray(0.0, f))
        if out_ref.dtype != f:
            new = new.astype(out_ref.dtype).astype(f)
        if with_errors:
            ctr = new[k - s: k - s + bx]
            for j in range(bx):
                col = i * bx + j
                # Pad columns must emit 0: their mid-onion values hold
                # displaced ghost planes (real data at the wrong x), and
                # their sxct is zero-padded.
                real = col < n_real
                diff = jnp.abs(ctr[j] - sxct_ref[s - 1, col] * syz)
                dmax_ref[s - 1, col] = jnp.where(
                    real, jnp.max(diff), 0.0
                ).astype(jnp.float32)
                rmax_ref[s - 1, col] = jnp.where(
                    real, jnp.max(diff * rsyz), 0.0
                ).astype(jnp.float32)
        prev, cur = c, new

    px = (
        i * bx + lax.broadcasted_iota(jnp.int32, (bx, 1, 1), 0)
    ) < n_real
    out_prev_ref[:] = jnp.where(
        px, prev, jnp.asarray(0.0, f)
    ).astype(out_prev_ref.dtype)
    out_ref[:] = jnp.where(
        px, cur, jnp.asarray(0.0, f)
    ).astype(out_ref.dtype)


def fused_kstep_padded(ext_prev, ext_cur, n_real, syz, rsyz, sxct, *,
                       k, coeff, inv_h2, ext_c2=None, block_x,
                       interpret=False, with_errors=True,
                       compute_dtype=None):
    """k fused leapfrog steps of an uneven (pad-and-mask) x-sharded block.

    Must run inside `shard_map` on an (MX, 1, 1) mesh (MX = 1 works too:
    the caller assembles ghosts from local slices).  `ext_prev`/`ext_cur`
    are (D + 2k, ny, nz) extended blocks: k exchanged lo-ghost planes,
    the D-plane padded local block with the k hi-ghost planes written at
    offset k + n_real (comm assembly in solver/sharded_kfused.py), and k
    trailing junk planes.  `n_real` is this shard's real-plane count as
    an int32 scalar array; `sxct` the (k, D) local oracle rows
    (zero-padded columns).  Returns (u_prev, u) as (D, ny, nz) blocks
    with pad planes zeroed, plus (k, D) error rows (zero at pad
    columns).  `block_x` is required (the caller owns the D/bx/VMEM
    trade; k must divide block_x, block_x must divide D).

    This is the remainder-folding analog of the reference
    (mpi_sol.cpp:417-421) for the temporally blocked path; the even-N
    point-to-point path (`fused_kstep_sharded`) remains the flagship
    fast path.  k=1 degenerates to a 1-step padded update (used for the
    bootstrap and the remainder tail).

    `ext_c2` is the c^2tau^2 field assembled exactly like `ext_prev`
    (same lo-ghost/hi-splice layout; the field is time-invariant, so the
    solver builds it once per solve); with it the variable-c substep runs
    and `coeff` is ignored.
    """
    dtot, ny, nz = ext_cur.shape
    bx = block_x
    d = dtot - 2 * k
    has_field = ext_c2 is not None
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(ext_cur.dtype)
    if d % bx or bx % k:
        raise ValueError(f"block_x={bx} must divide the padded depth {d} "
                         f"and be a multiple of k={k}")
    bk = bx // k
    parts = [
        pl.BlockSpec((k, ny, nz),
                     (lambda t: (lambda i, _bk=bk, _t=t:
                                 (i * _bk + _t, 0, 0)))(t),
                     memory_space=pltpu.VMEM)
        for t in range(bk + 2)
    ]
    out_slab = pl.BlockSpec((bx, ny, nz), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((ny, nz), lambda i: (0, 0),
                         memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kstep_padded_kernel, k=k, bx=bx, bk=bk, coeff=coeff,
        inv_h2=inv_h2, compute_dtype=compute_dtype,
        with_errors=with_errors, has_field=has_field,
    )
    state = _out_struct(ext_cur, shape=(d, ny, nz))
    out_specs = [out_slab, out_slab]
    out_shape = [state, state]
    if with_errors:
        err = _out_struct(ext_cur, shape=(k, d), dtype=jnp.float32)
        out_specs += [smem, smem]
        out_shape += [err, err]
    in_specs = [smem, smem] + parts + parts
    operands = (
        [jnp.asarray(n_real, jnp.int32).reshape(1), sxct]
        + [ext_prev] * (bk + 2) + [ext_cur] * (bk + 2)
    )
    if has_field:
        fld = jnp.asarray(ext_c2, dtype=compute_dtype)
        in_specs += parts
        operands += [fld] * (bk + 2)
    in_specs += [plane, plane]
    operands += [syz, rsyz]
    out = pl.pallas_call(
        kern,
        grid=(d // bx,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KSTEP_VMEM_LIMIT
        ),
        name="fused_kstep_padded",
        interpret=interpret,
    )(*operands)
    if with_errors:
        return out
    return out[0], out[1], None, None


def _kstep_sharded_xy_kernel(*refs, k, bx, nl_y, n_global, coeff, inv_h2,
                             compute_dtype, with_errors, has_field=False):
    """`_kstep_sharded_kernel` for blocks ALSO sharded along y.

    The solver hands in blocks pre-extended in y by k ghost rows per side
    (width W = nl_y + 2k), so the in-VMEM y rolls behave exactly as on the
    full domain for every row the onion still considers valid: staleness
    creeps inward one row per substep from the ghost edges and never
    reaches the central nl_y rows that are written back.  Two deltas vs
    the x-only kernel:

     * the y Dirichlet mask tests the WRAPPED global row index
       ((y0 - k + row) mod N != 0): the global y=0 stored zero plane must
       be re-zeroed wherever it appears, including inside a ghost strip,
       or its evolved copy would leak nonzero values into real rows;
     * outputs and error maxes slice the central y rows.

    `has_field` adds the c^2tau^2 onion: pre-extended in y like the state
    (its ghost ROWS hold the real neighbour's coefficients, which the
    onion-valid ghost-row updates genuinely consume), x ghosts from the
    extended field.
    """
    it = iter(refs)
    off_ref = next(it)
    sxct_ref = next(it)
    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    f = compute_dtype
    ix, iy, iz = (jnp.asarray(v, f) for v in inv_h2)

    def pick(edge_is_lo, ghost_ref, wrap_ref):
        at_edge = (i == 0) if edge_is_lo else (i == last)
        return jnp.where(
            at_edge, ghost_ref[:].astype(f), wrap_ref[:].astype(f)
        )

    c2_onion = _sharded_field_onion(it, pick, f, has_field)
    uprev_ref, uc_ref = next(it), next(it)
    plo_ref, phi_ref = next(it), next(it)
    lo_ref, hi_ref = next(it), next(it)
    pglo_ref, pghi_ref = next(it), next(it)
    glo_ref, ghi_ref = next(it), next(it)
    syzc_ref, rsyzc_ref = next(it), next(it)
    out_refs = list(it)
    if with_errors:
        out_prev_ref, out_ref, dmax_ref, rmax_ref = out_refs
    else:
        out_prev_ref, out_ref = out_refs

    prev = jnp.concatenate([
        pick(True, pglo_ref, plo_ref),
        uprev_ref[:].astype(f),
        pick(False, pghi_ref, phi_ref),
    ], 0)
    cur = jnp.concatenate([
        pick(True, glo_ref, lo_ref),
        uc_ref[:].astype(f),
        pick(False, ghi_ref, hi_ref),
    ], 0)
    w, nz = cur.shape[1], cur.shape[2]

    gy = (off_ref[0] - k + lax.broadcasted_iota(jnp.int32, (1, w, nz), 1))
    gy = gy % n_global
    zm = lax.broadcasted_iota(jnp.int32, (1, w, nz), 2) != 0
    mask = (gy != 0) & zm

    for s in range(1, k + 1):
        c = cur[1:-1]
        lap = (cur[:-2] + cur[2:] - 2.0 * c) * ix
        lap = lap + (
            pltpu.roll(c, 1, 1) + pltpu.roll(c, w - 1, 1) - 2.0 * c
        ) * iy
        lap = lap + (
            pltpu.roll(c, 1, 2) + pltpu.roll(c, nz - 1, 2) - 2.0 * c
        ) * iz
        new = 2.0 * c + _substep_coeff(c2_onion, coeff, s, f) * lap \
            - prev[1:-1]
        new = jnp.where(mask, new, jnp.asarray(0.0, f))
        if out_ref.dtype != f:
            new = new.astype(out_ref.dtype).astype(f)
        if with_errors:
            ctr = new[k - s: k - s + bx, k: k + nl_y]
            syz = syzc_ref[:]
            rsyz = rsyzc_ref[:]
            for j in range(bx):
                diff = jnp.abs(ctr[j] - sxct_ref[s - 1, i * bx + j] * syz)
                dmax_ref[s - 1, i * bx + j] = jnp.max(diff)
                rmax_ref[s - 1, i * bx + j] = jnp.max(diff * rsyz)
        prev, cur = c, new

    out_prev_ref[:] = prev[:, k: k + nl_y].astype(out_prev_ref.dtype)
    out_ref[:] = cur[:, k: k + nl_y].astype(out_ref.dtype)


def fused_kstep_sharded_xy(u_prev_ext, u_ext, prev_ghosts, cur_ghosts,
                           syz_c, rsyz_c, sxct, y0, n_global, *,
                           k, nl_y, coeff, inv_h2, c2tau2_ext=None,
                           c2_ghosts=None, block_x=None,
                           interpret=False, with_errors=True,
                           compute_dtype=None):
    """k fused leapfrog steps of an (x, y)-sharded block.

    Must run inside `shard_map` on a (P, Q, 1) mesh.  `u_prev_ext`/`u_ext`
    are the local blocks pre-extended along y with k ghost rows per side
    (comm: one cyclic y-ppermute pair per field); `prev_ghosts`/`cur_ghosts`
    are ((k, W, nz) lo, hi) x-ghost pairs ppermute'd FROM THE EXTENDED
    blocks - which is what makes the diagonal corner regions arrive for
    free.  `syz_c`/`rsyz_c` are the central (nl_y, nz) oracle plane
    slices, `sxct` this shard's (k, nl_x) oracle rows, `y0` the shard's
    global y offset as an int32 scalar array.  Returns central
    (nl_x, nl_y, nz) layers + (k, nl_x) error rows (max over this shard's
    y range; callers pmax over the y mesh axis).

    With `c2tau2_ext` (the field block y-extended exactly like the state)
    and `c2_ghosts` (its (lo, hi) x-ghost pair, exchanged once per solve)
    the variable-c substep runs and `coeff` is ignored.
    """
    nl_x, w, nz = u_ext.shape
    if compute_dtype is None:
        compute_dtype = stencil_ref.compute_dtype(u_ext.dtype)
    if w != nl_y + 2 * k:
        raise ValueError(
            f"extended y width {w} != nl_y + 2k = {nl_y + 2 * k}"
        )
    if nl_x % k:
        raise ValueError(f"k={k} must divide the shard depth {nl_x}")
    has_field = c2tau2_ext is not None
    bx = block_x or choose_kstep_block(
        nz, k, u_ext.dtype.itemsize, depth=nl_x, ghosts=True,
        plane_elems=w * nz, field=has_field,
    )
    if bx is None:
        raise ValueError(f"k={k} does not fit VMEM for {u_ext.shape}")
    if nl_x % bx or bx % k:
        raise ValueError(f"block_x={bx} must divide the shard depth "
                         f"{nl_x} and be a multiple of k={k}")
    slab = pl.BlockSpec((bx, w, nz), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    nb = nl_x // k
    lo = pl.BlockSpec((k, w, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      ((i * _bk - 1) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    hi = pl.BlockSpec((k, w, nz),
                      lambda i, _bk=bx // k, _nb=nb:
                      (((i + 1) * _bk) % _nb, 0, 0),
                      memory_space=pltpu.VMEM)
    ghost = pl.BlockSpec((k, w, nz), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    out_slab = pl.BlockSpec((bx, nl_y, nz), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((nl_y, nz), lambda i: (0, 0),
                         memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kstep_sharded_xy_kernel, k=k, bx=bx, nl_y=nl_y,
        n_global=n_global, coeff=coeff, inv_h2=inv_h2,
        compute_dtype=compute_dtype, with_errors=with_errors,
        has_field=has_field,
    )
    in_specs = [smem, smem]
    operands = [jnp.asarray(y0, jnp.int32).reshape(1), sxct]
    if has_field:
        fld = jnp.asarray(c2tau2_ext, dtype=compute_dtype)
        in_specs += [slab, lo, hi, ghost, ghost]
        operands += [fld, fld, fld, c2_ghosts[0], c2_ghosts[1]]
    in_specs += [slab, slab, lo, hi, lo, hi, ghost, ghost, ghost, ghost,
                 plane, plane]
    operands += [u_prev_ext, u_ext, u_prev_ext, u_prev_ext, u_ext, u_ext,
                 prev_ghosts[0], prev_ghosts[1],
                 cur_ghosts[0], cur_ghosts[1], syz_c, rsyz_c]
    state = _out_struct(u_ext, shape=(nl_x, nl_y, nz))
    out_specs = [out_slab, out_slab]
    out_shape = [state, state]
    if with_errors:
        err = _out_struct(u_ext, shape=(k, nl_x), dtype=jnp.float32)
        out_specs += [smem, smem]
        out_shape += [err, err]
    out = pl.pallas_call(
        kern,
        grid=(nl_x // bx,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KSTEP_VMEM_LIMIT
        ),
        name="fused_kstep_sharded_xy",
        interpret=interpret,
    )(*operands)
    if with_errors:
        return out
    return out[0], out[1], None, None


def make_step_fn(block_x=None, interpret=False, c2tau2_field=None):
    """A `(u_prev, u, problem) -> u_next` closure for `make_solver(step_fn=)`
    with the kernel tuning parameters bound.

    With `c2tau2_field` (see `stencil_ref.make_c2tau2_field`) the update uses
    the spatially varying wave speed kernel and returns a `ParamStep` so the
    field is a runtime argument of the jitted program, not a baked-in
    constant (see solver.leapfrog.ParamStep); the analytic oracle only holds
    for constant speed, so pair it with compute_errors=False.
    """
    if c2tau2_field is None:
        def step(u_prev, u, problem):
            return leapfrog_step(u_prev, u, problem,
                                 block_x=block_x, interpret=interpret)
        return step

    from wavetpu.solver.leapfrog import ParamStep

    def var_step(u_prev, u, problem, field):
        return _fused_step(
            u_prev, u, c2tau2_field=field, inv_h2=problem.inv_h2,
            block_x=block_x, interpret=interpret,
        )

    return ParamStep(var_step, ParamStep.materialize(c2tau2_field))
