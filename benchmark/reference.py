"""The plain reference: the 3D wave equation's leapfrog march in plain
jax.numpy, written from the reference solver's equations and importing
nothing of wavetpu.

Problem (aleksgri/3D-wave-equation-MPI-CUDA, openmp_sol.cpp): u_tt =
a^2 lap(u) on [0,Lx]x[0,Ly]x[0,Lz], a^2 = 1/(4 pi^2), periodic in x,
u = 0 on the y and z faces, exact solution

    u(t,x,y,z) = sin(2 pi x/Lx) sin(pi y/Ly) sin(pi z/Lz) cos(a_t t + phase),
    a_t = 0.5 sqrt(4/Lx^2 + 1/Ly^2 + 1/Lz^2).

The grid holds the N^3 points i,j,k = 0..N-1 (x's seam point N is point
0 again; the y and z faces at index N are zero and not stored, the
faces at index 0 are stored as zeros), so a cyclic shift gives every
neighbour.  Layer 0 is the exact solution; layer 1 is the Taylor
half-step u0 + (C/2) lap(u0) at the reference's phase 2 pi, where the
initial velocity is zero, and the exact solution at tau for any other
phase.  Each layer's error is the largest |u - exact| over the points
with no index 0 (the reference's interior).

Two schemes, each the textbook form of what the configuration states:

- standard: u_{n+1} = 2 u_n - u_{n-1} + C lap(u_n);
- compensated: the increment form v_{n+1} = v_n + C lap(u_n),
  u_{n+1} = u_n + v_{n+1}, the sum carried by Kahan compensation
  (its carry in the state's own precision).

`dtype` is the precision of the whole march; the control runs it in
bfloat16.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

TWO_PI = 2.0 * math.pi


class RefProblem(NamedTuple):
    N: int
    timesteps: int
    Lx: float = 1.0
    Ly: float = 1.0
    Lz: float = 1.0
    T: float = 1.0

    @classmethod
    def of(cls, args: dict) -> "RefProblem":
        """From a configuration's `problem` block."""
        return cls(*(args[k] if k in args else d for k, d in (
            ("N", None), ("timesteps", None), ("Lx", 1.0), ("Ly", 1.0),
            ("Lz", 1.0), ("T", 1.0))))

    @property
    def tau(self):
        return self.T / self.timesteps

    @property
    def coeff(self):  # C = a^2 tau^2
        return self.tau**2 / (4.0 * math.pi**2)

    @property
    def inv_h2(self):
        return tuple((self.N / L) ** 2 for L in (self.Lx, self.Ly, self.Lz))

    @property
    def a_t(self):
        return 0.5 * math.sqrt(4 / self.Lx**2 + 1 / self.Ly**2 + 1 / self.Lz**2)


class RefOutput(NamedTuple):
    u_cur: jax.Array     # layer timesteps
    u_prev: jax.Array    # layer timesteps - 1
    v: jax.Array         # u_cur - u_prev as the scheme holds it
    abs_errors: jax.Array  # (timesteps + 1,) f32, layer 0 is 0


def spatial_factors(p: RefProblem):
    """Host float64 sin factors along x, y, z."""
    i = np.arange(p.N, dtype=np.float64)
    return (np.sin(2 * np.pi * i / p.N), np.sin(np.pi * i / p.N),
            np.sin(np.pi * i / p.N))


def time_factors(p: RefProblem, phase: float) -> np.ndarray:
    """cos(a_t tau n + phase), n = 0..timesteps, in float64 on the host
    (a device cosine is an approximation)."""
    n = np.arange(p.timesteps + 1, dtype=np.float64)
    return np.cos(p.a_t * p.tau * n + phase)


def _lap(u, inv_h2):
    ix, iy, iz = inv_h2
    out = (jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0) - 2 * u) * ix
    out = out + (jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1) - 2 * u) * iy
    return out + (jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2) - 2 * u) * iz


def _zero_faces(u):
    return u.at[:, 0, :].set(0).at[:, :, 0].set(0)


@partial(jax.jit, static_argnames=("p", "scheme", "dtype", "shifted"))
def _march(sx, sy, sz, ct, *, p: RefProblem, scheme: str, dtype, shifted):
    f32 = jnp.float32
    s = sx[:, None, None] * sy[None, :, None] * sz[None, None, :]  # f32
    interior = (
        (jnp.arange(p.N) > 0)[:, None, None]
        & (jnp.arange(p.N) > 0)[None, :, None]
        & (jnp.arange(p.N) > 0)[None, None, :]
    )

    def err(u, n):
        return jnp.max(jnp.where(interior, jnp.abs(u.astype(f32) - s * ct[n]), 0))

    c = jnp.asarray(p.coeff, dtype)
    u0 = (s * ct[0]).astype(dtype)
    if shifted:
        u1 = (s * ct[1]).astype(dtype)
        v1 = (s * (ct[1] - ct[0])).astype(dtype)
    else:
        v1 = _zero_faces((c / 2) * _lap(u0, p.inv_h2))
        u1 = u0 + v1
    if scheme == "standard":
        def body(carry, n):
            up, u = carry
            un = _zero_faces(2 * u - up + c * _lap(u, p.inv_h2))
            return (u, un), err(un, n)

        (up, u), rows = jax.lax.scan(body, (u0, u1), jnp.arange(2, p.timesteps + 1))
        v = u - up
    elif scheme == "compensated":
        def body(carry, n):
            u, v, k = carry
            v = v + _zero_faces(c * _lap(u, p.inv_h2))
            y = v - k
            t = u + y
            return (t, v, (t - u) - y), err(t, n)

        k1 = jnp.zeros_like(u0) if shifted else (u1 - u0) - v1
        (u, v, _), rows = jax.lax.scan(
            body, (u1, v1, k1), jnp.arange(2, p.timesteps + 1))
        up = u - v
    else:
        raise ValueError(f"unknown scheme {scheme!r}")
    rows = jnp.concatenate([jnp.zeros((1,), f32), err(u1, 1)[None], rows])
    return RefOutput(u, up, v, rows)


def solve(p: RefProblem, scheme: str, phase: float = TWO_PI,
          dtype=jnp.float32, device=None) -> RefOutput:
    """The reference march for one problem and initial phase.  Phase is
    a runtime input: one compile serves every phase of a problem."""
    sx, sy, sz = (jnp.asarray(a, jnp.float32) for a in spatial_factors(p))
    ct = jnp.asarray(time_factors(p, phase), jnp.float32)
    args = (sx, sy, sz, ct)
    if device is not None:
        args = jax.device_put(args, device)
    return _march(*args, p=p, scheme=scheme, dtype=jnp.dtype(dtype),
                  shifted=bool(phase != TWO_PI))
