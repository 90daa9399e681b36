"""wavetpu - a TPU-native framework for the 3D acoustic wave equation.

A ground-up JAX/XLA/Pallas rebuild of the capabilities of the reference
MPI+CUDA solver (aleksgri/3D-wave-equation-MPI-CUDA): explicit leapfrog with a
7-point Laplacian, periodic x / Dirichlet y-z boundaries, per-layer L-inf
validation against the closed-form analytic solution, 3D domain decomposition,
and halo exchange - expressed as one jitted program per chip with cyclic
`ppermute` halos over the ICI mesh instead of MPI messages.
"""

from wavetpu.core.problem import Problem, parse_length

# Part of the disk program cache's fingerprint (serve/progcache.py):
# bump it whenever a compiled program changes, so that entries written
# by an older version are never adopted.
__version__ = "0.2.0"

__all__ = ["Problem", "parse_length", "__version__"]
