"""The main path's kernels compile for a v5e chip that is described, not
attached (TPU compiler rehearsal; no chip needed, nothing runs).

Interpret-mode tests cannot see what Mosaic refuses: unaligned slices,
scoped-VMEM overflows, a kernel that cannot be partitioned.  These
compiles can.  The topology is described inside a module fixture, never
at import: only one process may load the TPU library, and each xdist
worker imports every test file.  Keep every such compile in this one
file, so one worker loads the library.  JAX's compilation cache stays off
around them (an entry written here cannot be read back without a chip).
"""

import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp

from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_pallas

N = 512
K = 4
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2, with the chip's own config for the module:
    the compilation cache off, and 32-bit mode as on the chip (the suite
    turns x64 on, under which Mosaic refuses pltpu.roll's i64 shift)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    saved = {name: getattr(jax.config, name) for name in (
        "jax_enable_compilation_cache", "jax_enable_x64")}
    for name in saved:
        jax.config.update(name, False)
    yield desc
    for name, value in saved.items():
        jax.config.update(name, value)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def test_leapfrog_step(one_chip):
    problem = Problem(N=N, timesteps=1000)
    field = _f32((N, N, N), one_chip)
    step = functools.partial(stencil_pallas.leapfrog_step, problem=problem)
    _check(jax.jit(step).lower(field, field).compile())


def test_fused_kstep(one_chip):
    problem = Problem(N=N, timesteps=1000)
    field, plane = _f32((N, N, N), one_chip), _f32((N, N), one_chip)
    kstep = functools.partial(
        stencil_pallas.fused_kstep, k=K, coeff=problem.a2tau2,
        inv_h2=problem.inv_h2,
    )
    _check(jax.jit(kstep).lower(
        field, field, plane, plane, _f32((K, N), one_chip)
    ).compile())


def test_fused_kstep_comp(one_chip):
    problem = Problem(N=N, timesteps=1000)
    field, plane = _f32((N, N, N), one_chip), _f32((N, N), one_chip)
    kstep = functools.partial(
        stencil_pallas.fused_kstep_comp, k=K, coeff=problem.a2tau2,
        inv_h2=problem.inv_h2,
    )
    _check(jax.jit(kstep).lower(
        field, field, field, plane, plane, _f32((K, N), one_chip)
    ).compile())


@pytest.mark.parametrize("blocks", [7, 8])
@pytest.mark.parametrize("scheme", ["standard", "compensated"])
def test_solo_march_copies_no_state(one_chip, scheme, blocks):
    """The solo k=4 march at N=64 (no 1-step tail) copies no (N, N, N)
    state field in the compiled program, in any dtype: each kernel call's
    outputs land in the loop's own buffers, for odd and even block
    counts."""
    from wavetpu.solver import kfused, kfused_comp

    n = 64
    problem = Problem(N=n, timesteps=1 + K * blocks)
    if scheme == "standard":
        runner, _ = kfused.make_kfused_solver(problem, k=K)
    else:
        runner, _ = kfused_comp.make_kfused_comp_solver(problem, k=K)
    compiled = jax.jit(
        lambda: runner(), out_shardings=one_chip
    ).lower().compile()
    _check(compiled)
    copies = re.findall(
        rf"= \w+\[{n},{n},{n}\]\S* copy\(", compiled.as_text()
    )
    assert not copies, copies


def _computations(text):
    """HLO module text -> {computation name: its instruction lines}."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _called(comps, root):
    """`root` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line
            )
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _loop_lines(comps, branch):
    """Instruction lines of the while-loop bodies reached from `branch`."""
    bodies = [
        body for name in _called(comps, branch) for line in comps[name]
        for body in re.findall(r"body=%([\w.\-]+)", line)
    ]
    assert bodies, branch
    return [line for body in bodies for name in _called(comps, body)
            for line in comps[name]]


def _lane_selects(lines, lanes):
    """Selects whose predicate is one flag a lane: a pred[lanes]
    broadcast along the batch axis (the per-lane stop mask)."""
    defs = {line.split(" = ", 1)[0].strip(): line
            for line in lines if " = " in line}
    out = []
    for line in lines:
        m = re.search(r" select\((%[\w.\-]+),", line)
        if m:
            pred = defs.get(m.group(1), "")
            if (re.search(rf"= pred\[{lanes},\S* broadcast\(", pred)
                    and "dimensions={0}" in pred):
                out.append(line.split(" = ", 1)[0].strip())
    return out


@pytest.mark.parametrize("compute_errors", [True, False])
def test_lane_march_unmasked_branch(one_chip, compute_errors):
    """The batched 1-step lane program (2 lanes, N=64, 9 marched layers)
    branches once on "every lane runs to the last layer": the unmasked
    branch's loop holds no per-lane select, the masked branch's loop
    keeps its lane selects, and neither loop copies the (2, N, N, N)
    state."""
    from wavetpu.ensemble import batched

    n, lanes = 64, 2
    solver = batched.EnsembleSolver(
        Problem(N=n, timesteps=10), lanes, path="pallas", interpret=False,
        compute_errors=compute_errors,
    )
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in solver._example_args()]
    compiled = solver._runner.lower(*args).compile()
    _check(compiled)
    comps = _computations(compiled.as_text())
    (branches,) = [
        re.search(r"branch_computations=\{([^}]*)\}", line).group(1)
        for lines in comps.values() for line in lines
        if " conditional(" in line
    ]
    # lax.cond lowers to branch 0 = false (masked), 1 = true (unmasked).
    masked, unmasked = (b.strip().lstrip("%") for b in branches.split(","))
    state = re.compile(rf"= f32\[{lanes},{n},{n},{n}\]\S* copy\(")
    loop = _loop_lines(comps, unmasked)
    assert not _lane_selects(loop, lanes)
    if not compute_errors:
        assert not [line for line in loop if " select(" in line]
    assert not [line for line in loop if state.search(line)]
    loop = _loop_lines(comps, masked)
    assert _lane_selects(loop, lanes)
    assert not [line for line in loop if state.search(line)]


def test_fused_kstep_comp_sharded_xy_four_chips(topo):
    """The y-sharded compensated onion (fused_kstep_comp_sharded_xy) in
    the (2,2,1) mesh program that runs it: bootstrap plus one k=4 block
    (timesteps 5), each of the four shards a (256, 256, 512) block."""
    from wavetpu.core.grid import build_mesh
    from wavetpu.solver import kfused_comp

    problem = Problem(N=N, timesteps=1 + K)
    mesh = build_mesh((2, 2, 1), topo.devices[:4])
    runner = kfused_comp._make_sharded_runner(
        problem, mesh, (2, 2), jnp.float32, jnp.float32, True, K,
        True, problem.timesteps, None, None, False,
    )
    compiled = runner.lower().compile()
    _check(compiled)
    assert compiled.output_shardings[0].mesh.devices.size == 4
