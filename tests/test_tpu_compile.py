"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The solver's Pallas kernels at the full single-chip size (n = 1,259,712
rows, an m + 1 = 101 row basis, float32, l in {8, 16}), the DIA SpMV of
both benchmark stencils at full size, and one whole float32 frsz2_16
device solve at 48^3 rows must pass the TPU compiler: interpret-mode tests
cannot see its tiling and lowering refusals.

The topology is described inside a module fixture (never at import), so
under pytest-xdist only the worker that runs this file loads the TPU
compiler, and the file skips where no v5e can be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import frsz2 as F
from repro.kernels import ops

N = 1_259_712           # 108^3: the paper's atmosmodd size (Table I)
M = 101                 # GMRES(100) basis rows
P = 8                   # block-GMRES right-hand sides
BS = 32                 # format_by_name's FRSZ2 block size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_compile(one_chip):
    """Compile ``fn`` for the described chip with compiled kernels, x64 off
    and the persistent cache off (its entries cannot be read back here);
    returns the HLO text of the compiled program."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    pin, ops.INTERPRET = ops.INTERPRET, False

    def compile_(fn, *shapes):
        with jax.enable_x64(False):
            args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                    for s, d in shapes]
            return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    ops.INTERPRET = pin
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(l):
    return F.FrszSpec(bs=BS, l=l, dtype=jnp.float32)


def _basis(l, rows, n):
    nb = -(-n // BS)
    return ((rows, nb, BS), F._code_dtype(l)), ((rows, nb), jnp.int32)


def _bc(codes, exps, n, l):
    return F.BlockCompressed(codes=codes, exps=exps, n=n, spec=_spec(l))


@pytest.mark.parametrize("l", [8, 16])
def test_matvec_compiles(tpu_compile, l):
    hlo = tpu_compile(lambda c, e, x: ops.matvec(_bc(c, e, N, l), x),
                      *_basis(l, M, N), ((N,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("l", [8, 16])
def test_rmatvec_compiles(tpu_compile, l):
    hlo = tpu_compile(lambda c, e, h: ops.rmatvec(_bc(c, e, N, l), h),
                      *_basis(l, M, N), ((M,), jnp.float32))
    assert "tpu_custom_call" in hlo


def _segments():
    """Flattened block-store length: ``P`` segments of 128-aligned ``N``."""
    return P * (-(-N // 128) * 128)


@pytest.mark.parametrize("l", [8, 16])
def test_block_dots_compiles(tpu_compile, l):
    n_flat = _segments()
    hlo = tpu_compile(
        lambda c, e, W: ops.block_dots(_bc(c, e, n_flat, l), W, p=P),
        *_basis(l, M, n_flat), ((P, N), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("l", [8, 16])
def test_block_combine_compiles(tpu_compile, l):
    n_flat = _segments()
    hlo = tpu_compile(
        lambda c, e, Y: ops.block_combine(_bc(c, e, n_flat, l), Y, p=P),
        *_basis(l, M, n_flat), ((M, P, P), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("l", [8, 16])
def test_compress_compiles(tpu_compile, l):
    hlo = tpu_compile(lambda x: ops.compress(x, _spec(l)).codes,
                      ((N,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("l", [8, 16])
def test_decompress_compiles(tpu_compile, l):
    (codes, exps) = _basis(l, 1, N)
    hlo = tpu_compile(lambda c, e: ops.decompress(_bc(c, e, N, l)),
                      codes, exps)
    assert "tpu_custom_call" in hlo


def _device_solve(storage, use_kernels):
    """A float32 GMRES(100) device solve at 48^3 and its accessor."""
    from repro.core.accessor import format_by_name
    from repro.solver.gmres import build_device_solve
    from repro.sparse import make_problem, rhs_for

    with jax.enable_x64(False):
        A, _ = make_problem("synth:atmosmod", 48 ** 3, dtype=np.float32)
        b, _ = rhs_for(A)
        fmt = format_by_name(storage, use_kernels=use_kernels,
                             arith_dtype=jnp.float32)
        solve, accs = build_device_solve(A, b, storage=fmt, m=100,
                                         max_iters=400, target_rrn=1e-6)
    return solve, accs[0], b.shape


def test_frsz2_16_device_solve_compiles(tpu_compile, whole_store_copies):
    """A whole float32 GMRES(100) solve over a fused frsz2_16 basis; the
    kernels read the store in its own layout, so no pass copies it."""
    solve, acc, shape = _device_solve("frsz2_16", True)
    hlo = tpu_compile(solve, (shape, jnp.float32), (shape, jnp.float32))
    assert "tpu_custom_call" in hlo
    assert "f64[" not in hlo
    assert whole_store_copies(hlo, jax.eval_shape(acc.empty)) == []


def test_float32_device_solve_copies_no_whole_store(tpu_compile,
                                                    whole_store_copies):
    """The float32 basis of the same solve: no loop carries the store, so
    the chip's compiler copies it nowhere."""
    solve, acc, shape = _device_solve("float32", False)
    hlo = tpu_compile(solve, (shape, jnp.float32), (shape, jnp.float32))
    assert "dynamic-update-slice" in hlo
    assert whole_store_copies(hlo, jax.eval_shape(acc.empty)) == []


@pytest.mark.parametrize("s, offsets", [
    (108, (-108 * 108, -108, -1, 0, 1, 108, 108 * 108)),
    (104, tuple(104 * 104 * i + 104 * j + k
                for i in (-1, 0, 1) for j in (-1, 0, 1) for k in (-1, 0, 1))),
])
def test_dia_matvec_compiles(tpu_compile, s, offsets):
    """The 7-point (108^3) and 27-point (104^3) DIA SpMVs: shifted slices
    and a multiply-add, with no gather and no scatter."""
    from repro.sparse.csr import DIA

    n = s ** 3
    hlo = tpu_compile(lambda x, *v: DIA(offsets, v, (n, n)).matvec(x),
                      *[((n,), jnp.float32)] * (1 + len(offsets)))
    assert "spmv/dia" in hlo
    assert " gather(" not in hlo and " scatter(" not in hlo


# -- a system no one chip holds: 336^3 on the four chips of one v5e host ----

S336 = 336
N336 = S336 ** 3        # 37,933,056 rows: the atmos7_336 configuration
HBM = 16e9              # one v5e chip's memory
OFFSETS_7PT = (-S336 * S336, -S336, -1, 0, 1, S336, S336 * S336)


@pytest.fixture(scope="module")
def four_chips(tpu_compile):
    """A mesh over the four chips of the described ``v5e:2x2``."""
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.asarray(topo.devices), ("basis",))


def _chip_bytes(compiled) -> int:
    """Bytes one chip holds for the executable: arguments, outputs and
    scratch, less the outputs that alias an argument."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _float32_pipeline(n_local):
    """The pipeline of the four-chip cell's slabs, from the one resolver."""
    from repro.solver.gmres import _resolve

    b = jax.ShapeDtypeStruct((n_local * 4,), jnp.float32)
    pipe = _resolve(None, b, "float32", None, 100, None, None, None, "mgs",
                    1.15e-6, axis_name="basis", rows=(n_local, n_local * 4))
    return pipe.policy, pipe.accs, pipe.ortho, pipe.precond, pipe.dist


def test_four_chip_program_at_336_cubed_fits_each_chip(four_chips,
                                                       tpu_compile,
                                                       whole_store_copies):
    """The solve program ``solve_program`` returns for the 336^3 system:
    the restart driver under ``shard_map`` on four slabs of 84 x-planes,
    with the DIA halo SpMV, compiles for the 2x2 host, holds under one
    chip's memory on each and copies no chip's slab of the store."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.solver.sharded import _sharded_fn
    from repro.sparse.shard import dia_halo_matvec

    n_local = N336 // 4
    with jax.enable_x64(False):
        policy, accs, ortho, precond, dist = _float32_pipeline(n_local)
        mv = dia_halo_matvec(OFFSETS_7PT, (S336 * S336,), n_local, 4,
                             "basis")
        specs = tuple(PartitionSpec("basis") for _ in OFFSETS_7PT)
        sm = _sharded_fn(four_chips, specs, mv, mv, False, accs, policy,
                         100, 4000, 0.7071067811865475, 1.15e-6, ortho,
                         precond, dist, "basis", "vmap")
        slab = jax.ShapeDtypeStruct(
            (N336,), jnp.float32,
            sharding=NamedSharding(four_chips, PartitionSpec("basis")))
        compiled = jax.jit(sm).lower(
            (slab,) * len(OFFSETS_7PT), slab, slab).compile()
    hlo = compiled.as_text()
    assert "collective-permute" in hlo and " gather(" not in hlo
    assert _chip_bytes(compiled) < HBM
    assert whole_store_copies(hlo, jax.eval_shape(accs[0].empty)) == []


def test_one_device_program_at_336_cubed_exceeds_one_chip(one_chip,
                                                          tpu_compile):
    """The same system's one-device program needs more than one chip's
    memory, or the compiler refuses it for memory: the reason the layout
    takes four chips."""
    from repro.core.accessor import BasisAccessor, format_by_name
    from repro.solver.gmres import _device_solve_fn
    from repro.solver.pipeline import (
        StaticPolicy,
        orthogonalizer_by_name,
        resolve_preconditioner,
    )
    from repro.sparse.csr import DIA

    with jax.enable_x64(False):
        fmt = format_by_name("float32", arith_dtype=jnp.float32)
        acc = BasisAccessor(fmt=fmt, m=101, n=N336, arith_dtype=jnp.float32)

        def solve(b, x0, vals):
            mv = DIA(OFFSETS_7PT, vals, (N336, N336)).matvec
            return _device_solve_fn(
                mv, (acc,), StaticPolicy(fmt), 100, 4000,
                0.7071067811865475, 1.15e-6, orthogonalizer_by_name("mgs"),
                resolve_preconditioner(None, None))(b, x0)

        vec = jax.ShapeDtypeStruct((N336,), jnp.float32, sharding=one_chip)
        lowered = jax.jit(solve).lower(vec, vec, (vec,) * len(OFFSETS_7PT))
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 - the refusal is the reading
            assert "memory" in str(e).lower(), e
            return
    assert _chip_bytes(compiled) > HBM
