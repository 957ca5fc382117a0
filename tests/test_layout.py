"""A system too large for one chip: the layout rule, the slab DIA halo SpMV
and ``solve_program``'s multi-chip branch.

The rule (``repro.solver.layout``) is a pure function of sizes and is
checked in-process against the benchmark's configurations.  The SpMV and
the multi-chip program run on 8 emulated host devices in a subprocess (the
pattern of ``tests/test_sharded_driver.py``), with the device memory limit
patched low so that a 16^3 solve does not "fit" one device.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.accessor import format_by_name
from repro.solver import layout

ROOT = Path(__file__).resolve().parents[1]
CHIP = 16e9                       # one v5e chip's memory


def _config(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def _need(name, storage):
    """The rule's bytes for a one-chip solve of configuration ``name``:
    its diagonals (the stencil's terms) at float32."""
    cfg = _config(name)
    diagonals = 1 + len(cfg["stencil"]["neighbors"])
    fmt = format_by_name(storage, arith_dtype=np.float32)
    return layout.solve_bytes(cfg["n"], cfg["m"], (fmt,),
                              diagonals * cfg["n"] * 4, 4)


@pytest.mark.parametrize("name, storage, chips", [
    ("atmos7_108", "float32", 1),
    ("atmos7_108", "frsz2_16", 1),
    ("stencil27_104", "float32", 1),
    ("atmos7_336_4chip", "float32", 4),
])
def test_layout_rule_at_one_chips_memory(name, storage, chips):
    assert layout.layout_chips(_need(name, storage), CHIP, 4) == chips


def test_layout_rule_counts_the_store_and_its_copy():
    fmt = format_by_name("float32", arith_dtype=np.float32)
    need = layout.solve_bytes(1000, 9, (fmt,), 7000, 4)
    assert need == 2 * 10 * 1000 * 4 + 7000 + layout.VECTORS * 1000 * 4
    assert layout.layout_chips(need, None, 8) == 1      # no limit reported
    assert layout.layout_chips(need, need, 8) == 1
    assert layout.layout_chips(need + 1, need, 8) == 8


def test_one_device_keeps_the_one_device_program():
    """One local device (the CPU test process): the rule never runs, and
    the program keeps its contract."""
    import jax.numpy as jnp

    from repro.solver.gmres import solve_program
    from repro.sparse import make_problem, rhs_for

    A, _ = make_problem("synth:atmosmod", 6 ** 3)
    b, _ = rhs_for(A)
    solve, args, plan = solve_program(A, b, m=10, max_iters=50,
                                      target_rrn=1e-8)
    assert plan is None and len(args) == 3
    assert layout.solve_chips(A, A.shape[0], 10, (), jnp.float64) == 1


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import sys
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

jax.config.update("jax_enable_x64", True)
sys.path.insert(0, os.path.join(os.environ["REPO"], "bench"))

import reference
from repro.solver import gmres, layout
from repro.solver.gmres import _device_result, solve_program
from repro.sparse import csr_from_coo, make_problem, partition_matvec

out = {"spmv": [], "ell": None, "program": []}
rng = np.random.default_rng(3)


def host(A):
    return (np.asarray(A.indptr), np.asarray(A.indices), np.asarray(A.data))


def apply(A, P):
    mesh = Mesh(np.asarray(jax.devices()[:P]), ("basis",))
    operand, specs, lmv = partition_matvec(A, P, "basis", mode="halo",
                                           mesh=mesh)
    x = rng.standard_normal(A.shape[0])
    xp = jnp.pad(jnp.asarray(x), (0, lmv.probe.n_pad - A.shape[0]))
    sm = jax.shard_map(lmv, mesh=mesh, in_specs=(specs, specs_vec),
                       out_specs=specs_vec, check_vma=False)
    y = np.asarray(jax.jit(sm)(operand, xp))[:A.shape[0]]
    want = reference.matvec64(*host(A), x)
    return operand, lmv, float(np.max(np.abs(y - want)) / np.max(np.abs(want)))


from jax.sharding import PartitionSpec
specs_vec = PartitionSpec("basis")
# 6^3 at 8 devices: a halo of two hops (bandwidth 36, slabs of 27 rows)
for name, s in (("synth:atmosmod", 12), ("synth:atmosmod", 11),
                ("synth:atmosmod", 6), ("synth:stencil27", 13)):
    A, _ = make_problem(name, s ** 3)
    for P in (2, 4, 8):
        operand, lmv, err = apply(A, P)
        out["spmv"].append(dict(
            name=name, n=A.shape[0], P=P, mode=lmv.mode, err=err,
            leaves=[list(a.shape) for a in jax.tree.leaves(operand)],
            n_pad=lmv.probe.n_pad,
            sharded=all(len(a.sharding.device_set) == P
                        for a in jax.tree.leaves(operand))))

# a banded operator whose diagonals fill too sparsely for DIA
n, band = 600, 40
rows = np.repeat(np.arange(n), 12)
cols = np.clip(rows + rng.integers(-band, band + 1, rows.size), 0, n - 1)
keep = np.unique(rows * n + cols)
rows, cols = keep // n, keep % n
vals = np.where(rows == cols, 30.0, rng.uniform(-1, 1, rows.size))
Ae = csr_from_coo(rows, cols, vals, (n, n))
operand, lmv, err = apply(Ae, 4)
out["ell"] = dict(dia=Ae.dia_arrays() is None, mode=lmv.mode, err=err,
                  leaves=[list(a.shape) for a in jax.tree.leaves(operand)])

# solve_program with the memory limit patched low runs on every device;
# with no limit, on one: the same systems, the same right-hand sides
kw = dict(storage="float64", m=30, max_iters=400, target_rrn=1e-10)
for s in (16, 15):
    A, _ = make_problem("synth:atmosmod", s ** 3)
    op = host(A)
    b = jnp.asarray(reference.matvec64(*op, rng.standard_normal(A.shape[0])))
    layout.bytes_limit = lambda device: 1000
    solve, args, plan = solve_program(A, b, **kw)
    state = solve(*args)
    res = _device_result(state)
    host_csr = all(isinstance(a, np.ndarray)
                   for a in (A.indptr, A.indices, A.data))
    via_gmres = gmres(A, b, **kw)
    layout.bytes_limit = lambda device: None
    solve1, args1, _ = solve_program(A, b, **kw)
    one = _device_result(solve1(*args1))
    out["program"].append(dict(
        n=A.shape[0], plan=plan is None, x_len=int(res.x.shape[0]),
        devices=len(args[0].sharding.device_set),
        stores=len(state["stores"][0].sharding.device_set),
        rrn=reference.true_rrn(op, b, np.asarray(res.x)),
        rrn_gmres=reference.true_rrn(op, b, np.asarray(via_gmres.x)),
        it=res.iterations, it_one=one.iterations,
        host_csr=host_csr,
        one_device=len(args1[0].sharding.device_set) == 1))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def multidevice():
    env = dict(os.environ, REPO=str(ROOT),
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_slab_dia_halo_spmv_is_the_reference_product(multidevice):
    assert len(multidevice["spmv"]) == 12
    for case in multidevice["spmv"]:
        assert case["mode"] == "halo", case
        assert case["err"] < 1e-13, case
        # one slab of each diagonal per device, no index arrays
        diagonals = 7 if case["name"] == "synth:atmosmod" else 27
        assert case["leaves"] == [[case["n_pad"]]] * diagonals, case
        assert case["sharded"], case
    assert any(c["n"] % c["P"] for c in multidevice["spmv"])


def test_an_operator_dia_refuses_takes_the_ell_halo(multidevice):
    ell = multidevice["ell"]
    assert ell["dia"] and ell["mode"] == "halo", ell
    assert ell["err"] < 1e-13, ell
    assert [len(shape) for shape in ell["leaves"]] == [2, 2], ell


def test_solve_program_runs_on_every_device_past_the_limit(multidevice):
    for case in multidevice["program"]:
        assert case["plan"], case                  # plan is None
        assert case["devices"] == 8 and case["stores"] == 8, case
        assert case["x_len"] == case["n"], case    # trimmed, operator order
        assert case["host_csr"], case              # no whole-operator copy
        assert case["rrn"] < 1e-10 and case["rrn_gmres"] < 1e-10, case
        assert abs(case["it"] - case["it_one"]) <= 1, case
        assert case["one_device"], case
