"""The SpMV layer's diagonal path: :class:`DIA` against :class:`CSR`, the
structural rule that picks it (:meth:`CSR.to_dia`,
:func:`operator_matvec`), and the solve program it puts on the device."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.solver.gmres import gmres, gmres_batched, solve_program
from repro.sparse import csr as csr_mod
from repro.sparse import make_problem, permute_csr, rcm_permutation
from repro.sparse.csr import CSR, DIA, csr_from_coo, operator_matvec

#: problem_suite operators that convert at 12^3 rows
CONVERTS = ("synth:atmosmod", "synth:aniso2d", "synth:lung",
            "synth:widerange", "synth:varcoef", "synth:stretched",
            "synth:stencil27")
TOL = {np.float64: 1e-12, np.float32: 1e-5}
_OPCODE = re.compile(r"=\s+\S+\s+([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _x(n, dtype=np.float64, seed=0):
    return np.random.default_rng(seed).standard_normal(n).astype(dtype)


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("name", CONVERTS)
def test_dia_matvec_equals_csr(name, dtype):
    A, _ = make_problem(name, 12 ** 3, dtype=dtype)
    dia = A.to_dia()
    assert isinstance(dia, DIA) and len(dia.vals) == len(dia.offsets)
    assert all(v.shape == (A.shape[0],) and v.dtype == dtype
               for v in dia.vals)
    x = jnp.asarray(_x(A.shape[0], dtype))
    assert _rel_err(dia.matvec(x), A.matvec(x)) <= TOL[dtype]


def _tridiagonal(n=40):
    """A tridiagonal CSR with a stored zero on the superdiagonal (row 5)
    and row 10 missing its superdiagonal entry."""
    i = np.arange(n)
    rows = [i, i[1:], i[:-1]]
    cols = [i, i[:-1], i[1:]]
    vals = [4.0 + i, -1.0 - 0.01 * i[1:], -2.0 + 0.01 * i[:-1]]
    r, c, v = (np.concatenate(a) for a in (rows, cols, vals))
    v[(r == 5) & (c == 6)] = 0.0
    keep = ~((r == 10) & (c == 11))
    return csr_from_coo(r[keep], c[keep], v[keep], (n, n))


def test_dia_boundary_rows_stored_zero_and_missing_entry():
    A = _tridiagonal()
    dia = A.to_dia()
    assert dia.offsets == (-1, 0, 1)
    vals = np.asarray(dia.vals)
    # row 0 has no subdiagonal, row n-1 no superdiagonal: both stored as 0
    assert vals[0, 0] == 0.0 and vals[2, -1] == 0.0
    assert vals[2, 5] == 0.0 and vals[2, 10] == 0.0
    x = _x(A.shape[0])
    want = np.asarray(A.to_dense()) @ x
    np.testing.assert_allclose(np.asarray(dia.matvec(jnp.asarray(x))), want,
                               rtol=1e-14, atol=1e-14)


def test_dia_matvec_vmaps_over_a_batch():
    A, _ = make_problem("synth:stencil27", 12 ** 3)
    X = jnp.asarray(np.stack([_x(A.shape[0], seed=s) for s in range(3)]))
    got = jax.vmap(A.to_dia().matvec)(X)
    for k in range(X.shape[0]):
        assert _rel_err(got[k], A.matvec(X[k])) <= 1e-12


@pytest.mark.parametrize("name", ["synth:atmosmod", "synth:stencil27",
                                  "synth:varcoef", "synth:widerange"])
def test_rule_picks_dia_for_banded_operators(name):
    A, _ = make_problem(name, 12 ** 3)
    mv = operator_matvec(A)
    assert mv.func is DIA.matvec and mv.args[0] is A.to_dia()


def test_rule_picks_csr_for_unstructured():
    A, _ = make_problem("synth:unstructured", 12 ** 3)
    assert A.to_dia() is None
    mv = operator_matvec(A)
    assert mv.func is CSR.matvec and "row_ids" in mv.keywords


def test_rule_picks_csr_for_an_rcm_reordered_operator():
    A, _ = make_problem("synth:atmosmod", 12 ** 3)
    B = permute_csr(A, rcm_permutation(A))
    assert A.to_dia() is not None and B.to_dia() is None
    assert operator_matvec(B).func is CSR.matvec


def test_duplicate_entries_stay_on_csr():
    """CSR sums a duplicated (row, column); DIA has one slot for it."""
    A = _tridiagonal(12)
    r = np.repeat(np.arange(12), np.diff(np.asarray(A.indptr)))
    dup = csr_from_coo(np.append(r, 3), np.append(np.asarray(A.indices), 3),
                       np.append(np.asarray(A.data), 1.0), A.shape)
    assert dup.to_dia() is None
    x = jnp.asarray(_x(12))
    np.testing.assert_allclose(np.asarray(operator_matvec(dup)(x)),
                               np.asarray(dup.to_dense()) @ np.asarray(x))


def test_second_solve_program_reuses_the_dia_and_skips_row_ids(monkeypatch):
    A, _ = make_problem("synth:atmosmod", 8 ** 3)
    b = A.matvec(jnp.asarray(_x(A.shape[0])))
    kw = dict(m=20, max_iters=200, target_rrn=1e-10)
    solve1, _, _ = solve_program(A, b, **kw)
    dia = A.to_dia()

    def refuse(*_a, **_k):
        raise AssertionError("called on a second solve_program")

    monkeypatch.setattr(csr_mod, "_dia_from_csr", refuse)
    monkeypatch.setattr(CSR, "row_ids", refuse)
    solve2, args, _ = solve_program(A, b, **kw)
    assert solve2 is solve1 and A.to_dia() is dia
    assert solve2(*args)["x"].shape == b.shape


def test_device_solves_agree_on_both_paths():
    A, _ = make_problem("synth:atmosmod", 16 ** 3)
    x_sol = _x(A.shape[0])
    b = A.matvec(jnp.asarray(x_sol))
    target = 1e-10
    dia = gmres(A, b, m=40, max_iters=400, target_rrn=target)
    csr = gmres(A, b, m=40, max_iters=400, target_rrn=target,
                matvec=A.matvec)
    assert dia.converged and csr.converged
    assert dia.iterations == csr.iterations
    dense = np.asarray(A.to_dense())
    for res in (dia, csr):
        r = np.asarray(b) - dense @ np.asarray(res.x)
        assert np.linalg.norm(r) / np.linalg.norm(np.asarray(b)) <= target


def test_batched_solves_run_the_dia_path():
    A, _ = make_problem("synth:stencil27", 12 ** 3)
    B = jnp.stack([A.matvec(jnp.asarray(_x(A.shape[0], seed=s)))
                   for s in range(2)])
    res = gmres_batched(A, B, m=30, max_iters=300, target_rrn=1e-10)
    assert all(r.converged and r.rrn <= 1e-10 for r in res)


def _spmv_ops(text: str) -> collections.Counter:
    """(opcode, under ``spmv/dia``) -> count, for the instructions of a
    compiled HLO module that sit in the ``spmv`` scope."""
    out = collections.Counter()
    for line in text.splitlines():
        op, name = _OPCODE.search(line), _OP_NAME.search(line)
        if op and name and "spmv" in name.group(1).split("/"):
            out[(op.group(1), "spmv/dia" in name.group(1))] += 1
    return out


@pytest.mark.parametrize("path", ["dia", "csr"])
def test_solve_program_spmv_has_no_gather_or_scatter(path):
    """The DIA path puts no gather and no scatter under the ``spmv`` scope
    of the compiled solve, and its ops under ``spmv/dia``; the CSR path,
    run through ``matvec=A.matvec``, shows both, so the check can fail."""
    A, _ = make_problem("synth:atmosmod", 12 ** 3)
    b = A.matvec(jnp.asarray(_x(A.shape[0])))
    matvec = A.matvec if path == "csr" else None
    solve, args, _ = solve_program(A, b, m=20, max_iters=200,
                                   target_rrn=1e-10, matvec=matvec)
    ops = _spmv_ops(solve.lower(*args).compile().as_text())
    opcodes = {op for op, _ in ops}
    if path == "dia":
        assert not opcodes & {"gather", "scatter"}
        assert sum(c for (_, dia), c in ops.items() if dia) > 0
    else:
        assert {"gather", "scatter"} <= opcodes
        assert not any(dia for _, dia in ops)
