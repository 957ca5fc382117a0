"""Chip smoke test: CB-GMRES with an FRSZ2 basis on a TPU, at paper size.

    python chip_smoke.py                # one chip: four storage formats
    python chip_smoke.py --four-chips   # four chips: sharded vs one-chip solve

The one-chip run solves ``synth:atmosmod`` at 108^3 = 1,259,712 rows (the
size of the paper's atmosmodd, Table I) with GMRES(100) through
``repro.solver.gmres``, in the backend's arithmetic (float32 on TPU), for
the storage formats float32, frsz2_16 on the jnp route, frsz2_16 on the
fused Pallas route and float16.  Per format it prints iterations, residual,
convergence, solution error, compile and warm-solve seconds, the kernel
route, the memory analysis of the compiled program and the device's memory
counters.  Compile time, memory analysis and the ``tpu_custom_call`` check
all come from the executable ``gmres`` itself runs
(``repro.solver.gmres.solve_program``).  Each returned ``x`` is checked
against a plain reference: its true residual, recomputed on the host in
numpy float64 from the same CSR arrays, must be within twice the target.
The fused-route program must contain a compiled Pallas kernel
(``tpu_custom_call``).

``--four-chips`` runs only the sharded solve (``shard=4``, ``shard_matvec=
"auto"``, plain transport) for float32 and frsz2_16 and the one-chip solve
of the same problem it is compared with: both must converge and their
iteration counts may differ by at most 2.

The script refuses to run without a TPU and catches no phase's exception.
Its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import runtime  # noqa: E402
from repro.core.accessor import format_by_name  # noqa: E402
from repro.solver import gmres  # noqa: E402
from repro.solver.gmres import solve_program  # noqa: E402
from repro.sparse import make_problem, rhs_for  # noqa: E402

PROBLEM = "synth:atmosmod"
N = 108 ** 3            # the paper's atmosmodd size (Table I)
M = 100                 # GMRES(100)
TARGET = 1e-6           # relative residual, the same for every format
MAX_ITERS = 4000


def storage_formats():
    """``(label, storage, route)`` of the one-chip phase."""
    return [
        ("float32", "float32", "native"),
        ("frsz2_16", "frsz2_16", "jnp"),
        ("frsz2_16+kernels", format_by_name("frsz2_16", use_kernels=True),
         "pallas"),
        ("float16", "float16", "native"),
    ]


def host_residual(A, b, x) -> float:
    """``||b - A x|| / ||b||`` in numpy float64 from the CSR arrays."""
    indptr = np.asarray(A.indptr)
    rows = np.repeat(np.arange(A.shape[0]), np.diff(indptr))
    data = np.asarray(A.data, np.float64)
    x = np.asarray(x, np.float64)
    ax = np.bincount(rows, weights=data * x[np.asarray(A.indices)],
                     minlength=A.shape[0])
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - ax) / np.linalg.norm(b))


def rel_error(x, x_sol) -> float:
    x, x_sol = np.asarray(x, np.float64), np.asarray(x_sol, np.float64)
    return float(np.linalg.norm(x - x_sol) / np.linalg.norm(x_sol))


def memory_stats() -> dict:
    return dict(jax.devices()[0].memory_stats() or {})


def timed_solve(A, b, **kw):
    """One ``gmres`` call, ended in ``block_until_ready``: (result, s)."""
    t = time.perf_counter()
    res = gmres(A, b, **kw)
    res.x.block_until_ready()
    return res, time.perf_counter() - t


def solve_format(A, b, x_sol, label, storage, route):
    """Compile, run and check one storage format; returns its record."""
    kw = dict(storage=storage, m=M, max_iters=MAX_ITERS, target_rrn=TARGET)
    solve, args, _ = solve_program(A, b, **kw)
    in_use_before = memory_stats().get("bytes_in_use")
    t = time.perf_counter()
    compiled = solve.lower(*args).compile()
    compile_s = time.perf_counter() - t
    _, first_s = timed_solve(A, b, **kw)
    res, warm_s = timed_solve(A, b, **kw)
    # the calls above ran this executable: lowering again hands it back
    same = (solve.lower(*args).compile().runtime_executable()
            is compiled.runtime_executable())
    mem = compiled.memory_analysis()
    return dict(
        format=label, route=route, iterations=res.iterations,
        rrn=res.rrn, converged=res.converged,
        host_rrn=host_residual(A, b, res.x), x_err=rel_error(res.x, x_sol),
        compile_s=compile_s, first_call_s=first_s, warm_solve_s=warm_s,
        executable_gmres_ran=same,
        temp_bytes=mem.temp_size_in_bytes,
        argument_bytes=mem.argument_size_in_bytes,
        output_bytes=mem.output_size_in_bytes,
        generated_code_bytes=mem.generated_code_size_in_bytes,
        tpu_custom_call="tpu_custom_call" in compiled.as_text(),
        bytes_in_use_before=in_use_before, memory_stats=memory_stats())


def check_format(rec):
    """The one-chip contract for one format record."""
    if not rec["converged"]:
        raise SystemExit(f"{rec['format']}: did not converge: {rec}")
    if rec["host_rrn"] > 2 * TARGET:
        raise SystemExit(f"{rec['format']}: host float64 residual "
                         f"{rec['host_rrn']:.3e} > 2 x target {TARGET:.1e}")
    if not rec["executable_gmres_ran"]:
        raise SystemExit(f"{rec['format']}: the inspected executable is not "
                         "the one gmres ran")
    if rec["route"] == "pallas" and not rec["tpu_custom_call"]:
        raise SystemExit(f"{rec['format']}: no tpu_custom_call in the "
                         "compiled kernel-route solve")


def one_chip(A, b, x_sol):
    for label, storage, route in storage_formats():
        rec = solve_format(A, b, x_sol, label, storage, route)
        print(json.dumps(rec), flush=True)
        check_format(rec)


def four_chips(A, b, x_sol):
    from repro.sparse.plan import plan_operator

    mode = plan_operator(A, 4, reorder="auto", matvec_mode="auto").matvec_mode
    for fmt in ("float32", "frsz2_16"):
        recs = {}
        for shard in (None, 4):
            kw = dict(storage=fmt, m=M, max_iters=MAX_ITERS,
                      target_rrn=TARGET, shard=shard,
                      shard_transport="plain", shard_matvec="auto")
            _, first_s = timed_solve(A, b, **kw)
            res, warm_s = timed_solve(A, b, **kw)
            rec = recs[shard] = dict(
                format=fmt, shard=shard or 1,
                matvec_mode=mode if shard else "local",
                iterations=res.iterations, rrn=res.rrn,
                converged=res.converged,
                host_rrn=host_residual(A, b, res.x),
                x_err=rel_error(res.x, x_sol), first_call_s=first_s,
                warm_solve_s=warm_s,
                peak_bytes_in_use=memory_stats().get("peak_bytes_in_use"))
            print(json.dumps(rec), flush=True)
        one, four = recs[None], recs[4]
        if not (one["converged"] and four["converged"]):
            raise SystemExit(f"{fmt}: a solve did not converge")
        if abs(one["iterations"] - four["iterations"]) > 2:
            raise SystemExit(
                f"{fmt}: sharded solve took {four['iterations']} iterations, "
                f"one chip {one['iterations']} (allowed difference: 2)")
        if four["host_rrn"] > 2 * TARGET:
            raise SystemExit(f"{fmt}: sharded host float64 residual "
                             f"{four['host_rrn']:.3e} > 2 x target")


def run(four: bool, n: int = N) -> None:
    """Build the problem and run one phase.  ``n`` is cut only for a CPU
    rehearsal, which calls this function directly."""
    dtype = runtime.configure_arithmetic()
    t = time.perf_counter()
    A, _ = make_problem(PROBLEM, n, dtype=np.dtype(dtype))
    b, x_sol = rhs_for(A)
    print(f"# {PROBLEM} n={A.shape[0]} nnz={A.nnz} dtype={np.dtype(dtype)} "
          f"setup_s={time.perf_counter() - t:.2f}", flush=True)
    (four_chips if four else one_chip)(A, b, x_sol)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded solve on four chips and the "
                         "one-chip solve it is compared with")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    runtime.enable_compile_cache()
    run(args.four_chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
