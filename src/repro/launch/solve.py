"""Solver driver: the paper's experiment — CB-GMRES with FRSZ2 storage.

  python -m repro.launch.solve --problem synth:atmosmod --n 8000 \
      --formats float64,float32,frsz2_32,float16

Arithmetic follows the backend (``repro.runtime``): float32 over a float32
operator on TPU, float64 elsewhere.

``--driver device`` (default) runs each solve as one device-resident XLA
program (``lax.while_loop`` restart loop, zero host syncs); ``--driver
host`` is the seed python-looped driver for overhead comparison.

``--batch k`` solves ``k`` right-hand sides per format through
``gmres_batched`` (vmap over the device-resident driver) and reports
per-format wall time both total and per solve — the scenario layer for
serving many simultaneous systems.  ``--method block`` switches the
batched solve to block-GMRES (one shared Krylov basis for the whole
batch — ``repro.solver.block``); the README's decision table says when
that wins.

Pipeline flags (see ``repro.solver.pipeline``):

  * ``--precond jacobi`` applies right preconditioning inside the jitted
    cycle of every solve;
  * ``--ortho cgs2`` swaps the orthogonalizer (default ``mgs``);
  * ``--policy adaptive`` (or an explicit ladder such as
    ``adaptive:float64,frsz2_32@1e-2,frsz2_16@1e-6``) adds one extra run
    whose storage format is chosen per restart cycle; its row reports the
    policy name as the format.

``--shard P`` runs every solve's restart loop inside ``jax.shard_map``
over ``P`` devices (vector dim row-partitioned; ``--shard-transport``
picks plain vs FRSZ2-compressed collectives; ``--shard-matvec`` picks the
row-partitioned SpMV — ``auto`` probes the operator bandwidth and uses the
neighbor halo exchange for banded operators, the gathered operand
otherwise, and the 3-D block partition when the problem carries cell
geometry and its face wire wins; ``--shard-grid 2x2x2`` forces the
process-grid factorization) — composes with ``--batch`` for multi-device
multi-RHS serving.  ``--reorder`` controls the setup-time RCM bandwidth-reduction
permutation (``auto`` applies it exactly when it unlocks the halo matvec
for an unstructured operator; see ``repro.sparse.plan``).  See the
README's multi-device and operator-planning sections.
"""
from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.solver import gmres
from repro.solver.gmres import gmres_batched
from repro.sparse import make_problem, rhs_for


def _batch_rhs(A, b, k: int):
    """k deterministic right-hand sides: the reference b plus k-1 variants."""
    n = A.shape[0]
    cols = [b]
    for i in range(1, k):
        t = jnp.arange(n, dtype=b.dtype)
        cols.append(b * (1.0 + 0.1 * i) + 0.05 * i * jnp.sin(t * (i + 1)))
    return jnp.stack(cols)


def solve_suite(problem: str, n: int, formats: list[str], *, m: int = 100,
                max_iters: int = 20000, target_rrn: float | None = None,
                driver: str = "device", batch: int = 1,
                method: str = "vmap",
                precond: str | None = None, ortho: str = "mgs",
                policy: str | None = None, shard: int | None = None,
                shard_transport: str = "plain", shard_matvec: str = "auto",
                shard_grid=None, reorder: str = "auto",
                verbose: bool = True):
    dtype = runtime.configure_arithmetic()
    A, rrn = make_problem(problem, n, dtype=np.dtype(dtype))
    if target_rrn is not None:
        rrn = target_rrn
    b, x_sol = rhs_for(A)
    rows = []
    runs = [dict(label=fmt, storage=fmt, policy=None) for fmt in formats]
    if policy:
        runs.append(dict(label=policy, storage=None, policy=policy))
    for run in runs:
        kw = dict(storage=run["storage"], policy=run["policy"],
                  precond=precond, ortho=ortho, m=m, max_iters=max_iters,
                  target_rrn=rrn, shard=shard,
                  shard_transport=shard_transport,
                  shard_matvec=shard_matvec, shard_grid=shard_grid,
                  reorder=reorder)
        t0 = time.time()
        if batch > 1:
            B = _batch_rhs(A, b, batch)
            results = gmres_batched(A, B, method=method, **kw)
            res = results[0]               # reference rhs: accuracy metrics
            iters = sum(r.iterations for r in results)
            conv = all(r.converged for r in results)
            nbytes = sum(r.bytes_read for r in results)
        else:
            res = gmres(A, b, driver=driver, **kw)
            iters = res.iterations
            conv = bool(res.converged)
            nbytes = res.bytes_read
        wall = time.time() - t0
        err = float(jnp.linalg.norm(res.x - x_sol)
                    / jnp.linalg.norm(x_sol))
        rows.append(dict(problem=problem, n=A.shape[0], format=run["label"],
                         driver=driver if batch == 1 else "device",
                         batch=batch, method=method if batch > 1 else None,
                         precond=precond or "identity",
                         ortho=ortho, shard=shard or 1,
                         shard_transport=shard_transport if shard else None,
                         shard_matvec=shard_matvec if shard else None,
                         shard_grid=("x".join(map(str, shard_grid))
                                     if shard and shard_grid else None),
                         reorder=reorder,
                         iters=iters, rrn=res.rrn,
                         converged=conv, x_err=err,
                         restarts=res.restarts, wall_s=wall,
                         bytes_read=nbytes,
                         wall_per_solve_s=wall / max(batch, 1)))
        if verbose:
            r = rows[-1]
            extra = (f" batch={batch} t/solve={r['wall_per_solve_s']:.2f}s"
                     if batch > 1 else "")
            print(f"{problem:18s} {r['format']:10s} iters={r['iters']:6d} "
                  f"rrn={r['rrn']:.3e} conv={r['converged']} "
                  f"t={r['wall_s']:.1f}s{extra}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="synth:atmosmod")
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--formats",
                    default="float64,float32,frsz2_32,float16")
    ap.add_argument("--m", type=int, default=100)
    ap.add_argument("--target-rrn", type=float, default=None)
    ap.add_argument("--driver", choices=["device", "host"], default="device")
    ap.add_argument("--batch", type=int, default=1,
                    help="solve this many RHS per format (vmap batch)")
    ap.add_argument("--method", choices=["vmap", "block"], default="vmap",
                    help="batched solve method: independent per-RHS solves "
                         "(vmap) or one shared Krylov basis for the whole "
                         "batch (block) — only meaningful with --batch > 1")
    ap.add_argument("--precond", default=None,
                    help="right preconditioner: jacobi (default: none)")
    ap.add_argument("--ortho", choices=["mgs", "cgs2"], default="mgs",
                    help="orthogonalization scheme")
    ap.add_argument("--policy", default=None,
                    help="per-cycle precision policy run to append, e.g. "
                         "'adaptive', 'adaptive:auto' (thresholds derived "
                         "from the target RRN and format epsilons), or "
                         "'adaptive:float64,frsz2_32@1e-2,frsz2_16@1e-6'")
    ap.add_argument("--shard", type=int, default=None,
                    help="run the whole device-resident solve inside "
                         "shard_map over this many devices (vector dim "
                         "row-partitioned; requires n %% shard == 0)")
    ap.add_argument("--shard-transport", default="plain",
                    choices=["plain", "compressed", "compressed+norms"],
                    help="wire format for the sharded solve's collectives")
    ap.add_argument("--shard-matvec", default="auto",
                    choices=["auto", "halo", "rows", "replicated",
                             "block3d"],
                    help="row-partitioned SpMV: auto probes the operator "
                         "bandwidth (neighbor halo exchange for banded "
                         "operators, gathered operand otherwise; 3-D block "
                         "partition when the problem carries cell geometry "
                         "and its face wire wins)")
    ap.add_argument("--shard-grid", default=None,
                    help="force the block partition's (Px,Py,Pz) process "
                         "grid, e.g. '2x2x2' ('auto'/omitted: factor the "
                         "mesh axis to minimize modelled face wire)")
    ap.add_argument("--reorder", default="auto",
                    choices=["auto", "rcm", "none"],
                    help="RCM bandwidth-reduction reordering at setup: "
                         "auto permutes only when it unlocks the sharded "
                         "halo matvec for an unstructured operator "
                         "(repro.sparse.plan)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()
    shard_grid = None
    if args.shard_grid and args.shard_grid != "auto":
        try:
            shard_grid = tuple(int(p) for p in args.shard_grid.split("x"))
            if len(shard_grid) != 3:
                raise ValueError
        except ValueError:
            ap.error(f"--shard-grid must be 'PxPyPz' (e.g. 2x2x2) or "
                     f"'auto', got {args.shard_grid!r}")
    rows = solve_suite(args.problem, args.n, args.formats.split(","),
                       m=args.m, target_rrn=args.target_rrn,
                       driver=args.driver, batch=args.batch,
                       method=args.method,
                       precond=args.precond, ortho=args.ortho,
                       policy=args.policy, shard=args.shard,
                       shard_transport=args.shard_transport,
                       shard_matvec=args.shard_matvec,
                       shard_grid=shard_grid,
                       reorder=args.reorder)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
