"""Record a trace of the sharded solve on the chips of one host, as a fixture.

    python bench/fixtures/record_shard.py 16 4 [out_dir]

Solves the ``atmos7_108`` configuration's operator cut to a 16^3 grid, in
float32 with a float32 basis, through ``gmres(A, b, shard=4)``: one SPMD
program over four chips, its layout (``partition_mode="auto"``) as the
program picks it.  One warm solve is traced inside the harness's
``bench.window`` span, under one ``bench.dispatch`` span (``gmres`` plans,
dispatches, waits and fetches in one call).  Keeps, under ``out_dir``
(default ``bench/fixtures/atmos7.float32.shard<P>/``), the files that
``record.py`` keeps: the ``.xplane.pb`` (xz), the HLO text of the sharded
executable and the readers' inputs (gzip), and ``result.json``: what the
harness's reduction and the ``atmos7_108.float32`` cell's readers make of
the trace, with the device's and the reference's readings.
"""
from __future__ import annotations

import gzip
import json
import lzma
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402

SEED = 20260
CELL = "atmos7_108.float32"


def record(side: int, shards: int, dst: Path) -> dict:
    import jax
    import numpy as np
    from repro.solver import gmres, sharded
    from repro.sparse import make_problem

    cell = run.cells.load_cell(CELL)
    cfg = cell.config
    dtype = np.dtype(cfg["arithmetic"])
    A, _ = make_problem(cfg["problem"], side ** 3, dtype=dtype)
    op = (np.asarray(A.indptr), np.asarray(A.indices), np.asarray(A.data))
    _, b_host = run.rhs.make_rhs(SEED, op, dtype)
    b = jax.device_put(b_host)
    kw = dict(m=cfg["m"], max_iters=cfg["max_iters"],
              target_rrn=cfg["target_rrn"], arith_dtype=dtype, shard=shards)

    # the executable that ran, and its arguments, for its HLO text
    built = {}
    build = sharded._build_sharded_solve

    def keep(*a, **k):
        solve, operand = build(*a, **k)

        def call(*args):
            built.update(solve=solve, args=args)
            return solve(*args)

        return call, operand

    sharded._build_sharded_solve = keep
    try:
        gmres(A, b, **kw)                        # compiles
        trace_dir = run.OUT / "trace" / f"shard{shards}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.devtrace.start(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                res = gmres(A, b, **kw)
        run.devtrace.stop()
    finally:
        sharded._build_sharded_solve = build
    hlo_text = built["solve"].lower(*built["args"]).compile().as_text()

    n, nnz, m = A.shape[0], A.nnz, cfg["m"]
    inputs = dict(iterations=[res.iterations],
                  cycles=[run.roofline.cycle_lengths(
                      res.rrn_history, cfg["target_rrn"], m)],
                  n=n, nnz=nnz, steps=[res.steps], spmvs=[res.spmvs])
    red = run.devtrace.reduce(trace_dir, hlo_text, n=n, nnz=nnz, m=m)
    devices = jax.devices()[:shards]
    peak = run.roofline.peak(devices[0].device_kind)
    ref_op = run.reference.stencil_csr(cfg, grid=(side,) * 3)
    rrn = run.reference.true_rrn(ref_op, b_host, np.asarray(res.x))
    out = dict(
        correct=bool(rrn <= cfg["target_rrn"]), attempted=1,
        failed=int(rrn > cfg["target_rrn"]),
        metrics=run.per_layer(cell, red, peak, **inputs),
        device=dict(platform=devices[0].platform,
                    kind=devices[0].device_kind, count=len(jax.devices()),
                    memory_peak_bytes=max(run._peak_bytes(d)
                                          for d in devices),
                    busy_s=red.busy_s, window_s=red.window_s,
                    chips=red.chips),
        breakdown=dict(device_ops=red.top_ops(10),
                       idle_gaps=red.top_gaps(10)),
        checks=dict(rrn_max=dict(value=rrn, limit=cfg["target_rrn"])))

    dst.mkdir(parents=True, exist_ok=True)
    pb = run.devtrace.xplane_file(trace_dir)
    (dst / "trace.xplane.pb.xz").write_bytes(
        lzma.compress(pb.read_bytes(), preset=9))
    (dst / "hlo.txt.gz").write_bytes(gzip.compress(hlo_text.encode()))
    (dst / "inputs.json.gz").write_bytes(
        gzip.compress(json.dumps(inputs).encode()))
    (dst / "result.json").write_text(json.dumps(out, indent=1) + "\n")
    return out


def main(argv) -> int:
    side, shards = int(argv[0]), int(argv[1])
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < shards:
        print(f"record_shard: needs {shards} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    run.use_checkout_cache()
    dst = (Path(argv[2]) if len(argv) > 2 else
           Path(__file__).resolve().parent / f"atmos7.float32.shard{shards}")
    t0 = time.perf_counter()
    out = record(side, shards, dst)
    print(json.dumps(out))
    print(f"record_shard: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
