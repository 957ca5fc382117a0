"""Effective bandwidth of the fused decode-inside-contraction kernels.

The FRSZ2 kernels never materialize the decoded operand, so the right
figure of merit is *effective* bandwidth: the bytes the equivalent
uncompressed kernel would have streamed, divided by wall time.  Each cell
of the (kernel, format, p, n) grid reports

  * ``bytes``      — modelled bytes actually moved (compressed codes +
    exponents + dense inputs + outputs);
  * ``gbps``       — ``bytes`` / wall time (achieved traffic rate);
  * ``eff_bytes`` / ``eff_gbps`` — the uncompressed-equivalent stream
    (decoded basis instead of codes), the paper's headline metric: when
    ``eff_gbps`` exceeds the memcpy rate the codec is beating the memory
    wall;
  * ``memcpy_gbps`` and ``ratio = eff_gbps / memcpy_gbps`` — the same
    device's measured copy bandwidth as the roofline reference.

Kernels covered: ``decompress`` (codec alone), ``matvec`` /
``rmatvec`` (fused basis contractions), ``block_dots`` /
``block_combine`` (fused block-GMRES contractions, per block width p).
On a CPU backend the Pallas kernels execute in interpret mode, so wall
times (and hence GB/s) are orientation only — the committed snapshot records the *trajectory* and is
regenerated on real accelerators by ``python -m benchmarks.run --only
kernel_bw``.

``--check`` gates what is meaningful on any backend: every kernel cell
must match its pure-jnp oracle (rtol/atol 2e-5) and the snapshot schema
must be complete.  CI runs ``--quick --check``.

Run directly::

    PYTHONPATH=src python -m benchmarks.kernel_bw [--quick] [--check]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

DEFAULT_NS = (8192, 32768)
DEFAULT_PS = (2, 8)
DEFAULT_FORMATS = ("frsz2_32", "frsz2_16")
BASIS_ROWS = 12          # m: compressed rows per basis for the contractions
TOL = 2e-5
SCHEMA_KEYS = ("kernel", "storage", "p", "n", "bytes", "eff_bytes",
               "wall_s", "gbps", "eff_gbps", "memcpy_gbps", "ratio",
               "max_err")


def _sync(x):
    import jax

    jax.block_until_ready(x)
    return x


def _wall(fn, repeats: int = 3) -> tuple[float, object]:
    """Best-of-``repeats`` wall time of ``fn`` after one warmup call."""
    out = _sync(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        _sync(fn())
        best = min(best, time.perf_counter() - t0)
    return best, out


def _spec_of(storage: str):
    from repro.core.accessor import format_by_name

    return format_by_name(storage).spec


def _basis_nbytes(m: int, n: int, spec) -> float:
    from repro.core import frsz2 as F

    return float(m * F.storage_nbytes(n, spec))


def _max_err(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def _memcpy_gbps(n_bytes: int) -> float:
    """Measured device copy bandwidth (read + write) at this footprint."""
    import jax
    import jax.numpy as jnp

    src = jnp.arange(max(n_bytes // 4, 1), dtype=jnp.float32)
    copy = jax.jit(lambda a: a + 0.0)
    wall, _ = _wall(lambda: copy(src))
    return 2.0 * src.size * 4 / wall / 1e9


def _cell(kernel, storage, p, n, bytes_, eff_bytes, wall, memcpy_gbps, err):
    gbps = bytes_ / wall / 1e9
    eff_gbps = eff_bytes / wall / 1e9
    return dict(kernel=kernel, storage=storage, p=p, n=n,
                bytes=float(bytes_), eff_bytes=float(eff_bytes),
                wall_s=wall, gbps=gbps, eff_gbps=eff_gbps,
                memcpy_gbps=memcpy_gbps,
                ratio=eff_gbps / memcpy_gbps if memcpy_gbps else 0.0,
                max_err=err)


def _codec_cells(storage: str, n: int, memcpy_gbps: float, rng):
    """decompress / matvec / rmatvec over a compressed (m, n) basis."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import frsz2 as F
    from repro.kernels import ops

    spec = _spec_of(storage)
    m = BASIS_ROWS
    V = jnp.asarray(rng.standard_normal((m, n)), spec.dtype)
    bc = F.compress(V, spec)
    Vd = F.decompress(bc)
    comp = _basis_nbytes(m, n, spec)
    dense = float(m * n * np.dtype(spec.dtype).itemsize)
    cells = []

    wall, out = _wall(lambda: ops.decompress(bc))
    cells.append(_cell("decompress", storage, 1, n, comp + dense,
                       2 * dense, wall, memcpy_gbps, _max_err(out, Vd)))

    x = jnp.asarray(rng.standard_normal(n), spec.dtype)
    vec = float(n * np.dtype(spec.dtype).itemsize)
    wall, out = _wall(lambda: ops.matvec(bc, x))
    ref = Vd @ x
    cells.append(_cell("matvec", storage, 1, n, comp + vec, dense + vec,
                       wall, memcpy_gbps, _max_err(out, ref)))

    h = jnp.asarray(rng.standard_normal(m), spec.dtype)
    wall, out = _wall(lambda: ops.rmatvec(bc, h))
    ref = h @ Vd
    cells.append(_cell("rmatvec", storage, 1, n, comp + vec, dense + vec,
                       wall, memcpy_gbps, _max_err(out, ref)))
    return cells


def _block_cells(storage: str, p: int, n: int, memcpy_gbps: float, rng):
    """block_dots / block_combine through the accessor's kernel route."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.accessor import BlockBasisAccessor, format_by_name
    from repro.core import frsz2 as F

    spec = _spec_of(storage)
    m = BASIS_ROWS
    def mk(uk):
        return BlockBasisAccessor(
            fmt=format_by_name(storage, use_kernels=uk,
                               arith_dtype=spec.dtype),
            m=m, p=p, n=n, arith_dtype=spec.dtype)

    acc, acc_ref = mk(True), mk(False)
    store = acc.empty()
    for j in range(m):
        store = acc.write_block(
            store, j, jnp.asarray(rng.standard_normal((p, n)), spec.dtype))
    comp = float(m * F.storage_nbytes(acc.n_flat, spec))
    dense = float(m * p * n * np.dtype(spec.dtype).itemsize)
    cells = []

    W = jnp.asarray(rng.standard_normal((p, n)), spec.dtype)
    wb = float(W.nbytes)
    wall, H = _wall(lambda: acc.block_dots(store, W))
    H_ref = acc_ref.block_dots(store, W)
    cells.append(_cell("block_dots", storage, p, n, comp + wb,
                       dense + wb, wall, memcpy_gbps, _max_err(H, H_ref)))

    Y = jnp.asarray(rng.standard_normal((m, p, p)), spec.dtype)
    out_b = float(p * n * np.dtype(spec.dtype).itemsize)
    wall, C = _wall(lambda: acc.block_combine(store, Y))
    C_ref = acc_ref.block_combine(store, Y)
    cells.append(_cell("block_combine", storage, p, n, comp + out_b,
                       dense + out_b, wall, memcpy_gbps,
                       _max_err(C, C_ref)))
    return cells


def run(ns=DEFAULT_NS, ps=DEFAULT_PS, formats=DEFAULT_FORMATS,
        check: bool = False, json_path: str | None = None,
        snapshot_path: str | None = None):
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    backend = jax.default_backend()
    memcpy = _memcpy_gbps(max(ns) * 4)
    print(f"backend={backend} memcpy~{memcpy:.2f} GB/s "
          f"(interpret-mode walls are orientation only on cpu)")
    print(f"{'kernel':14s} {'fmt':9s} {'p':>2s} {'n':>7s} "
          f"{'GB/s':>8s} {'effGB/s':>8s} {'ratio':>7s} {'max_err':>9s}")
    rows = []
    failures = []
    for storage in formats:
        for n in ns:
            cells = _codec_cells(storage, n, memcpy, rng)
            for p in ps:
                cells += _block_cells(storage, p, n, memcpy, rng)
            for c in cells:
                rows.append(c)
                print(f"{c['kernel']:14s} {c['storage']:9s} {c['p']:2d} "
                      f"{c['n']:7d} {c['gbps']:8.3f} {c['eff_gbps']:8.3f} "
                      f"{c['ratio']:7.3f} {c['max_err']:9.2e}")
                if check and c["max_err"] > TOL:
                    failures.append(
                        f"{c['kernel']} {c['storage']} p={c['p']} "
                        f"n={c['n']}: max err {c['max_err']:.2e} > {TOL}")
    if json_path:
        snap = dict(suite="kernel_bw", backend=backend, ns=list(ns),
                    ps=list(ps), formats=list(formats),
                    memcpy_gbps=memcpy, rows=rows)
        with open(json_path, "w") as f:
            json.dump(snap, f, indent=1)
        print(f"\nwrote {json_path} ({len(rows)} rows)")
    if check:
        failures += _schema_failures(rows, snapshot_path)
        if failures:
            print("\nCHECK FAILED:\n  " + "\n  ".join(failures),
                  file=sys.stderr)
            raise SystemExit(1)
        print(f"\nCHECK OK: all kernel cells within {TOL} of the jnp "
              "oracle; snapshot schema complete")
    return rows


def _schema_failures(rows, snapshot_path: str | None):
    """Schema gate: fresh rows and (if present) the committed snapshot
    must both carry the full cell schema for every kernel family."""
    failures = []
    for source, rws in (("run", rows),) + (
            (("snapshot", _load_rows(snapshot_path)),)
            if snapshot_path else ()):
        if rws is None:
            continue  # snapshot not committed yet — nothing to gate
        for c in rws:
            missing = [k for k in SCHEMA_KEYS if k not in c]
            if missing:
                failures.append(f"{source}: row {c.get('kernel')} missing "
                                f"keys {missing}")
                break
        kernels = {c.get("kernel") for c in rws}
        want = {"decompress", "matvec", "rmatvec", "block_dots",
                "block_combine"}
        if not want <= kernels:
            failures.append(f"{source}: kernels missing "
                            f"{sorted(want - kernels)}")
    return failures


def _load_rows(path: str):
    try:
        with open(path) as f:
            return json.load(f)["rows"]
    except FileNotFoundError:
        return None


def snapshot(json_path: str, ns=DEFAULT_NS, ps=DEFAULT_PS,
             formats=DEFAULT_FORMATS):
    """Write the committed ``BENCH_kernel_bw.json`` snapshot.  Regenerated
    by ``python -m benchmarks.run --only kernel_bw``."""
    return run(ns=ns, ps=ps, formats=formats, check=True,
               json_path=json_path, snapshot_path=json_path)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes, single block width")
    ap.add_argument("--ns", default=None,
                    help="comma-separated vector lengths")
    ap.add_argument("--ps", default=None,
                    help="comma-separated block widths")
    ap.add_argument("--formats", default=",".join(DEFAULT_FORMATS))
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless every kernel matches its "
                         "jnp oracle and the snapshot schema is complete")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    ns = (tuple(int(v) for v in args.ns.split(",")) if args.ns
          else ((2048, 8192) if args.quick else DEFAULT_NS))
    ps = (tuple(int(v) for v in args.ps.split(",")) if args.ps
          else ((4,) if args.quick else DEFAULT_PS))
    run(ns=ns, ps=ps, formats=tuple(args.formats.split(",")),
        check=args.check, json_path=args.json,
        snapshot_path="BENCH_kernel_bw.json" if args.check else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
