"""CB-GMRES benchmark: one cell of ``BENCHMARK.json``, one process.

    python bench/run.py --workload atmos7_108.float32 --seed 7 \
        --seconds 10 --trace 0

Set-up builds the cell's operator through the program (``make_problem``),
draws the right-hand side from the seed, and compiles the solve program
that ``repro.solver.gmres`` runs (``solve_program``), through the
persistent compile cache.  The window then solves the right-hand side back
to back, one call of that program per solve, each ended in
``block_until_ready``, and closes at the first solve boundary at or after
``--seconds``.  As ``gmres`` does, a solve fetches its counters and
residual history to the host and leaves ``x`` on the device; the Krylov
basis is never fetched.  A compile inside the window fails the run.

After the window each ``x`` is fetched and the plain reference
(``bench/reference.py``) checks every solve: the true relative residual in float64, from the operator that the
configuration states, must be within the configuration's target, and the
program's operator must hash to the configuration's digest.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` (its
``memory_peak_bytes`` and ``hbm_gb`` are the fullest of the cell's chips),
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number
compared, beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, or on a chip missing from ``bench/peaks.json``, it prints no
result and exits with code 2.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax._src import dispatch  # noqa: E402

import cells  # noqa: E402
import devtrace  # noqa: E402
import reference  # noqa: E402
import rhs  # noqa: E402
import roofline  # noqa: E402

OUT = BENCH / "out"
#: what a solve leaves on the device: its answer and its Krylov basis
ON_DEVICE = ("x", "stores")
CACHE_DIR = ROOT / ".jax_cache"
_COMPILES: list = []     # backend compiles and persistent-cache loads
_CACHE: list = []        # persistent-cache hits and misses


def _on_compile(event: str, _secs: float, **_kw) -> None:
    if event == dispatch.BACKEND_COMPILE_EVENT:
        _COMPILES.append(event)


def _on_cache(event: str, **_kw) -> None:
    if event.startswith("/jax/compilation_cache/cache_"):
        _CACHE.append(event.rsplit("/", 1)[1])


jax.monitoring.register_event_duration_secs_listener(_on_compile)
jax.monitoring.register_event_listener(_on_cache)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _program():
    """The system under test: imported only once the device is known."""
    from repro.core.accessor import format_by_name
    from repro.solver.gmres import _device_result, solve_program
    from repro.sparse import make_problem

    return types.SimpleNamespace(format_by_name=format_by_name,
                                 solve_program=solve_program,
                                 result=_device_result,
                                 make_problem=make_problem)


def per_layer(cell: cells.Cell, red, peak: dict, *, iterations, cycles,
              n: int, nnz: int, steps=None, spmvs=None) -> dict:
    """The cell's per-layer metrics that its readers find in ``red``.

    Per solve of the window: ``iterations``, ``cycles`` (its restart-cycle
    lengths), and the restart driver's counters ``steps`` (inner-loop trips
    run) and ``spmvs`` (operator applications run), ``None`` where the
    program does not count them.  A reader whose input is missing returns
    ``None`` and its metric is left out."""
    ctx = types.SimpleNamespace(
        iterations=iterations, cycles=cycles, steps=steps, spmvs=spmvs,
        config=cell.config, traffic=cell.traffic,
        peak=peak, n=n, nnz=nnz, layer_s=red.layer_s, scope_s=red.scope_s,
        chips=red.chips, busy_s=red.busy_s, window_s=red.window_s)
    metrics = {}
    for spec in cell.per_layer:
        value = cells.metric_reader(spec["name"])(ctx)
        if value is not None:
            metrics[spec["name"]] = dict(value=value, unit=spec["unit"])
    return metrics


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, peak: dict, devices, grid=None) -> dict:
    """Set up, run the window, check it against the reference, and return
    the result object.  ``grid`` cuts the operator (CPU tests only);
    ``devices`` are the cell's chips, whose memory counters are read."""
    phases = {"start": time.perf_counter() - t0}
    lap = time.perf_counter()

    def phase(name):
        nonlocal lap
        now = time.perf_counter()
        phases[name] = now - lap
        lap = now

    prog = _program()
    cfg, tr = cell.config, cell.traffic
    dtype = np.dtype(cfg["arithmetic"])
    m = cfg["m"]
    n_rows = int(np.prod(grid)) if grid else cfg["n"]
    A, _ = prog.make_problem(cfg["problem"], n_rows, dtype=dtype)
    n, nnz = A.shape[0], A.nnz
    if grid is None and (n, nnz) != (cfg["n"], cfg["nnz"]):
        raise SystemExit(f"operator has n={n} nnz={nnz}; the configuration "
                         f"states n={cfg['n']} nnz={cfg['nnz']}")
    op = (np.asarray(A.indptr), np.asarray(A.indices), np.asarray(A.data))
    phase("problem")
    _, b_host = rhs.make_rhs(seed, op, dtype)
    b = jax.device_put(b_host)
    phase("rhs")
    storage = prog.format_by_name(tr["storage"], arith_dtype=dtype,
                                  use_kernels=tr["kernels"])
    kw = dict(storage=storage, m=m, max_iters=cfg["max_iters"],
              target_rrn=cfg["target_rrn"])
    solve, args, plan = prog.solve_program(A, b, **kw)
    if plan is not None:
        raise SystemExit("the solve runs on a reordered operator; the "
                         "benchmark compares x in the operator's own order")
    jax.block_until_ready(args)
    phase("solve_program")
    cache0 = len(_CACHE)
    compiled = solve.lower(*args).compile()
    hlo_text = compiled.as_text() if trace else None
    phase("compile")
    setup_s = time.perf_counter() - t0
    _log(f"# {cell.name}: n={n} nnz={nnz} format={storage.name} "
         f"setup_s={setup_s} phases_s={json.dumps(phases)} "
         f"persistent_cache={_CACHE[cache0:]}")

    trace_dir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        devtrace.start(trace_dir)
    compiles0 = len(_COMPILES)
    done = []
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                solve, args, _ = prog.solve_program(A, b, **kw)
                state = solve(*args)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(state)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                host = jax.device_get({k: v for k, v in state.items()
                                       if k not in ON_DEVICE})
                done.append(dict(host, x=state["x"]))
                del state
            if time.perf_counter() - t_start >= seconds:
                break
    window_s = time.perf_counter() - t_start
    if trace:
        devtrace.stop()
    window_compiles = len(_COMPILES) - compiles0
    _log(f"# window {window_s} s for {len(done)} solves "
         f"(overrun {window_s - seconds} s past --seconds)")
    if window_compiles:
        raise SystemExit(f"{window_compiles} compile(s) inside the window")

    hbm_bytes = max(_peak_bytes(d) for d in devices)
    del b, args, solve, compiled

    results = [prog.result(dict(s, x=np.asarray(s["x"]))) for s in done]
    del done
    ref_op = reference.stencil_csr(cfg, grid)
    want = (cfg["operator_sha256"] if grid is None
            else reference.operator_sha256(*ref_op))
    operator_mismatch = int(
        reference.operator_sha256(*ref_op) != want
        or reference.operator_sha256(*op) != want)
    rrns = [reference.true_rrn(ref_op, b_host, res.x) for res in results]
    limit = cfg["target_rrn"]
    failed = sum(r > limit for r in rrns)
    correct = bool(results) and failed == 0 and not operator_mismatch
    its = [res.iterations for res in results]
    cycles = [roofline.cycle_lengths(res.rrn_history, cfg["target_rrn"], m)
              for res in results]
    counters = {k: [getattr(res, k, None) for res in results]
                for k in ("steps", "spmvs")}
    counters = {k: (None if None in v else v) for k, v in counters.items()}
    _log(f"# iterations {its} cycles {cycles} rrn {rrns}")

    device = dict(platform=jax.devices()[0].platform,
                  kind=jax.devices()[0].device_kind,
                  count=len(jax.devices()), memory_peak_bytes=hbm_bytes)
    out = dict(correct=correct, attempted=len(results), failed=failed)
    if trace:
        # the trace directory holds all that the readers need, to re-read
        (trace_dir / "hlo.txt").write_text(hlo_text)
        inputs = dict(iterations=its, cycles=cycles, n=n, nnz=nnz,
                      **counters)
        (trace_dir / "inputs.json").write_text(json.dumps(inputs))
        red = devtrace.reduce(trace_dir, hlo_text, n=n, nnz=nnz, m=m)
        out["metrics"] = per_layer(cell, red, peak, **inputs)
        out["device"] = dict(device, busy_s=red.busy_s,
                             window_s=red.window_s)
        out["breakdown"] = dict(device_ops=red.top_ops(10),
                                idle_gaps=red.top_gaps(10))
    else:
        e2e = dict(solve_s=window_s / len(results), hbm_gb=hbm_bytes / 1e9,
                   setup_s=setup_s)
        out["metrics"] = {s["name"]: dict(value=e2e[s["name"]],
                                          unit=s["unit"])
                          for s in cell.end_to_end}
        out["device"] = device
    checks = dict(
        rrn_max=dict(value=max(rrns), limit=limit),
        operator_mismatch=dict(value=operator_mismatch, limit=0))
    for name, c in checks.items():
        _log(f"check {name} {c['value']} limit {c['limit']}")
    out["checks"] = checks
    return out


def _peak_bytes(device) -> int:
    """Peak device memory: ``peak_bytes_in_use`` + ``peak_bytes_reserved``
    (the reservation carries the program's scratch)."""
    stats = device.memory_stats() or {}
    missing = [k for k in ("peak_bytes_in_use", "peak_bytes_reserved")
               if k not in stats]
    if missing:
        raise SystemExit(f"device memory counters missing: {missing}")
    return stats["peak_bytes_in_use"] + stats["peak_bytes_reserved"]


def use_checkout_cache() -> None:
    """Keep the persistent compile cache at a fixed path inside the
    checkout, handed to the program through the variable it reads, with no
    size cap: a solve program carries its operator as constants (200-400
    MB), more than a cap set for a shared cache keeps."""
    from repro import runtime

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    runtime.enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    # libtpu logs to /tmp/tpu_logs unless told otherwise: keep runs off /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"bench: needs a TPU, JAX found {devices[0].platform}")
        return 2
    if len(devices) < cell.chips:
        _log(f"bench: {cell.name} needs {cell.chips} chips, "
             f"JAX found {len(devices)}")
        return 2
    try:
        peak = roofline.peak(devices[0].device_kind)
    except KeyError as e:
        _log(f"bench: {e}")
        return 2
    use_checkout_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0=T0,
                   peak=peak, devices=devices[:cell.chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
