"""What the program says about its own work, as the harness reads it: the
restart driver's counters (``GmresResult.steps``, ``spmvs``,
``cycle_lengths``), the named scopes at each layer boundary, and the
``gmres.*`` host spans, on the CPU and on traces recorded on the chip
(``bench/fixtures/<cell>.scoped``, made by ``bench/fixtures/record.py``)."""
from __future__ import annotations

import dataclasses
import gzip
import json
import lzma
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cells  # noqa: E402
import devtrace  # noqa: E402
import hlo  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import run  # noqa: E402

FIXTURES = BENCH / "fixtures"
#: the named scopes of the program, from the SpMV up to the restart driver
SCOPES = ("spmv", "dots", "combine", "compress", "store", "decode",
          "givens", "residual", "update")
#: the host spans of the program, under the harness's ``bench.dispatch``
SPANS = ("gmres.solve_program", "gmres.plan", "gmres.lookup")


def _solve(storage: str, grid=(10, 10, 10), m=100, target=1e-6):
    """A float32 solve of the 7-point cell's operator cut to ``grid``."""
    import jax.numpy as jnp
    from repro.core.accessor import format_by_name
    from repro.solver.gmres import _device_result, solve_program
    from repro.sparse.csr import CSR

    cfg = cells._load_json(BENCH / "configs" / "atmos7_108.json")
    indptr, indices, data = reference.stencil_csr(cfg, grid=grid)
    A = CSR(jnp.asarray(indptr, jnp.int32), jnp.asarray(indices, jnp.int32),
            jnp.asarray(data, jnp.float32), (indptr.size - 1,) * 2)
    b = reference.matvec64(indptr, indices, data,
                           np.random.default_rng(1).standard_normal(
                               indptr.size - 1)).astype(np.float32)
    fmt = format_by_name(storage, arith_dtype=np.float32)
    solve, args, _ = solve_program(A, jnp.asarray(b), storage=fmt, m=m,
                                   max_iters=400, target_rrn=target,
                                   arith_dtype=np.float32)
    return solve, args, _device_result(solve(*args)), A


def _timed(module: hlo.Module) -> set:
    """Instructions that run as ops of their own on the device: those of
    the entry computation and of the loop bodies and branches, not those
    fused into another op or applied by one (a reduction's combiner)."""
    inner = {c for ins in module.instrs.values()
             if ins.opcode not in hlo.CONTROL_OPCODES for c in ins.called}
    return {name for name, ins in module.instrs.items()
            if ins.computation not in inner
            and ins.opcode not in hlo.CONTROL_OPCODES}


def _unscoped(module: hlo.Module) -> hlo.Module:
    """``module`` with the program's scope names taken out of every
    ``op_name``: what the classifier saw before the program named them."""
    instrs = {k: dataclasses.replace(
        ins, op_name="/".join(c for c in ins.op_name.split("/")
                              if c not in SCOPES))
        for k, ins in module.instrs.items()}
    return dataclasses.replace(module, instrs=instrs)


def _moved(module: hlo.Module, n: int, nnz: int, m: int) -> dict:
    """Timed op -> (layer without the scope names, layer with them), for
    each op that the scope names move."""
    was = hlo.classify(_unscoped(module), n=n, nnz=nnz, m=m)
    now = hlo.classify(module, n=n, nnz=nnz, m=m)
    return {k: (was[k], now[k]) for k in _timed(module) if was[k] != now[k]}


def _innermost_scope(ins: hlo.Instr) -> str:
    return next((c for c in reversed(ins.op_name.split("/")) if c in SCOPES),
                "")


# -- counters of the restart driver --------------------------------------------

@pytest.mark.parametrize("storage", ["float32", "frsz2_16"])
def test_cycle_lengths_are_the_rule_the_harness_rebuilds(storage):
    """The driver's own ``j_stop`` per cycle is what ``roofline`` rebuilds
    from the residual history.  Its SpMVs are those the solve needs, plus
    one per masked trip, plus one per cycle: the loop head recomputes the
    residual that the start or the cycle before it ended on.  The 16-bit
    basis restarts early (after its implicit estimate meets the target),
    the float32 one does not."""
    m, target = 100, 1e-6
    _, _, res, _ = _solve(storage, m=m, target=target)
    cycles = roofline.cycle_lengths(res.rrn_history, target, m)
    assert res.converged
    assert res.cycle_lengths.tolist() == cycles
    assert (len(cycles) >= 2) == (storage == "frsz2_16")
    masked = sum(m - c for c in cycles)
    assert res.steps == sum(cycles) + masked == m * len(cycles)
    assert res.spmvs == roofline.spmv_calls(cycles) + masked + len(cycles)


# -- named scopes ---------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled_frsz2():
    """The HLO text of a compiled frsz2_16 solve on the CPU, and its
    operator's rows and values."""
    solve, args, _, A = _solve("frsz2_16", grid=(8, 8, 8), m=20)
    return solve.lower(*args).compile().as_text(), A.shape[0], A.nnz


def test_the_solve_carries_every_scope(compiled_frsz2):
    text, _, _ = compiled_frsz2
    module = hlo.parse(text)
    found = {c for ins in module.instrs.values()
             for c in ins.op_name.split("/")}
    assert set(SCOPES) <= found


def test_the_scopes_move_no_op_between_layers_on_the_cpu(compiled_frsz2):
    text, n, nnz = compiled_frsz2
    module = hlo.parse(text)
    assert _moved(module, n, nnz, 20) == {}
    classes = hlo.classify(module, n=n, nnz=nnz, m=20)
    under = {}
    for name in _timed(module):
        scope = _innermost_scope(module.instrs[name])
        if scope in ("givens", "residual", "update"):
            under.setdefault(scope, set()).add(classes[name])
    assert set(under) == {"givens", "residual", "update"}
    assert set().union(*under.values()) <= {"driver", "spmv", "basis",
                                            "reductions"}


# -- traces recorded on the chip --------------------------------------------------

def _read(cell: str, name: str):
    path = FIXTURES / cell / name
    if path.suffix == ".xz":
        return lzma.decompress(path.read_bytes())
    if path.suffix == ".gz":
        return gzip.decompress(path.read_bytes()).decode()
    return path.read_text()


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    fixture = "atmos7_108.float32.scoped"
    inputs = json.loads(_read(fixture, "inputs.json.gz"))
    module = hlo.parse(_read(fixture, "hlo.txt.gz"))
    classes = hlo.classify(module, n=inputs["n"], nnz=inputs["nnz"], m=100)
    profile = ProfileData.from_serialized_xspace(
        _read(fixture, "trace.xplane.pb.xz"))
    red = devtrace.reduce_profile(profile, module, classes)
    result = json.loads(_read(fixture, "result.json"))
    return inputs, module, profile, red, result


def test_the_harness_reads_the_scoped_program_as_recorded(recorded):
    inputs, _, _, red, result = recorded
    cell = cells.load_cell("atmos7_108.float32")
    metrics = run.per_layer(cell, red, roofline.peak("TPU v5 lite"),
                            **inputs)
    # what the fixture recorded reads the same; of the readers added since,
    # only store_copy_ms finds its input (the fixture has no counters)
    assert set(metrics) == set(result["metrics"]) | {"store_copy_ms"}
    assert {k: metrics[k] for k in result["metrics"]} == result["metrics"]
    assert {"device_ops": red.top_ops(10),
            "idle_gaps": red.top_gaps(10)} == result["breakdown"]


def test_device_time_by_layer_and_innermost_scope(recorded):
    """Each layer's time splits by the innermost named scope of its ops;
    the store copies that XLA adds sit under none, and are what
    ``store_copy_ms`` reads."""
    inputs, _, _, red, _ = recorded
    by_layer: dict = {}
    for (layer, _), s in red.scope_s.items():
        by_layer[layer] = by_layer.get(layer, 0.0) + s
    assert by_layer == pytest.approx(red.layer_s)
    assert {("spmv", "spmv"), ("basis", "dots"), ("basis", "combine"),
            ("basis", "store"), ("driver", "givens"),
            ("basis", "")} <= set(red.scope_s)
    assert {scope for _, scope in red.scope_s} <= set(SCOPES) | {""}
    copy = run.per_layer(cells.load_cell("atmos7_108.float32"), red,
                         roofline.peak("TPU v5 lite"),
                         **inputs)["store_copy_ms"]["value"]
    assert copy == 1e3 * red.scope_s[("basis", "")]


def test_the_chip_program_carries_its_scopes_and_they_move_no_op(recorded):
    inputs, module, _, _, _ = recorded
    found = {c for ins in module.instrs.values()
             for c in ins.op_name.split("/")}
    # a float32 basis has no codec: no compress scope
    assert set(SCOPES) - {"compress"} <= found
    assert _moved(module, inputs["n"], inputs["nnz"], 100) == {}


def test_the_scopes_move_only_basis_work_of_the_frsz2_program():
    """On the chip's frsz2_16 program the source-file rule puts some of
    the codec's encode ops and a reduction of the dot-product kernel's
    output under the driver or the orthogonalizer (the source frame that
    ``hlo`` reads for them is the caller's); their ``compress``/``dots``
    scope puts them in the basis.  No other op moves."""
    text = _read("atmos7_108.frsz2_16.scoped", "hlo.txt.gz")
    module = hlo.parse(text)
    moved = _moved(module, n=16 ** 3, nnz=27136, m=100)
    for name, (was, now) in moved.items():
        assert was in ("driver", "orthogonalizer") and now == "basis", name
        assert _innermost_scope(module.instrs[name]) in ("compress", "dots")


def test_the_program_spans_sit_inside_the_dispatch_span(recorded):
    _, _, profile, _, _ = recorded
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    dispatch = [s for s in spans if s[2] == "bench.dispatch"]
    assert dispatch
    for name in SPANS:
        inside = [s for s in spans if s[2] == name]
        assert len(inside) == len(dispatch), name
        assert all(any(d0 <= a and b <= d1 for d0, d1, _ in dispatch)
                   for a, b, _ in inside), name
