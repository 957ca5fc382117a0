"""The benchmark's yardstick on the CPU: peaks, least bytes, the plain
reference and its operator hash, and how cells are found by name."""
from __future__ import annotations

import hashlib
import json
import re
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import cells  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


# -- peaks and least bytes ---------------------------------------------------

def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in peaks.json"):
        roofline.peak("TPU v99")


def test_v5e_peaks_from_the_table():
    p = roofline.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("fmt,bits", [("float32", 32), ("frsz2_16", 17),
                                      ("float16", 16), ("frsz2_32", 33)])
def test_stored_bits_per_value(fmt, bits):
    assert roofline.stored_bits(fmt) == bits


def _rows(k):
    # iteration j: one row written, j + 1 read by dots, j + 1 by combine
    return sum(1 + 2 * (j + 1) for j in range(k))


@pytest.mark.parametrize("bits", [32, 17])
def test_basis_bytes_count_j_plus_1_rows_per_pass(bits):
    n = 1000
    assert roofline.basis_bytes([33], n, bits) == _rows(33) * n * bits / 8
    # restarts begin again at j = 0: 5 iterations of GMRES(2) = 2 + 2 + 1
    rows = sum(_rows(k) for k in (2, 2, 1))
    assert roofline.basis_bytes([2, 2, 1], n, bits) == rows * n * bits / 8
    # a solve that restarts early after 33 and ends 8 later: 33 + 8, not 41
    early = roofline.basis_bytes([33, 8], n, bits)
    assert early == (_rows(33) + _rows(8)) * n * bits / 8
    assert early < roofline.basis_bytes([41], n, bits)


def test_spmv_bytes_have_no_index_bytes():
    n, nnz = 1000, 7000
    assert roofline.spmv_bytes(n, nnz, 4) == 4 * nnz + 4 * n + 4 * n
    # the same count for any index width: the formula takes none
    assert roofline.spmv_bytes(n, nnz, 4) < 4 * nnz + 4 * nnz + 4 * (n + 1)


@pytest.mark.parametrize("cycles,calls", [([33], 35), ([100], 102),
                                          ([100, 1], 104), ([33, 8], 44)])
def test_spmv_calls(cycles, calls):
    assert roofline.spmv_calls(cycles) == calls


def _history(cycles, target, m):
    """An implicit-residual history as the restart driver writes it: each
    cycle's estimates fall towards the target and end at or under it,
    unless the cycle runs all ``m`` iterations."""
    out = []
    for k in cycles:
        est = np.geomspace(1.0, target * 4, k).astype(np.float32)
        if k < m:
            est[-1] = np.float32(target)
        out.append(est)
    return np.concatenate(out)


@pytest.mark.parametrize("cycles,m", [([33, 8], 100), ([41], 100),
                                      ([100, 100, 7], 100), ([5, 5, 2], 5),
                                      ([3, 1, 4], 100)])
def test_cycle_lengths_from_the_history(cycles, m):
    target = 1.15e-6
    hist = _history(cycles, target, m)
    assert roofline.cycle_lengths(hist, target, m) == cycles


def test_share_is_none_without_time_and_exact_with():
    assert roofline.share_pct(1e9, 0.0, 819e9) is None
    assert roofline.share_pct(819e9, 2.0, 819e9) == pytest.approx(50.0)


# -- the plain reference -----------------------------------------------------

def _config(name):
    return cells._load_json(BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_operator_is_the_programs(name):
    from repro.sparse import make_problem

    cfg = _config(name)
    A, _ = make_problem(cfg["problem"], 6 ** 3, dtype=np.float32)
    ref = reference.stencil_csr(cfg, grid=(6, 6, 6))
    assert (reference.operator_sha256(A.indptr, A.indices, A.data)
            == reference.operator_sha256(*ref))


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_hash_is_the_reference_operator(name):
    cfg = _config(name)
    indptr, indices, data = reference.stencil_csr(cfg)
    assert indptr[-1] == cfg["nnz"] and indptr.size - 1 == cfg["n"]
    assert reference.operator_sha256(indptr, indices, data) == \
        cfg["operator_sha256"]


def test_changed_operator_fails_the_hash():
    cfg = _config("atmos7_108")
    indptr, indices, data = reference.stencil_csr(cfg, grid=(5, 5, 5))
    digest = reference.operator_sha256(indptr, indices, data)
    # entry order within a row is not part of the operator
    row = slice(indptr[7], indptr[8])
    shuffled_i, shuffled_d = indices.copy(), data.copy()
    shuffled_i[row], shuffled_d[row] = indices[row][::-1], data[row][::-1]
    assert reference.operator_sha256(indptr, shuffled_i, shuffled_d) == digest
    easier = data.copy()
    easier[indptr[3]] *= np.float32(1.001)       # one coefficient changed
    assert reference.operator_sha256(indptr, indices, easier) != digest
    moved = indices.copy()
    moved[indptr[3]] = (moved[indptr[3]] + 1) % (indptr.size - 1)
    assert reference.operator_sha256(indptr, moved, data) != digest


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(np.asarray(x, np.float32))


def _csr(op):
    import jax.numpy as jnp
    from repro.sparse.csr import CSR

    indptr, indices, data = op
    return CSR(jnp.asarray(indptr, jnp.int32), jnp.asarray(indices, jnp.int32),
               jnp.asarray(data), (indptr.size - 1, indptr.size - 1))


def test_reference_accepts_a_converged_x_and_flags_a_perturbed_one():
    cfg = _config("stencil27_104")
    op = reference.stencil_csr(cfg, grid=(6, 6, 6))
    x = np.random.default_rng(3).standard_normal(op[0].size - 1)
    b = reference.matvec64(*op, x)
    assert reference.true_rrn(op, b, x) < 1e-14
    x_bad = x.copy()
    x_bad[5] += 1e-4
    assert reference.true_rrn(op, b, x_bad) > cfg["target_rrn"]
    # a float32 solution of the float32-rounded b passes
    from repro.solver import gmres

    A = _csr(op)
    res = gmres(A, _f32(b), m=100, max_iters=400,
                target_rrn=cfg["target_rrn"], arith_dtype=np.float32)
    assert res.converged
    assert reference.true_rrn(op, np.asarray(b, np.float32), res.x) <= \
        cfg["target_rrn"]


def test_cycle_lengths_of_a_solve_that_restarts_early():
    """A real solve through the restart driver with a 16-bit FRSZ2 basis,
    which restarts before ``m`` (as the frsz2_16 cell does after 33): the
    cycles read from its returned history are as many as the driver ran,
    the first ends early, and they sum to its iterations."""
    import jax.numpy as jnp
    from repro.core.accessor import format_by_name
    from repro.solver.gmres import _device_result, solve_program

    cfg = _config("atmos7_108")
    op = reference.stencil_csr(cfg, grid=(10, 10, 10))
    b = reference.matvec64(*op, np.random.default_rng(1).standard_normal(
        op[0].size - 1)).astype(np.float32)
    m, target = 100, 1e-6
    storage = format_by_name("frsz2_16", arith_dtype=np.float32)
    solve, args, _ = solve_program(_csr(op), jnp.asarray(b), storage=storage,
                                   m=m, max_iters=400, target_rrn=target,
                                   arith_dtype=np.float32)
    state = solve(*args)
    res = _device_result(state)
    got = roofline.cycle_lengths(res.rrn_history, target, m)
    assert res.converged
    assert len(got) == int(state["cycles"]) >= 2
    assert got[0] < m and sum(got) == res.iterations
    assert roofline.spmv_calls(got) == res.iterations + len(got) + 1


# -- cells found by name -----------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(x) for x in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m["workloads"]) <= {w["name"] for w in SPEC["workloads"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(x["why"]) <= 200


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_with_its_metrics(workload):
    cell = cells.load_cell(workload)
    assert cell.chips in (1, 4)
    assert cell.config["operator_sha256"]
    assert {"setup_s", "solve_s"} <= {m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]))


def _four_chip_cells_allowed(workloads) -> bool:
    """The contract's rule: at most half the cells, rounded down, ask for
    four chips, and one always may."""
    four = sum(w["chips"] == 4 for w in workloads)
    return four <= max(1, len(workloads) // 2)


def test_at_most_half_the_cells_take_four_chips():
    assert {w["chips"] for w in SPEC["workloads"]} <= {1, 4}
    assert _four_chip_cells_allowed(SPEC["workloads"])


@pytest.mark.parametrize("four,cells_,allowed", [
    (0, 3, True), (1, 1, True), (1, 3, True), (2, 3, False), (4, 8, True),
    (5, 8, False), (12, 24, True), (13, 24, False)])
def test_the_four_chip_rule_counts_half_rounded_down(four, cells_, allowed):
    workloads = [{"chips": 4}] * four + [{"chips": 1}] * (cells_ - four)
    assert _four_chip_cells_allowed(workloads) is allowed


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_added_workload_is_picked_up_without_editing_a_file(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(root / "bench")
    # a later change adds a traffic file, a metric reader and their entries
    (root / "bench" / "traffic" / "float16.json").write_text(json.dumps(
        {"storage": "float16", "kernels": False}))
    (root / "bench" / "metrics" / "solves.py").write_text(
        "def read(ctx):\n    return float(len(ctx.iterations))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "atmos7_108.float16",
                              "config": "atmos7_108", "traffic": "float16",
                              "chips": 1, "why": "cast compression"})
    spec["per_layer"].append({"name": "solves", "unit": "solves",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "solve_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.load_cell("atmos7_108.float16", root=root)
    assert cell.traffic["storage"] == "float16"
    assert cell.config["name"] == "atmos7_108"
    assert "solves" in [m["name"] for m in cell.per_layer]
    read = cells.metric_reader("solves", root=root)
    assert read(type("C", (), {"iterations": [3, 4]})) == 2.0
    after = _digests(root / "bench")
    assert {p: d for p, d in after.items() if p in before} == before


def test_added_four_chip_workload_is_picked_up_without_editing_a_file(
        tmp_path):
    """A later change adds a cell on four chips with a configuration, a
    traffic file and entries alone: it resolves with its metrics, keeps to
    the four-chip rule, and no file of the benchmark changes."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(root / "bench")
    cfg = _config("atmos7_108")
    cfg.update(name="atmos7_336", n=336 ** 3)
    cfg["stencil"] = dict(cfg["stencil"], grid=[336, 336, 336])
    (root / "bench" / "configs" / "atmos7_336.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "float32_shard4.json").write_text(
        json.dumps({"storage": "float32", "kernels": False}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    name = "atmos7_336.float32_shard4"
    spec["configs"].append({"name": "atmos7_336", "source": "arXiv",
                            "file": "bench/configs/atmos7_336.json",
                            "reduced": [], "why": "one system on 4 chips"})
    spec["workloads"].append({"name": name, "config": "atmos7_336",
                              "traffic": "float32_shard4", "chips": 4,
                              "why": "halo exchange and all-reduces"})
    for m in spec["per_layer"]:
        m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.load_cell(name, root=root)
    assert cell.chips == 4 and cell.config["n"] == 336 ** 3
    assert _four_chip_cells_allowed(spec["workloads"])
    assert {m["name"] for m in cell.per_layer} == \
        {m["name"] for m in SPEC["per_layer"]}
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"], root=root))
    after = _digests(root / "bench")
    assert {p: d for p, d in after.items() if p in before} == before
