"""No solve program copies its whole Krylov store.

A restart cycle writes every row of its store before any trip reads it,
so nothing in the store outlives the cycle: the cycle allocates it, and no
restart loop carries it.  Carried through the restart loop's
``while_loop``, ``cond`` and ``switch``, the store was copied whole by XLA
(on the v5e, twice on every inner trip: half of each benchmark solve).
These tests count, in the optimized HLO of ``solve_program``'s executable,
the ``copy`` ops as large as a leaf of the store, on one device and on a
four-device sharded layout (emulated host devices in a subprocess, as in
``tests/test_layout.py``); the v5e's own compile is counted in
``tests/test_tpu_compile.py``.  Beside them: the device driver still
reproduces the host driver, field for field and bit for bit.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accessor import BasisAccessor, format_by_name
from repro.solver.gmres import gmres, solve_program
from repro.sparse import make_problem, rhs_for

ROOT = Path(__file__).resolve().parents[1]
SIDE, M = 16, 10                  # 4096 rows, GMRES(10)
STORAGES = ("float32", "frsz2_16")


def _storage(name):
    # frsz2_16 on the fused-kernel route, as the benchmark runs it
    return format_by_name(name, arith_dtype=np.float64,
                          use_kernels=name != "float32")


def _store(name, n):
    acc = BasisAccessor(fmt=_storage(name), m=M + 1, n=n,
                        arith_dtype=np.float64)
    return jax.eval_shape(acc.empty)


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys
import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core.accessor import format_by_name
from repro.solver import layout
from repro.solver.gmres import solve_program
from repro.sparse import make_problem, rhs_for

side, m, out_dir = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
A, _ = make_problem("synth:atmosmod", side ** 3)
b, _ = rhs_for(A)
layout.bytes_limit = lambda device: 1000      # no one device holds it
out = {}
for name in sys.argv[4:]:
    fmt = format_by_name(name, arith_dtype=np.float64,
                         use_kernels=name != "float32")
    solve, args, _ = solve_program(A, b, storage=fmt, m=m, max_iters=200,
                                   target_rrn=1e-8)
    path = os.path.join(out_dir, name + ".hlo")
    with open(path, "w") as f:
        f.write(solve.lower(*args).compile().as_text())
    out[name] = dict(hlo=path, devices=len(args[0].sharding.device_set),
                     n_local=args[0].shape[0] // 4)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    """The four-device programs' HLO files, by storage."""
    out_dir = tmp_path_factory.mktemp("four")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(SIDE), str(M), str(out_dir),
         *STORAGES], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", STORAGES)
def test_no_solve_program_copies_the_whole_store(name, devices, request,
                                                 whole_store_copies):
    if devices == 1:
        A, _ = make_problem("synth:atmosmod", SIDE ** 3)
        b, _ = rhs_for(A)
        solve, args, _ = solve_program(A, b, storage=_storage(name), m=M,
                                       max_iters=200, target_rrn=1e-8)
        hlo = solve.lower(*args).compile().as_text()
        n_local = A.shape[0]
    else:
        case = request.getfixturevalue("four_devices")[name]
        assert case["devices"] == 4, case
        hlo = Path(case["hlo"]).read_text()
        n_local = case["n_local"]
    store = _store(name, n_local)
    # the store is in the program: its row write is there, at its shape
    assert "dynamic-update-slice" in hlo
    assert whole_store_copies(hlo, store) == []


@pytest.mark.parametrize("policy", [
    dict(storage="float32"),
    dict(storage=format_by_name("frsz2_16", arith_dtype=np.float64,
                                use_kernels=True)),
    dict(policy="adaptive:float64,frsz2_32@1e-3"),
], ids=["float32", "frsz2_16", "two-level"])
def test_device_driver_reproduces_the_host_driver(policy):
    """Identical counters and history, and ``x`` to the bit: the store
    each cycle allocates holds what the carried one held."""
    A, _ = make_problem("synth:atmosmod", 12 ** 3)
    b, _ = rhs_for(A)
    # a restart restarts: GMRES(8) needs several cycles to 1e-9
    kw = dict(m=8, max_iters=400, target_rrn=1e-9, **policy)
    host = gmres(A, b, driver="host", **kw)
    dev = gmres(A, b, driver="device", **kw)
    assert host.converged and dev.converged
    assert len(dev.cycle_lengths) > 2
    for field in ("iterations", "steps", "spmvs", "restarts"):
        assert getattr(host, field) == getattr(dev, field), field
    np.testing.assert_array_equal(host.cycle_lengths, dev.cycle_lengths)
    assert host.rrn == dev.rrn
    np.testing.assert_array_equal(np.asarray(host.x), np.asarray(dev.x))


def test_two_level_policy_runs_both_levels():
    """The two-level case above switches format between cycles, so each
    ``lax.switch`` branch allocates and fills its own store."""
    from repro.solver.pipeline import resolve_policy

    policy = resolve_policy("adaptive:float64,frsz2_32@1e-3", None,
                            jnp.float64, 1e-9, 8)
    A, _ = make_problem("synth:atmosmod", 12 ** 3)
    b, _ = rhs_for(A)
    res = gmres(A, b, m=8, max_iters=400, target_rrn=1e-9,
                policy="adaptive:float64,frsz2_32@1e-3")
    levels = {int(policy.level(r, i)) for i, r in enumerate(res.restart_rrns)}
    assert levels == {0, 1}
