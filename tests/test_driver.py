"""Device-resident GMRES driver: parity with the host driver, batching,
and the storage-format protocol (mixed format, registry extension)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accessor import (
    BasisAccessor,
    FORMATS,
    MixedFormat,
    NativeFormat,
    StorageFormat,
    format_by_name,
    register_format,
)
from repro.solver import gmres
from repro.solver.gmres import gmres_batched
from repro.sparse import make_problem, rhs_for


def _problem(n=512):
    A, rrn = make_problem("synth:atmosmod", n)
    b, x_sol = rhs_for(A)
    return A, b, x_sol, rrn


# ---------------------------------------------------------------------------
# driver parity: the device-resident while_loop must replicate the host
# loop's restart decisions exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["float64", "float32", "frsz2_32"])
def test_device_driver_parity(fmt):
    A, b, _, rrn = _problem()
    kw = dict(storage=fmt, m=40, max_iters=4000, target_rrn=rrn)
    rh = gmres(A, b, driver="host", **kw)
    rd = gmres(A, b, driver="device", **kw)
    assert rh.iterations == rd.iterations, fmt
    assert rh.restarts == rd.restarts, fmt
    assert rh.converged == rd.converged, fmt
    np.testing.assert_allclose(rh.rrn, rd.rrn, rtol=1e-12)
    np.testing.assert_allclose(np.asarray(rh.x), np.asarray(rd.x),
                               rtol=1e-10, atol=1e-12)
    # restart schedule identical; per-iteration history equal to fusion noise
    np.testing.assert_allclose(rh.restart_rrns, rd.restart_rrns, rtol=1e-12)
    assert rh.rrn_history.shape == rd.rrn_history.shape
    np.testing.assert_allclose(rh.rrn_history, rd.rrn_history,
                               rtol=1e-10, atol=1e-15)
    # what the device ran: trips, operator applications, cycle lengths
    assert rh.steps == rd.steps > 0, fmt
    assert rh.spmvs == rd.spmvs, fmt
    np.testing.assert_array_equal(rh.cycle_lengths, rd.cycle_lengths)


def test_device_driver_stagnation_parity():
    """widerange stalls frsz2 (paper Fig. 9b): both drivers must cut off
    at the same iteration via the stagnation guard, not run to max_iters."""
    A, _ = make_problem("synth:widerange", 256)
    b, _ = rhs_for(A)
    kw = dict(storage="frsz2_32", m=20, max_iters=400, target_rrn=1e-12)
    rh = gmres(A, b, driver="host", **kw)
    rd = gmres(A, b, driver="device", **kw)
    assert rh.iterations == rd.iterations
    assert rh.converged == rd.converged
    assert rh.restarts == rd.restarts


def test_stagnated_flag_reported_by_both_drivers(monkeypatch):
    """Stagnation must be distinguishable from plain non-convergence: the
    guard's cutoff is surfaced as GmresResult.stagnated by both drivers
    (previously the device flag was dropped and the host break invisible).

    The guard only fires when the *implicit* estimate reaches the target at
    a cycle's final inner iteration while the explicit residual is frozen —
    an optimistic-estimate stall that real problems hit only through codec
    noise.  To pin the branch deterministically, stub the cycle: est hits
    the target exactly at the last position, the update is a no-op (empty
    store => zero combine), so every cycle repeats identically and the
    guard must cut the solve off at its 5th repeating cycle in both
    drivers, at the same iteration."""
    import importlib

    gmres_mod = importlib.import_module("repro.solver.gmres")
    m, target = 4, 1e-8

    def fake_cycle(matvec, acc, b_norm, w0, beta, eta, tgt, ortho, precond,
                   dist=None):
        ad = acc.arith_dtype
        R = jnp.eye(m + 1, m, dtype=ad)          # benign back-substitution
        g = jnp.zeros((m + 1,), ad)              # y == 0 => x unchanged
        # decreasing est that first meets the target at the last position
        # (interior multipliers strictly > 1, final strictly < 1)
        est = jnp.asarray(target * np.linspace(2.0, 0.9, m), ad)
        zero = jnp.asarray(0, jnp.int32)
        return acc.empty(), R, g, est, zero, zero + m

    monkeypatch.setattr(gmres_mod, "_cycle", fake_cycle)
    # fresh solve cache: the device program compiled from the fake cycle
    # must not outlive the test (the cache is process-global)
    from collections import OrderedDict

    monkeypatch.setattr(gmres_mod, "_SOLVE_CACHE", OrderedDict())
    A, b, _, _ = _problem(64)
    kw = dict(storage="float64", m=m, max_iters=97, target_rrn=target)
    rh = gmres(A, b, driver="host", **kw)
    rd = gmres(A, b, driver="device", **kw)
    for r in (rh, rd):
        assert not r.converged
        assert r.stagnated
        assert r.iterations == 5 * m      # guard patience: 5th flat cycle
    assert rh.restarts == rd.restarts


def test_not_stagnated_on_budget_exhaustion_or_convergence():
    """Iteration-budget exhaustion and normal convergence both report
    stagnated=False (stagnation is not conflated with non-convergence)."""
    A, _ = make_problem("synth:widerange", 256)
    b, _ = rhs_for(A)
    rb = gmres(A, b, storage="frsz2_32", m=20, max_iters=40,
               target_rrn=1e-12)
    assert not rb.converged and not rb.stagnated
    A2, b2, _, rrn2 = _problem(216)
    rc = gmres(A2, b2, m=20, max_iters=2000, target_rrn=rrn2)
    assert rc.converged and not rc.stagnated


def test_zero_iteration_budget_reports_initial_residual():
    """max_iters=0: both drivers report the true initial residual (the
    host loop never runs; its rrn must not be a sentinel)."""
    A, b, _, _ = _problem(64)
    rh = gmres(A, b, driver="host", m=5, max_iters=0)
    rd = gmres(A, b, driver="device", m=5, max_iters=0)
    assert not rh.converged and not rd.converged
    assert rh.iterations == rd.iterations == 0
    np.testing.assert_allclose(rh.rrn, rd.rrn, rtol=1e-12)
    np.testing.assert_allclose(rh.rrn, 1.0, rtol=1e-12)   # x0 = 0


def test_device_driver_trivial_rhs_converges_immediately():
    A, b, _, _ = _problem(216)
    x0 = jnp.asarray(np.linalg.solve(np.asarray(A.to_dense()),
                                     np.asarray(b)))
    res = gmres(A, b, x0=x0, m=20, max_iters=100, target_rrn=1e-10)
    assert res.converged
    assert res.iterations == 0
    assert res.restarts == 1
    # the initial residual and the skipped cycle's head, and no trip
    assert (res.steps, res.spmvs, res.cycle_lengths.size) == (0, 2, 0)


@pytest.mark.parametrize("fmt,m,target", [("float64", 10, None),
                                          ("frsz2_16", 40, 1e-6)])
def test_counters_count_what_the_device_ran(fmt, m, target):
    """GMRES(10) restarts after m trips; the 16-bit basis restarts early,
    when the implicit estimate meets the target and the explicit residual
    does not.  Either way every cycle runs all m trips (the tail after
    j_stop is masked) and three residuals frame the trips."""
    A, b, _, rrn = _problem()
    res = gmres(A, b, storage=fmt, m=m, max_iters=4000,
                target_rrn=rrn if target is None else target)
    cycles = res.cycle_lengths.size
    assert res.converged and cycles >= 2
    assert res.steps == m * cycles
    skipped_heads = res.restarts - cycles
    assert res.spmvs == 1 + cycles * (m + 2) + skipped_heads
    assert res.cycle_lengths.sum() == res.iterations
    assert (res.cycle_lengths[:-1] < m).any() == (fmt == "frsz2_16")


def test_gmres_compiles_nothing_for_a_new_iteration_count():
    """The result is trimmed on the host: a solve of the same operator
    that stops after a different number of iterations runs the cached
    program and compiles nothing."""
    from jax._src import dispatch

    A, b, _, rrn = _problem(256)
    b2 = jnp.asarray(np.random.default_rng(0).standard_normal(b.shape[0]))
    kw = dict(storage="float64", m=20, max_iters=2000, target_rrn=rrn)
    first = gmres(A, b, **kw)
    compiles = []

    def on_compile(event, _secs, **_kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        second = gmres(A, b2, **kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert second.iterations != first.iterations
    assert compiles == []


# ---------------------------------------------------------------------------
# batched driver
# ---------------------------------------------------------------------------


def test_gmres_batched_matches_single():
    A, b, _, rrn = _problem()
    n = b.shape[0]
    B = jnp.stack([b, 2.0 * b, b + 0.1 * jnp.sin(jnp.arange(n))])
    kw = dict(storage="frsz2_32", m=40, max_iters=4000, target_rrn=rrn)
    batched = gmres_batched(A, B, **kw)
    assert len(batched) == 3
    for i, rb in enumerate(batched):
        rs = gmres(A, B[i], driver="device", **kw)
        assert rb.iterations == rs.iterations, i
        assert rb.converged and rs.converged
        np.testing.assert_allclose(np.asarray(rb.x), np.asarray(rs.x),
                                   rtol=1e-10, atol=1e-14)
        # vmapped matvec fuses differently: schedule identical, values to
        # within a few ULP of the (tiny) restart residuals
        np.testing.assert_allclose(rb.restart_rrns, rs.restart_rrns,
                                   rtol=1e-6)


def test_gmres_batched_nonzero_x0_matches_single():
    """Batched parity with a *nonzero* initial guess (only zero-init was
    covered before): each system must follow the same trajectory as its
    single solve started from the same x0."""
    A, b, _, rrn = _problem(216)
    n = b.shape[0]
    t = jnp.arange(n, dtype=b.dtype)
    B = jnp.stack([b, 1.5 * b + 0.1 * jnp.sin(t)])
    X0 = jnp.stack([0.05 * jnp.cos(t), 0.01 * t / n])
    kw = dict(storage="float64", m=20, max_iters=2000, target_rrn=rrn)
    batched = gmres_batched(A, B, X0=X0, **kw)
    for i, rb in enumerate(batched):
        rs = gmres(A, B[i], x0=X0[i], driver="device", **kw)
        assert rb.converged and rs.converged, i
        assert rb.iterations == rs.iterations, i
        assert rb.restarts == rs.restarts, i
        np.testing.assert_allclose(np.asarray(rb.x), np.asarray(rs.x),
                                   rtol=1e-8, atol=1e-10)
        # a nonzero x0 must actually matter: zero-init takes a different
        # first restart residual
        rz = gmres(A, B[i], driver="device", **kw)
        assert abs(rz.restart_rrns[0] - rs.restart_rrns[0]) > 1e-8, i


def test_gmres_batched_independent_schedules():
    """Systems of different difficulty stop at different iteration counts."""
    A, b, _, rrn = _problem(256)
    n = b.shape[0]
    B = jnp.stack([b, jnp.ones((n,), b.dtype)])
    out = gmres_batched(A, B, storage="float64", m=20, max_iters=2000,
                        target_rrn=rrn)
    assert all(r.converged for r in out)
    assert len({r.iterations for r in out} | {0}) >= 2  # not lock-stepped


# ---------------------------------------------------------------------------
# storage-format protocol
# ---------------------------------------------------------------------------


def test_accessor_has_no_concrete_format_dispatch():
    import inspect

    src = inspect.getsource(BasisAccessor)
    assert "isinstance" not in src


def test_mixed_format_head_is_exact():
    rng = np.random.default_rng(3)
    m, n = 6, 256
    fmt = format_by_name("mixed:2:frsz2_16", arith_dtype=jnp.float64, bs=32)
    assert isinstance(fmt, MixedFormat) and fmt.k == 2
    acc = BasisAccessor(fmt=fmt, m=m, n=n, arith_dtype=jnp.float64)
    store = acc.empty()
    V = rng.standard_normal((m, n))
    for j in range(m):
        store = acc.write_row(store, j, jnp.asarray(V[j]))
    Vr = np.asarray(acc.read_all(store))
    # head rows roundtrip exactly (f64), tail rows carry frsz2_16 error
    np.testing.assert_array_equal(Vr[:2], V[:2])
    tail_err = np.abs(Vr[2:] - V[2:]).max()
    assert 0 < tail_err < 1e-3
    # nbytes: between all-compressed and all-f64
    full = NativeFormat(jnp.float64).nbytes(m, n)
    tail_only = fmt.tail.nbytes(m, n)
    assert tail_only < acc.nbytes() < full


def test_mixed_format_converges_between_f64_and_tail():
    A, b, _, rrn = _problem(512)
    kw = dict(m=40, max_iters=4000, target_rrn=rrn)
    it64 = gmres(A, b, storage="float64", **kw).iterations
    res_mixed = gmres(A, b, storage="mixed:4:frsz2_16", **kw)
    res_tail = gmres(A, b, storage="frsz2_16",
                     arith_dtype=jnp.float64, **kw)
    assert res_mixed.converged
    assert it64 <= res_mixed.iterations <= res_tail.iterations + 2


def test_register_format_extension_point():
    """Adding a format = implement the protocol + register; no solver edit."""

    class NegatedF32(NativeFormat):
        """Stores -V (exercises that all reads go through the protocol)."""

        @property
        def name(self):
            return "neg32"

        def write_row(self, store, j, v):
            return store.at[j].set((-v).astype(self.dtype))

        def read_row(self, store, j, arith_dtype, n):
            return (-store[j]).astype(arith_dtype)

        def read_all(self, store, arith_dtype, n):
            return (-store).astype(arith_dtype)

    register_format("neg32")(lambda name, **ctx: NegatedF32(jnp.float32))
    try:
        fmt = format_by_name("neg32")
        assert isinstance(fmt, StorageFormat)
        A, b, _, rrn = _problem(256)
        res = gmres(A, b, storage="neg32", m=40, max_iters=4000,
                    target_rrn=rrn)
        assert res.converged
    finally:
        FORMATS.pop("neg32", None)
