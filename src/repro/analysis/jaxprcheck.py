"""spmdcheck Part A: collective-uniformity verification over closed jaxprs.

Stage 3 of ``repro.analysis``.  The AST lint (stage 1) sees Python; the
trace audits (stage 2) see compiled behaviour on concrete inputs; this
module reads the *program* — the closed jaxpr of a driver — and verifies
the one property neither of the other stages can: that every shard issues
the same collective sequence.  A ``shard_map`` program hangs (or silently
corrupts) when shards disagree on how many collectives to run, and JAX
cannot diagnose it at trace time because each shard's trace is identical —
the divergence only exists across devices at runtime.

The walker abstractly interprets shard-variance through the jaxpr: inside
``shard_map``, an input is *varying* iff its ``in_specs`` bind it to a mesh
axis; reductions over the mesh axis (``psum``/``pmean``/``pmax``/``pmin``/
``all_gather`` without ``axis_index_groups``) produce *invariant* outputs —
the mechanism that keeps the real solver's convergence predicates in
lockstep; ``ppermute``/``axis_index``/friends stay varying.  Control flow:

  * ``while`` — trip counts are fixpointed over the carry; a collective
    anywhere under a loop whose predicate is shard-varying is flagged
    (``nonuniform-collective``): shards would run different trip counts and
    the collective deadlocks.
  * ``cond`` — an invariant predicate is always fine (all shards take the
    same branch).  A *varying* predicate is fine only if every branch
    issues the identical collective sequence; a mismatch is flagged.
  * ``scan`` — static ``length``, always uniform.

Structural checks ride the same walk: every ``ppermute`` permutation must
be a partial injection on the mesh axis (``bad-permutation``, shared
definition in :func:`repro.dist.collectives.perm_defect`), and every
collective's axis names must be bound by the enclosing mesh — a collective
outside any ``shard_map`` is itself a finding (``axis-mismatch``).

Each collective becomes a :class:`CollectiveSite` carrying its operand
aval bytes and the enclosing loop structure; ``repro.analysis.traffic``
prices those sites against the hand-maintained wire model.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.extend.core as jex_core
import numpy as np

from repro.analysis.report import Finding
from repro.analysis.rules import COLLECTIVE_PRIMITIVES
from repro.dist.collectives import perm_defect

__all__ = [
    "CollectiveSite",
    "check_jaxpr",
    "run_local_checks",
]

#: collectives whose outputs are device-invariant along the reduced axis
#: (full reductions / gathers — every shard ends up holding the same value)
_INVARIANT_OUT = frozenset({"psum", "pmean", "pmax", "pmin", "all_gather"})


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective equation found in a jaxpr walk."""

    prim: str                     # primitive name (psum, ppermute, ...)
    path: str                     # eqn path, e.g. "shard_map@0/while@7[body]/psum@3"
    nbytes: int                   # total operand payload bytes
    size: int                     # total operand element count
    shapes: tuple[str, ...]       # operand avals, e.g. ("f64[7]",)
    axes: tuple[str, ...]         # named axes the collective runs over
    loops: tuple[tuple, ...]      # enclosing ("while", path, varying) /
    #                               ("scan", path, length) /
    #                               ("cond", path, branch, varying) entries
    axis_size: int | None = None  # all_gather's gather factor
    perm: tuple | None = None     # ppermute's (src, dst) pairs

    def signature(self):
        """Identity for branch-sequence comparison: what the fabric sees."""
        return (self.prim, self.shapes, self.axes)


def _open(j):
    return j.jaxpr if isinstance(j, jex_core.ClosedJaxpr) else j


def _body_jaxpr(params):
    """The single sub-jaxpr of a call-like primitive (pjit, custom_jvp,
    remat, shard_map...), or None."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = params.get(key)
        if isinstance(sub, (jex_core.ClosedJaxpr, jex_core.Jaxpr)):
            return _open(sub)
    return None


def _binds_axis(spec) -> bool:
    """True iff a shard_map PartitionSpec splits some dim over a mesh axis."""
    return any(entry is not None for entry in spec)


def _axis_names(params) -> tuple[str, ...]:
    """Named axes of a collective eqn (positional vmap axes filtered out)."""
    axes = params.get("axes", params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _aval_str(aval) -> str:
    dt = np.dtype(aval.dtype)
    return f"{dt.kind}{dt.itemsize * 8}[{','.join(map(str, aval.shape))}]"


def _operand_bytes(eqn):
    size = nbytes = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        n = int(np.prod(aval.shape)) if aval.shape else 1
        size += n
        nbytes += n * np.dtype(aval.dtype).itemsize
    return size, nbytes


class _Walker:
    """One abstract-interpretation pass over a jaxpr tree.

    ``emit`` gates site/finding recording: while/scan carry fixpoints
    re-walk their bodies until the variance assignment stabilizes, and
    only the final walk records.
    """

    def __init__(self, label: str):
        self.label = label
        self.findings: list[Finding] = []
        self.sites: list[CollectiveSite] = []
        self.emit = True

    def finding(self, rule: str, message: str):
        if self.emit:
            self.findings.append(
                Finding(path=f"jaxpr:{self.label}", line=0, rule=rule,
                        message=message))

    # -- the walk ----------------------------------------------------------

    def walk(self, jaxpr, in_vals, mesh, path, loops):
        """Returns the variance of ``jaxpr.outvars`` given invar variance.

        ``mesh`` is ``None`` outside shard_map, else ``{axis_name: size}``.
        """
        env: dict = {}

        def val(atom):
            if isinstance(atom, jex_core.Literal):
                return False
            return env.get(atom, False)

        for v in jaxpr.constvars:
            env[v] = False
        for v, b in zip(jaxpr.invars, in_vals):
            env[v] = bool(b)

        for i, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            ivals = [val(a) for a in eqn.invars]
            here = f"{path}/{prim}@{i}" if path else f"{prim}@{i}"
            if prim == "shard_map":
                outs = self._shard_map(eqn, here, loops)
            elif prim == "while":
                outs = self._while(eqn, ivals, mesh, here, loops)
            elif prim == "cond":
                outs = self._cond(eqn, ivals, mesh, here, loops)
            elif prim == "scan":
                outs = self._scan(eqn, ivals, mesh, here, loops)
            elif prim in COLLECTIVE_PRIMITIVES:
                outs = self._collective(eqn, mesh, here, loops)
            elif prim == "axis_index":
                outs = [True] * len(eqn.outvars)
            else:
                sub = _body_jaxpr(eqn.params)
                if sub is not None:
                    outs = self._call(eqn, sub, ivals, mesh, here, loops)
                else:
                    anyv = any(ivals)
                    outs = [anyv] * len(eqn.outvars)
            for v, b in zip(eqn.outvars, outs):
                env[v] = bool(b)
        return [val(v) for v in jaxpr.outvars]

    def _call(self, eqn, sub, ivals, mesh, here, loops):
        n = len(sub.invars)
        outs = self.walk(sub, (ivals + [False] * n)[:n], mesh, here, loops)
        if len(outs) != len(eqn.outvars):
            outs = [any(outs)] * len(eqn.outvars)
        return outs

    def _shard_map(self, eqn, here, loops):
        params = eqn.params
        mesh = {str(k): int(v) for k, v in dict(params["mesh"].shape).items()}
        sub = _open(params["jaxpr"])
        vals = [_binds_axis(spec) for spec in params["in_specs"]]
        vals = (vals + [True] * len(sub.invars))[:len(sub.invars)]
        self.walk(sub, vals, mesh, here, loops)
        outs = [_binds_axis(spec) for spec in params["out_specs"]]
        return (outs + [True] * len(eqn.outvars))[:len(eqn.outvars)]

    def _while(self, eqn, ivals, mesh, here, loops):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_j, body_j = _open(p["cond_jaxpr"]), _open(p["body_jaxpr"])
        cconsts, bconsts = ivals[:cn], ivals[cn:cn + bn]
        carry = list(ivals[cn + bn:])
        prev, self.emit = self.emit, False
        try:
            for _ in range(len(carry) + 2):
                outs = self.walk(body_j, bconsts + carry, mesh, here, loops)
                new = [a or b for a, b in zip(carry, outs)]
                if new == carry:
                    break
                carry = new
            pred = bool(self.walk(cond_j, cconsts + carry, mesh, here,
                                  loops)[0])
        finally:
            self.emit = prev
        mark = loops + (("while", here, pred),)
        outs = self.walk(body_j, bconsts + carry, mesh, here + "[body]", mark)
        self.walk(cond_j, cconsts + carry, mesh, here + "[cond]", mark)
        return outs

    def _cond(self, eqn, ivals, mesh, here, loops):
        pred, ops = ivals[0], ivals[1:]
        outs_any = None
        seqs = []
        for bi, br in enumerate(eqn.params["branches"]):
            bj = _open(br)
            mark = loops + (("cond", here, bi, pred),)
            n0 = len(self.sites)
            vals = (list(ops) + [False] * len(bj.invars))[:len(bj.invars)]
            outs = self.walk(bj, vals, mesh, f"{here}[br{bi}]", mark)
            seqs.append(tuple(s.signature() for s in self.sites[n0:]))
            outs_any = (list(outs) if outs_any is None
                        else [a or b for a, b in zip(outs_any, outs)])
        if pred:
            outs_any = [True] * len(outs_any or eqn.outvars)
            if len(set(seqs)) > 1:
                parts = "; ".join(
                    f"br{i}: [{', '.join('/'.join(map(str, s)) for s in q)}]"
                    or f"br{i}: []" for i, q in enumerate(seqs))
                self.finding(
                    "nonuniform-collective",
                    f"shard-varying predicate at {here} selects between "
                    f"branches with mismatched collective sequences ({parts})"
                    ": shards taking different branches would issue "
                    "different collectives and the program hangs")
        return outs_any if outs_any is not None else []

    def _scan(self, eqn, ivals, mesh, here, loops):
        p = eqn.params
        sub = _open(p["jaxpr"])
        nc, nk = p["num_consts"], p["num_carry"]
        consts, xs = ivals[:nc], ivals[nc + nk:]
        carry = list(ivals[nc:nc + nk])
        prev, self.emit = self.emit, False
        try:
            for _ in range(len(carry) + 2):
                outs = self.walk(sub, consts + carry + xs, mesh, here, loops)
                new = [a or b for a, b in zip(carry, outs[:nk])]
                if new == carry:
                    break
                carry = new
        finally:
            self.emit = prev
        mark = loops + (("scan", here, int(p["length"])),)
        return self.walk(sub, consts + carry + xs, mesh, here + "[body]",
                         mark)

    def _collective(self, eqn, mesh, here, loops):
        prim = eqn.primitive.name
        axes = _axis_names(eqn.params)
        size, nbytes = _operand_bytes(eqn)
        perm = axis_size = None
        if prim == "ppermute":
            perm = tuple((int(s), int(d)) for s, d in eqn.params["perm"])
        if "axis_size" in eqn.params:
            axis_size = int(eqn.params["axis_size"])
        if self.emit:
            self.sites.append(CollectiveSite(
                prim=prim, path=here, nbytes=nbytes, size=size,
                shapes=tuple(_aval_str(v.aval) for v in eqn.invars
                             if hasattr(getattr(v, "aval", None), "shape")),
                axes=axes, loops=loops, axis_size=axis_size, perm=perm))
            if mesh is None:
                self.finding(
                    "axis-mismatch",
                    f"{prim} at {here} runs outside any shard_map: no "
                    "device axis is bound at this point in the program")
            else:
                missing = [a for a in axes if a not in mesh]
                if missing:
                    self.finding(
                        "axis-mismatch",
                        f"{prim} at {here} names axis {missing} but the "
                        f"enclosing mesh binds {sorted(mesh)}")
                if prim == "ppermute":
                    ax = mesh.get(axes[0]) if axes else None
                    defect = perm_defect(perm, ax)
                    if defect is not None:
                        self.finding(
                            "bad-permutation",
                            f"ppermute at {here} has a malformed "
                            f"permutation: {defect} (perm={perm})")
        uniform = (prim in _INVARIANT_OUT
                   and eqn.params.get("axis_index_groups") is None
                   and mesh is not None and bool(axes)
                   and all(a in mesh for a in axes))
        return [not uniform] * len(eqn.outvars)


def check_jaxpr(closed, *, label: str):
    """Walk one closed jaxpr; returns ``(sites, findings)``.

    ``sites`` is every collective equation found (with operand bytes and
    loop context — the input to the stage-3 traffic pricing);
    ``findings`` carries the uniformity/structure violations.
    """
    jaxpr = _open(closed)
    w = _Walker(label)
    w.walk(jaxpr, [False] * len(jaxpr.invars), None, "", ())
    findings = list(w.findings)
    for s in w.sites:
        varying = [e for e in s.loops if e[0] == "while" and e[2]]
        if varying:
            findings.append(Finding(
                path=f"jaxpr:{label}", line=0, rule="nonuniform-collective",
                message=(f"{s.prim} at {s.path} executes under a "
                         f"shard-varying while trip count "
                         f"({varying[-1][1]}): shards would run different "
                         "iteration counts and the collective deadlocks")))
    return w.sites, findings


# ---------------------------------------------------------------------------
# Local driver walks (single device: the drivers must be collective-free)
# ---------------------------------------------------------------------------


def run_local_checks() -> list[Finding]:
    """Walk the host/device/block driver jaxprs on a single device.

    Off the sharded path no collective may appear at all (the walker's
    ``mesh is None`` rule), and the control-flow extraction must come back
    clean — this is also the smoke test that the walker handles every
    higher-order primitive the real drivers emit.
    """
    import importlib

    import jax.numpy as jnp

    from repro.analysis.traceaudit import _pin_environment, _problem

    _pin_environment()
    G = importlib.import_module("repro.solver.gmres")
    from repro.solver.block import build_block_solve

    findings: list[Finding] = []
    A, b, _ = _problem()
    kw = dict(storage="float64", m=6, max_iters=60, target_rrn=1e-8)
    vec = jax.ShapeDtypeStruct(b.shape, b.dtype)

    solve, _accs = G.build_device_solve(A, b, **kw)
    _, f = check_jaxpr(jax.make_jaxpr(solve)(vec, vec),
                       label="device-driver")
    findings += f

    B = jnp.stack([b, b * 2.0])
    bsolve, _baccs = build_block_solve(A, B, **kw)
    bvec = jax.ShapeDtypeStruct(B.shape, B.dtype)
    _, f = check_jaxpr(jax.make_jaxpr(bsolve)(bvec, bvec),
                       label="block-driver")
    findings += f

    # the host driver's unit of compilation is the cycle kernel
    pipe = G._resolve(A, b, "float64", None, 6, None, None, None, "mgs",
                      1e-8)

    def cycle(w0, beta, b_norm):
        return G._cycle(pipe.matvec, pipe.accs[0], b_norm, w0, beta,
                        0.7071067811865475, 1e-8, pipe.ortho, pipe.precond)

    scalar = jax.ShapeDtypeStruct((), b.dtype)
    _, f = check_jaxpr(jax.make_jaxpr(cycle)(vec, scalar, scalar),
                       label="host-cycle")
    findings += f
    return findings
