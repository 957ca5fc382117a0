"""Assign each instruction of a compiled HLO module to a layer of the solver.

The classification reads only the HLO text of the executable that ran
(``compiled.as_text()``): each instruction's shape, its operands, the
computations it calls, and the source stack that JAX recorded for it
(``stack_frame_id`` into the module's ``FileNames`` / ``FileLocations`` /
``StackFrames`` tables).  It never names an op (``fusion.80``), since op
names change with every compile.

Rules, first match wins:

1. ``control``: control flow and bookkeeping (``while``, ``conditional``,
   tuples, ...).  Their trace events span their children, so they are left
   out of every layer's sum.
2. ``spmv``: the op or an op fused into it comes from ``repro/sparse/``, or
   sits in a named scope ``spmv``, or reads or writes an array of ``nnz``
   elements (the operator's values or indices), or the op is a
   ``collective-permute``: the solver sends point to point only the halo
   of a sharded SpMV (``dist/collectives.py`` ``halo_exchange``,
   ``halo_exchange_3d``), and XLA gives some of those exchanges the source
   line of the loop around them.
3. ``basis``: the op or an op fused into it comes from ``repro/core/`` or
   ``repro/kernels/`` (accessor, codec, Pallas kernels), or sits in a named
   scope ``dots``/``combine``/``compress``/``store``/``basis``, or reads or
   writes the basis store: an array with a dimension of ``m + 1`` and at
   least ``(m + 1) * n / 64`` elements (copies and relayouts of the store).
4. by the innermost source file of the op: ``solver/gmres.py`` ->
   ``driver``, ``solver/pipeline.py`` -> ``orthogonalizer``, ``dist/`` ->
   ``reductions``; anything else -> ``other``.

Rules 2 and 3 read the whole source stack, callers included: the
all-reduce of the basis dot products, in ``dist/collectives.py`` called
from ``core/accessor.py``, is basis work.

:func:`scope` gives an op's innermost named scope of the program
(``jax.named_scope``), which the trace reduction sums time by.
"""
from __future__ import annotations

import dataclasses
import math
import re

CONTROL_OPCODES = frozenset({
    "while", "conditional", "call", "tuple", "get-tuple-element",
    "parameter", "constant", "after-all", "opt-barrier", "partition-id",
    "replica-id",
})
SPMV_SCOPES = frozenset({"spmv", "matvec"})
BASIS_SCOPES = frozenset({"dots", "combine", "compress", "store", "basis"})
SOURCE_LAYERS = (          # (path fragment, layer) for rule 4, in order
    ("repro/solver/gmres.py", "driver"),
    ("repro/solver/pipeline.py", "orthogonalizer"),
    ("repro/dist/", "reductions"),
)

_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TABLE_ROW = re.compile(r"^(\d+)\s+(.*)$")
_KV = re.compile(r"(\w+)=(\d+)")
#: ``op_name`` components that JAX writes for its own structure, not scopes
_STRUCTURE = re.compile(
    r"^(?:while|body|cond|branch_\d+_fun|closed_call|core_call|remat|"
    r"checkpoint|shard_map|scan|pjit|custom_jvp_call|custom_vjp_call)$")


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    arrays: list            # [(dtype, dims)] of the result
    operands: list          # operand instruction names
    called: list            # called computation names
    op_name: str
    frame: int | None
    computation: str


@dataclasses.dataclass
class Module:
    name: str
    instrs: dict            # name -> Instr
    computations: dict      # name -> [instruction names]
    frames: dict            # frame id -> tuple of source files, innermost first


def _matching(s: str, i: int) -> int:
    """Index just past the bracket that closes ``s[i]``."""
    pairs = {"(": ")", "{": "}", "[": "]"}
    close, depth = pairs[s[i]], 0
    for k in range(i, len(s)):
        if s[k] == s[i]:
            depth += 1
        elif s[k] == close:
            depth -= 1
            if depth == 0:
                return k + 1
    return len(s)


def _arrays(shape: str) -> list:
    out = []
    for dtype, dims in _ARRAY.findall(shape):
        out.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _parse_instr(line: str, comp: str) -> Instr | None:
    m = _INSTR.match(line)
    if not m:
        return None
    name, rest = m.group(1), m.group(2)
    if rest.startswith("("):
        end = _matching(rest, 0)
    else:
        end = rest.find(" ")
    if end <= 0:
        return None
    shape, tail = rest[:end], rest[end:].lstrip()
    paren = tail.find("(")
    if paren <= 0:
        return None
    opcode = tail[:paren]
    close = _matching(tail, paren)
    operands = re.findall(r"%([\w.\-]+)", tail[paren:close])
    attrs = tail[close:]
    called = []
    for group in _CALLED.findall(attrs):
        called.extend(re.findall(r"%?([\w.\-]+)", group.strip("{}")))
    frame = _FRAME_ID.search(attrs)
    op_name = _OP_NAME.search(attrs)
    return Instr(name=name, opcode=opcode, arrays=_arrays(shape),
                 operands=operands, called=called,
                 op_name=op_name.group(1) if op_name else "",
                 frame=int(frame.group(1)) if frame else None,
                 computation=comp)


def _parse_tables(lines: list) -> dict:
    """Stack frame id -> source files, innermost first."""
    tables: dict = {}
    current = None
    for line in lines:
        s = line.strip()
        if s in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            current = tables.setdefault(s, {})
            continue
        row = _TABLE_ROW.match(s) if current is not None else None
        if row is None:            # a blank line or a computation ends it
            current = None
            continue
        current[int(row.group(1))] = row.group(2)
    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    locs = {k: dict((a, int(b)) for a, b in _KV.findall(v))
            for k, v in tables.get("FileLocations", {}).items()}
    raw = {k: dict((a, int(b)) for a, b in _KV.findall(v))
           for k, v in tables.get("StackFrames", {}).items()}
    frames = {}
    for fid in raw:
        chain, seen, cur = [], set(), fid
        while cur in raw and cur not in seen:
            seen.add(cur)
            loc = locs.get(raw[cur].get("file_location_id"), {})
            chain.append(files.get(loc.get("file_name_id"), ""))
            # the table prints a parent as its row plus one; the outermost
            # frame's parent reads 1, which is no row
            cur = raw[cur].get("parent_frame_id", 0) - 1
        frames[fid] = tuple(chain)
    return frames


def parse(text: str) -> Module:
    """Parse the text of a compiled HLO module."""
    lines = text.splitlines()
    name = lines[0].split()[1].rstrip(",") if lines else ""
    instrs, comps, comp = {}, {}, None
    for line in lines:
        if comp is None:
            h = _COMP_HEADER.match(line)
            if h and "=" not in line.split("(")[0]:
                comp = h.group(1)
                comps[comp] = []
            continue
        if line.strip() == "}":
            comp = None
            continue
        ins = _parse_instr(line, comp)
        if ins is not None:
            instrs[ins.name] = ins
            comps[comp].append(ins.name)
    return Module(name=name, instrs=instrs, computations=comps,
                  frames=_parse_tables(lines))


def scope(ins: Instr) -> str:
    """The innermost named scope of the program around ``ins``, from its
    ``op_name`` (``jit(solve)/while/body/spmv/dia/mul`` -> ``dia``); ``""``
    where it sits under none.  The last component names the primitive, and
    JAX's own components (transformations, loop and branch bodies, merged
    names) are no scopes."""
    parts = ins.op_name.split("/")[:-1]
    return next((c for c in reversed(parts)
                 if c and not _STRUCTURE.match(c)
                 and not any(ch in c for ch in "();")), "")


def _fused(module: Module, ins: Instr) -> list:
    """``ins`` and every instruction of the computations it fuses."""
    out, stack, seen = [ins], list(ins.called), set()
    if ins.opcode in CONTROL_OPCODES:
        return out
    while stack:
        c = stack.pop()
        if c in seen:
            continue
        seen.add(c)
        for n in module.computations.get(c, ()):
            sub = module.instrs[n]
            out.append(sub)
            stack.extend(sub.called)
    return out


def _elements(dims) -> int:
    return math.prod(dims) if dims else 1


def classify(module: Module, n: int, nnz: int, m: int) -> dict:
    """Instruction name -> layer, for every instruction of ``module``.

    ``n``, ``nnz``: the operator's rows and stored values; ``m``: the
    restart length (the store holds ``m + 1`` rows).
    """
    store_min = (m + 1) * -(-n // 64)
    out = {}
    for ins in module.instrs.values():
        if ins.opcode in CONTROL_OPCODES:
            out[ins.name] = "control"
            continue
        group = _fused(module, ins)
        files, scopes, arrays = set(), set(), []
        for g in group:
            files.update(module.frames.get(g.frame, ()))
            scopes.update(g.op_name.split("/"))
            arrays.extend(g.arrays)
            for o in g.operands:
                if o in module.instrs:
                    arrays.extend(module.instrs[o].arrays)
        sizes = {_elements(d) for _, d in arrays}
        if (ins.opcode.startswith("collective-permute")
                or scopes & SPMV_SCOPES or nnz in sizes
                or any("repro/sparse/" in f for f in files)):
            out[ins.name] = "spmv"
        elif (scopes & BASIS_SCOPES
              or any("repro/core/" in f or "repro/kernels/" in f
                     for f in files)
              or any((m + 1) in d and _elements(d) >= store_min
                     for _, d in arrays)):
            out[ins.name] = "basis"
        else:
            inner = module.frames.get(ins.frame, ("",))[:1]
            out[ins.name] = next(
                (layer for frag, layer in SOURCE_LAYERS
                 if inner and frag in inner[0]), "other")
    return out
