"""Record a profiler trace of the window and reduce it to layer times.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes:

* device operations: the events on each device plane's ``XLA Ops`` line,
  each named by its HLO instruction, in the program that the ``XLA Modules``
  event around it names;
* host spans: the harness's own ``bench.*`` ``TraceAnnotation`` events.

Busy time is the union of the device operations' intervals inside the
``bench.window`` span, averaged over the devices that ran any; layer time is
the sum of the durations of the operations that :mod:`hlo` assigns to the
layer, from the HLO text of the executable that ran.  Operations of other
programs (eager ops dispatched around the solve) count as busy and are
reported as ``program:<module>``.

A trace of several chips has one device plane each, and the reduction reads
them so that every reading is one chip's:

* ``busy_s`` is the mean over the devices that ran any operation;
* ``layer_s``, ``op_s`` and ``scope_s`` are chip-seconds, summed over the
  devices, so a per-iteration time in ms is chip-ms;
* a roofline share divides the algorithm's least bytes, all chips' together,
  by chip-seconds times one chip's bandwidth: four chips that each move a
  quarter of the bytes at peak read 100%, as one chip that moves them all;
* ``chips`` counts the devices that ran operations of the solve program.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from pathlib import Path

import hlo

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(trace_dir: Path) -> None:
    """Start the profiler, with the Python tracer off (it slows the host
    and adds an event per Python call)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    Path(trace_dir).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def xplane_file(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


@dataclasses.dataclass
class Reduction:
    layer_s: dict       # layer -> summed device seconds
    op_s: dict          # (layer, op) -> summed device seconds
    scope_s: dict       # (layer, innermost named scope or "") -> seconds
    busy_s: float
    window_s: float
    chips: int          # device planes that ran the solve program's ops
    gaps: list          # [(host span label, seconds)] of idle gaps

    def top_ops(self, k: int) -> list:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]
        return [[f"{layer}:{op}", s] for (layer, op), s in ops]

    def top_gaps(self, k: int) -> list:
        return [list(g) for g in sorted(self.gaps, key=lambda g: -g[1])[:k]]


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _module_of(modules: list, t: float) -> str:
    """Name of the module event (sorted ``(t0, t1, name)``) spanning ``t``."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


def device_ops(profile) -> dict:
    """Device plane -> ``[(t0_ns, t1_ns, op, module)]`` of its operations.

    On a TPU the ``XLA Ops`` events are named by the instruction's HLO text
    (``%fusion.80 = f32[...] fusion(...)``), and the ``XLA Modules`` events
    (``jit_solve(<fingerprint>)``) span the operations of each program run.
    """
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                          e.name.split("(")[0])
                         for e in lines.get(MODULES_LINE, ()))
        ops = [(e.start_ns, e.start_ns + e.duration_ns,
                e.name.split(" = ")[0].strip().lstrip("%"),
                _module_of(modules, e.start_ns))
               for e in lines.get(OPS_LINE, ())]
        if ops:
            out[plane.name] = ops
    return out


def host_spans(profile) -> list:
    """``[(t0_ns, t1_ns, name)]`` of the harness's ``bench.*`` spans."""
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


def reduce_profile(profile, module: hlo.Module, classes: dict) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Reduction`."""
    spans = host_spans(profile)
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1, _ = windows[0]
    spans = [s for s in spans if s[2] != WINDOW_SPAN]
    layer_s = collections.Counter()
    op_s = collections.Counter()
    scope_s = collections.Counter()
    busy, gaps, chips = [], [], 0
    for events in device_ops(profile).values():
        inside = [(max(a, w0), min(b, w1), op, mod)
                  for a, b, op, mod in events if b > w0 and a < w1]
        merged = _union((a, b) for a, b, _, _ in inside)
        busy.append(sum(b - a for a, b in merged))
        chips += any(mod == module.name for _, _, _, mod in inside)
        for a, b, op, mod in inside:
            ours = mod == module.name
            layer = classes.get(op, "unknown") if ours else f"program:{mod}"
            if layer == "control":
                continue
            ins = module.instrs.get(op) if ours else None
            layer_s[layer] += (b - a) * 1e-9
            op_s[(layer, op)] += (b - a) * 1e-9
            scope_s[(layer, hlo.scope(ins) if ins else "")] += (b - a) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((_label(spans, g0, g1), (g1 - g0) * 1e-9))
    busy_s = sum(busy) / len(busy) * 1e-9 if busy else 0.0
    return Reduction(layer_s=dict(layer_s), op_s=dict(op_s),
                     scope_s=dict(scope_s), busy_s=busy_s,
                     window_s=(w1 - w0) * 1e-9, chips=chips, gaps=gaps)


def _label(spans: list, g0: float, g1: float) -> str:
    """The host span that overlaps the gap ``[g0, g1]`` most."""
    best = max(spans, key=lambda s: _overlap(g0, g1, s[0], s[1]),
               default=None)
    if best is None or _overlap(g0, g1, best[0], best[1]) <= 0:
        return "no bench span"
    return best[2]


def reduce(trace_dir: Path, hlo_text: str, *, n: int, nnz: int,
           m: int) -> Reduction:
    """Reduce the trace under ``trace_dir`` of the executable whose HLO
    text is ``hlo_text``."""
    from jax.profiler import ProfileData

    module = hlo.parse(hlo_text)
    classes = hlo.classify(module, n=n, nnz=nnz, m=m)
    profile = ProfileData.from_file(str(xplane_file(trace_dir)))
    return reduce_profile(profile, module, classes)
