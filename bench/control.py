"""Readings that set the limit of ``correct``: the program's own, over many
seeds, and the control's, in one process on the chip.

    python bench/control.py --workload atmos7_108.float32 \
        --seeds 11,12,13 --control 1

The control is the answer delivered one precision below the configuration's
float32: the solve's ``x`` rounded to bfloat16, in the program's place.  Each
seed is one run of the harness (``run.run_cell``) with a one-solve window;
the lines printed are the numbers compared, per seed.  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import run


def rounded(x):
    """``x`` rounded to bfloat16's 8-bit significand, in its own dtype.

    ``reduce_precision`` and not a cast there and back: XLA may drop a
    float32 -> bfloat16 -> float32 pair of converts as excess precision,
    and on the chip it does.
    """
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def with_answer(transform):
    """The program, with every solve's ``x`` replaced by
    ``transform(x, args)`` inside the jitted solve (``args`` are the
    solve's ``(b, x0)``)."""
    import jax

    real = run._program()
    jitted = {}

    def solve_program(A, b, **kw):
        solve, args, plan = real.solve_program(A, b, **kw)
        if id(solve) not in jitted:
            def changed(*a):
                state = dict(solve(*a))
                state["x"] = transform(state["x"], a)
                return state
            jitted[id(solve)] = (jax.jit(changed), solve)
        return jitted[id(solve)][0], args, plan

    return types.SimpleNamespace(**{**vars(real),
                                    "solve_program": solve_program})


def program():
    """The program, with every solve's answer rounded to bfloat16."""
    return with_answer(lambda x, _args: rounded(x))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    run.use_checkout_cache()
    if args.control:
        prog = program()
        run._program = lambda: prog
    cell = run.cells.load_cell(args.workload)
    peak = run.roofline.peak(dev.device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, seed, 0.0, False, t0=time.perf_counter(),
                           peak=peak, devices=jax.devices()[:cell.chips])
        print(json.dumps(dict(workload=cell.name, seed=seed,
                              control=bool(args.control),
                              correct=out["correct"],
                              solve_s=out["metrics"]["solve_s"]["value"],
                              checks=out["checks"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
