"""Record the trace fixture that the reduction's test reads, on the chip.

    python bench/fixtures/record.py atmos7_108.float32 16 [out_dir]

Runs the cell's harness with ``--trace 1`` on a 16^3 grid (one solve), and
keeps, under ``out_dir`` (default ``bench/fixtures/<cell>/``), the
``.xplane.pb`` (xz), the HLO text of the executable that ran and the
readers' inputs (gzip), and ``result.json``: the result the run printed.
"""
from __future__ import annotations

import gzip
import json
import lzma
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402


def main(argv) -> int:
    name, side = argv[0], int(argv[1])
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 2
    run.use_checkout_cache()
    cell = run.cells.load_cell(name)
    out = run.run_cell(cell, 20260, 0.0, True, t0=time.perf_counter(),
                       peak=run.roofline.peak(dev.device_kind),
                       devices=jax.devices()[:cell.chips],
                       grid=(side,) * 3)
    src = run.OUT / "trace" / name
    dst = (Path(argv[2]) if len(argv) > 2
           else Path(__file__).resolve().parent / name)
    dst.mkdir(parents=True, exist_ok=True)
    pb = run.devtrace.xplane_file(src)
    (dst / "trace.xplane.pb.xz").write_bytes(
        lzma.compress(pb.read_bytes(), preset=9))
    for f in ("hlo.txt", "inputs.json"):
        (dst / f"{f}.gz").write_bytes(gzip.compress((src / f).read_bytes()))
    (dst / "result.json").write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
