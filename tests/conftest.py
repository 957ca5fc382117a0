import re

import jax
import numpy as np
import pytest

# f64 needed by the paper-faithful solver tests; harmless elsewhere.
# NOTE: no XLA_FLAGS device-count override here — tests run on the real
# single CPU device; only launch/dryrun.py creates the 512 fake devices.
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "transfer_guard: device-driver sweep under "
        "jax.transfer_guard('disallow') — CI runs these as their own step",
    )


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.default_rng(0)


_COPY = re.compile(r"%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* copy(?:-start)?\(")


def _hlo_type(dtype) -> str:
    """HLO's element type name of ``dtype``: ``f32``, ``u16``, ``s32``..."""
    dtype = np.dtype(dtype)
    if dtype.name == "bfloat16":
        return "bf16"
    return {"f": "f", "u": "u", "i": "s"}[dtype.kind] + str(8 * dtype.itemsize)


@pytest.fixture(scope="session")
def whole_store_copies():
    """``count(hlo_text, store)``: the names of the ``copy`` ops of a
    compiled program that copy as many elements of one type as a leaf of
    the Krylov ``store`` (avals or arrays) holds, in any shape or layout."""

    def count(hlo: str, store) -> list[str]:
        sizes = {(_hlo_type(leaf.dtype), int(np.prod(leaf.shape)))
                 for leaf in jax.tree.leaves(store)}
        found = []
        for name, kind, dims in _COPY.findall(hlo):
            size = int(np.prod([int(d) for d in dims.split(",") if d]))
            if (kind, size) in sizes:
                found.append(name)
        return found

    return count
