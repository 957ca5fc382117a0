"""Process-wide settings that follow the backend: arithmetic dtype and the
persistent compile cache.

Arithmetic: a TPU has no float64 units.  XLA:TPU emulates f64 arithmetic
(at a large cost in temporaries) and does not implement the f64 <-> u64
``bitcast-convert`` the FRSZ2 codec is built on, so the solver runs in
float32 arithmetic over a float32 operator there, with x64 off.  Every other
backend keeps float64, the paper-faithful setting.

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (a fixed path, because the path is part of the
cache key).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
import jax.numpy as jnp

__all__ = ["arith_dtype", "configure_arithmetic", "enable_compile_cache",
           "require_codec_dtype", "CACHE_DIR"]

#: compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def arith_dtype():
    """The solver's arithmetic dtype: float32 on TPU, float64 elsewhere."""
    return jnp.float32 if jax.default_backend() == "tpu" else jnp.float64


def configure_arithmetic():
    """Set x64 to match :func:`arith_dtype` and return that dtype.

    Matrix-product precision is not a process setting: the solver passes
    ``Precision.HIGHEST`` to its own products (``repro.core.accessor``).
    """
    dtype = arith_dtype()
    jax.config.update("jax_enable_x64", dtype == jnp.float64)
    return dtype


def require_codec_dtype(dtype) -> None:
    """Refuse a 64-bit FRSZ2 value dtype on a TPU backend.

    Raised rather than downcast: a float64 format that silently ran in
    float32 would report accuracy it does not have.
    """
    if jnp.dtype(dtype).itemsize == 8 and jax.default_backend() == "tpu":
        raise ValueError(
            f"FRSZ2 over {jnp.dtype(dtype).name} cannot run on TPU: the codec "
            "bitcasts values to same-width unsigned integers, and XLA:TPU "
            "reports bitcast-convert f64->u64 as UNIMPLEMENTED.  Use float32 "
            "arithmetic there (repro.runtime.arith_dtype()).")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compile cache (see the module docstring)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
