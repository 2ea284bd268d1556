"""Where JAX keeps its persistent compilation cache for this repo.

The device paths (the jitted stage pass in :mod:`repro.core.jaxsim`
and the Pallas kernel behind :func:`repro.kernels.ops.cim_mvm`) pay
tens of seconds of XLA compile per decode-table bucket at real sizes,
so what a warm run costs is decided by whether those executables are
found again on the next run.  JAX keys cache entries by directory, so the
directory must stay put between runs.
"""

from __future__ import annotations

import os

__all__ = ["ENV_VAR", "DEFAULT_DIR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed path inside the checkout (results/* is git-ignored); never
# derived from a temporary name, a pid or the time, or it never hits
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "results", "jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it by
    itself, so nothing is changed.  A directory already configured in
    this process is left alone too.  Otherwise the cache goes to
    ``<checkout>/results/jax_cache``.  JAX reads the directory at its
    next compile, so call this before the compiles it should cover (the
    device entry points do, ahead of their own).  Returns the directory
    in use.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
