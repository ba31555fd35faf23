"""Run-mode rules shared by every entry point: where Pallas kernels run and
where compiled programs are cached.

* ``resolve_interpret`` — Pallas kernels run in interpret mode on the CPU
  (tests, CI) and compiled on a TPU. Any other backend is an error: nothing
  falls back to the interpreter on an accelerator by omission.
* ``configure_compile_cache`` — JAX's persistent compilation cache lives
  where ``JAX_COMPILATION_CACHE_DIR`` says; without it, at one fixed path
  inside the checkout (the path is part of the cache key, so a directory
  that moves between runs never hits).
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — src/repro/runtime/platform.py is three levels down
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` as given, or (``None``) decided by the JAX backend:
    ``True`` on ``cpu``, ``False`` on ``tpu``; any other backend raises."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"JAX backend {backend!r} is neither")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (which reads it
    itself); otherwise the cache goes to ``DEFAULT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
