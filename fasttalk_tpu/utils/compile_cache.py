"""Persistent XLA compilation cache.

The engine compiles ~6-20 executables at startup (warmup level fast/
full, engine/engine.py); on a cold process that is 30-60s of XLA work
that is byte-identical across restarts of the same (model, shapes,
flags) config. JAX can persist compiled executables to disk and reload
them in milliseconds — the reference's analogue was hiding its engine
container's multi-minute cold start behind a 300s health start_period
(reference: docker-compose.vllm.yml:62-67); here restart cost is paid
once per configuration, not per process.

Where the cache lives is decided in exactly one way: by
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, and this module then sets no directory), and
otherwise at one fixed path, ``<checkout>/.xla_cache``. The path is
part of the cache key, so it never contains a temp dir, a uid, a pid or
a time: a directory that moves never hits. ``TPU_COMPILE_CACHE=off``
disables persistence (in-process multi-engine fleets on the CPU need
that).
"""

from __future__ import annotations

import os

from fasttalk_tpu.utils.logger import get_logger

log = get_logger("compile_cache")

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")
OFF_VALUES = ("off", "0", "none", "false")

_enabled_dir: str | None = None


def cache_dir() -> str:
    """The directory the cache uses when enabled."""
    return os.environ.get(ENV_DIR) or REPO_CACHE_DIR


def enable_compilation_cache(setting: str = "") -> str | None:
    """Turn on JAX's persistent compilation cache. Idempotent; returns
    the cache dir in use (None when ``setting`` disables it). Must run
    before the first jit compilation to benefit that compilation. A
    directory that cannot be created raises: a start that silently
    loses its cache recompiles everything on every restart."""
    global _enabled_dir
    if setting.strip().lower() in OFF_VALUES:
        return None
    if _enabled_dir is not None:
        return _enabled_dir
    import jax

    if not os.environ.get(ENV_DIR):
        os.makedirs(REPO_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    # Persist everything: the engine's helper programs (slot-state
    # patch, sample-place) compile in well under the 1s default
    # threshold but still cost seconds as a first-request compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled_dir = cache_dir()
    log.info(f"persistent XLA compilation cache at {_enabled_dir}")
    return _enabled_dir
