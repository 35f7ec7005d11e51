"""XLA / JAX environment setup for entry points.

Importing this module is side-effect free and jax-free, so test
conftests and entry scripts can import it first thing:
``ensure_cpu_mesh_flags`` must run BEFORE the first jax import in the
process (env-var flags are read at backend init);
``ensure_compile_cache`` imports jax itself and must run before the
first compile; ``pallas`` imports jax and Pallas when a kernel is first
traced.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"


def ensure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory
    in use.  Every entry point that compiles calls this once.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, nothing is touched — JAX
    reads it itself and no other directory is named in code, so whoever
    runs the program decides where compiled programs survive.  Where it
    is not, the cache goes to ``<checkout>/.jax_cache`` (gitignored): a
    FIXED path, because the directory is part of what a later process
    must agree on to hit — never a tempdir, a pid or a timestamp.
    """
    env = os.environ.get(ENV_COMPILE_CACHE)
    if env:
        return env
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pallas():
    """Pallas and its TPU dialect, imported by the process whose first
    kernel (``transfer/tile_rmw.py``'s push, ``parallel/attention_kernel.py``'s
    forward walk) comes here and by no other.  The import is ~1 s of
    compiling Python sources (Mosaic's dialects, the GPU back end beside
    them) where the installation keeps no byte code
    (``PYTHONDONTWRITEBYTECODE``), ~2 s on the benchmark's host and most of
    what a kernel costs a run's set-up: where a persistent compile cache is
    configured the byte code is kept in it too, beside the compiled
    programs, and read back by the next process as they are.  That first
    import is the set-up span ``kernel_import``."""
    import sys

    if "jax.experimental.pallas.tpu" not in sys.modules:
        import jax

        from swiftmpi_tpu import obs

        cache = jax.config.jax_compilation_cache_dir
        held = sys.dont_write_bytecode, sys.pycache_prefix
        if cache and sys.pycache_prefix is None:
            sys.dont_write_bytecode = False
            sys.pycache_prefix = os.path.join(cache, "pycache")
        try:
            with obs.setup_span("kernel_import"):
                import jax.experimental.pallas.tpu  # noqa: F401
        finally:
            sys.dont_write_bytecode, sys.pycache_prefix = held
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def compile_cache_entries(path: str) -> int:
    """Number of compiled programs stored under ``path`` (0 when the
    directory does not exist yet).  JAX keeps one ``<key>-cache`` file
    per program next to its ``-atime`` bookkeeping file (and
    ``transfer/tile_rmw.py`` Pallas' byte code under ``pycache/``)."""
    try:
        return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
    except FileNotFoundError:
        return 0


def ensure_cpu_mesh_flags(n_devices: int | None = None,
                          force_device_count: bool = False) -> None:
    """Idempotently append the virtual-CPU-mesh XLA flags.

    * ``--xla_force_host_platform_device_count=N`` (when ``n_devices``
      is given) — the standard JAX fake-multi-device trick.
      ``force_device_count=True`` appends even when the flag is already
      present (XLA parses last-occurrence-wins, so the append overrides
      the earlier value) — the test suite uses this so a developer's
      leftover device-count export can never silently shrink the mesh
      and skip every ``devices8`` test.
    * Collective rendezvous timeouts: on an oversubscribed host the
      virtual devices' collective threads can miss XLA:CPU's in-process
      rendezvous window, and the default 40s terminate timeout
      CHECK-aborts the whole process ("Fatal Python error: Aborted" at
      a harmless-looking dispatch — see utils/pipeline.py for the
      full failure mode).  Warn at 60s, abort only at 600s.

    Every append is guarded by a substring check so a caller's own
    XLA_FLAGS value wins (XLA parses flags last-occurrence-wins; an
    unconditional append would silently override it).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if n_devices is not None and (
            force_device_count
            or "--xla_force_host_platform_device_count" not in flags):
        flags += f" --xla_force_host_platform_device_count={n_devices}"
    # each timeout flag guarded on ITS OWN substring: a caller who set
    # only one of the pair keeps their value
    if "--xla_cpu_collective_call_warn_stuck_timeout_seconds" not in flags:
        flags += " --xla_cpu_collective_call_warn_stuck_timeout_seconds=60"
    if "--xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
        flags += " --xla_cpu_collective_call_terminate_timeout_seconds=600"
    os.environ["XLA_FLAGS"] = flags
