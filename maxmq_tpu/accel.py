"""Accelerator policy shared by every entry that can build a device
engine (``maxmq start``, ``maxmq matcher-service``, ``bootstrap.run_server``,
``chip_smoke.py``): where compiled programs are cached, and the refusal
to serve from a CPU that JAX fell back to in silence.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# ``matcher`` values that build an engine on the device in this process
DEVICE_MATCHERS = ("sig",)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere that survives
    the process, and return the directory in use. The directory is part
    of the cache key's usefulness: a path that moves never hits, so it
    is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads the
    variable itself; no directory is set in code then) or the fixed
    ``<checkout>/.jax_cache``."""
    import jax

    # what makes an entry findable again, wherever the cache lives. A
    # Pallas kernel is serialised into its program with the location of
    # every Python frame that led to it, so with full tracebacks an edit
    # that moves a line in ANY caller (this package, a script) changes
    # the key of every kernel program; the innermost frame is enough.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    # the served bucket programs of a small table compile in under the
    # default one-second floor and would never be written
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_accelerator(what: str) -> None:
    """Refuse to build a device engine on a CPU nobody asked for.

    JAX falls back to the CPU with a warning when it finds no chip; the
    engines would then serve from the Pallas interpreter and say
    nothing. The CPU stays available on request — ``jax_platforms``
    naming ``cpu`` first (the ``JAX_PLATFORMS`` variable, or the pin in
    tests/conftest.py) is how the tests and harness children run."""
    import jax

    if jax.default_backend() != "cpu":
        return
    # a priority list: "tpu,cpu" asks for the chip and merely allows the
    # fallback this function exists to refuse
    if (jax.config.jax_platforms or "").split(",")[0].strip() == "cpu":
        return
    raise RuntimeError(
        f"{what}: JAX found no accelerator and fell back to the CPU; "
        "refusing to serve from the interpreter in silence. Fix the "
        "device, or set JAX_PLATFORMS=cpu to run on the CPU on purpose "
        "(matcher = \"trie\" needs no device at all)")
