"""Device compute ops. Importing anything here (or calling ensure_x64)
switches JAX to 64-bit mode.

Exact 64-bit keys are the product of an indexing framework (orderkeys, file
ids, row counts) — silent int64→int32 downcasting is data corruption. TPU
executes 64-bit integer ops via 32-bit emulation; value columns are cast
down explicitly where speed matters. x64 is enabled here, at the engine
boundary, not at package import, so metadata-only use of hyperspace_tpu
never touches jax or mutates process-global config.
"""


def ensure_x64() -> None:
    import jax

    jax.config.update("jax_enable_x64", True)
    _enable_persistent_compile_cache(jax)
    from ..telemetry.compiles import install

    install()


def configured_platform() -> str:
    """The jax platform that WOULD initialize, resolved without backend
    init, so pure-host code paths never pay backend start-up just to ask
    where they are. Env var first, then jax.config; only a fully
    unconfigured process falls back to jax.default_backend()."""
    import os

    platform = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if platform:
        return platform
    import jax

    cfg = jax.config.jax_platforms
    return cfg.split(",")[0].strip() if cfg else jax.default_backend()


def is_tpu_platform() -> bool:
    """Whether the configured platform is a TPU."""
    return configured_platform() == "tpu"


def fence_materialize(*arrays) -> None:
    """Wait for device results by materializing one element of each
    array: a D2H read observes execution on every backend. A 1-element
    read keeps the fence O(1). Multiple outputs of ONE dispatch need only
    their first array fenced."""
    import numpy as np

    for a in arrays:
        np.asarray(a[tuple(slice(0, 1) for _ in range(a.ndim))])


def fence_chain(arrays) -> None:
    """One materializing fence over MANY independent device arrays (e.g.
    a batch of uploads): chains a 1-element probe through every array so
    completion of all is observed with a single link round trip, where
    per-array ``fence_materialize`` would pay one round trip each. Also
    the device-loss detector for prefetch: a dead device raises here."""
    import jax.numpy as jnp
    import numpy as np

    acc = None
    for a in arrays:
        # slice BEFORE ravel: an eager ravel materializes a full-size
        # copy of the array, which would transiently double the largest
        # resident columns in HBM at the worst moment (prefetch)
        v = a[tuple(slice(0, 1) for _ in range(a.ndim))]
        v = v.ravel().astype(jnp.float32)
        acc = v if acc is None else acc + v
    if acc is not None:
        np.asarray(acc)


def _enable_persistent_compile_cache(jax) -> None:
    """TPU compiles of the build/query kernels cost tens of seconds; the
    persistent cache lets every process after the first reuse the
    serialized executable. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing is set here; otherwise the cache
    lives at the fixed ``<checkout>/.jax_cache`` (the path is part of the
    cache key, so it must not move). Opt out with
    HYPERSPACE_TPU_COMPILE_CACHE=off."""
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if os.environ.get("HYPERSPACE_TPU_COMPILE_CACHE", "on").lower() == "off":
        return
    from pathlib import Path

    cache_dir = Path(__file__).resolve().parent.parent.parent / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


ensure_x64()
