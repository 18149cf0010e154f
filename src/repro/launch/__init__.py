"""repro.launch — mesh, dry-run, training and serving launchers.

NOTE: do not import ``dryrun`` from library code — it sets XLA_FLAGS for
512 placeholder devices at import time (by design, per assignment)."""
import os
import pathlib

def _checkout() -> pathlib.Path | None:
    """Root of the checkout this package runs from (``<root>/src/repro``),
    or None when the package is installed outside a checkout."""
    pkg = pathlib.Path(__file__).resolve().parents[1]
    root = pkg.parents[1]
    if root / "src" / "repro" == pkg and (root / "pyproject.toml").is_file():
        return root
    return None


CHECKOUT = _checkout()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to JAX untouched.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (gitignored), so every run from the same
    checkout finds what the previous ones compiled; an installed package
    has no checkout, and then the variable is required.  Program entry
    points call this once; library code never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if CHECKOUT is None:
        raise RuntimeError(
            "repro is not running from a source checkout, so it has no "
            "<checkout>/.jax_cache; set JAX_COMPILATION_CACHE_DIR")
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_devices(n: int) -> None:
    """Force ``n`` host CPU devices by appending
    ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``.

    Must run before jax initializes — this module is jax-free precisely
    so CLIs can call it before their first jax import.  A no-op when
    ``n`` is falsy or the flag is already present."""
    if not n:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
