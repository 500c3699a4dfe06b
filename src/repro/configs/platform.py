"""Platform selection + XLA flags for the serving stack.

One place answers "what hardware are we on and how should the Pallas
kernels lower there?" — the style of the bayespec ``set_platform`` helper
and the olmax XLA-flag launch scripts (SNIPPETS.md): tiny functions that
mutate ``jax.config`` / ``XLA_FLAGS`` *before* the backend initializes,
plus pure queries the dispatch layer consults at trace time.

Lowering map (``kernel_lowering``):

  tpu -> "mosaic"     the native Pallas TPU path the kernels target
  gpu -> "triton"     staged: Pallas lowers TPU-style kernels to Triton via
                      ``pallas_call``'s GPU backend; the scalar-prefetch
                      grid specs in kernels/ are the TPU dialect, so the
                      GPU port lands behind this switch (gpu_xla_flags()
                      already carries the Triton-GEMM flags it will want)
  cpu -> "interpret"  ``pallas_call(interpret=True)`` — the CI / emulated
                      mesh path; ``kernel_interpret()`` is how
                      ``models.common.griffin_linear`` decides to force
                      interpret mode for the shard_map'd kernel calls
                      (DESIGN.md Section 10)

Interpret mode follows the backend the arrays live on and nothing else:
``kernel_interpret()`` asks jax, and ``checked_interpret`` refuses an
interpret-mode kernel on a TPU backend, so no setting can quietly run the
kernels in the interpreter on the chip.

Environment overrides: ``GRIFFIN_PLATFORM`` picks the platform
``set_platform`` pins (before the backend starts); it never changes the
lowering of an already-running backend.  ``set_host_device_count`` is the
in-process twin of the CI ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
export.  ``enable_compile_cache`` turns on JAX's persistent compilation
cache at one fixed place.
"""
from __future__ import annotations

import os
import warnings
from pathlib import Path
from typing import Optional

# the checkout root (src/repro/configs/platform.py -> three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]
# where compiled programs are cached when JAX_COMPILATION_CACHE_DIR is not
# set: a fixed path, since the path is part of the cache key (listed in
# .gitignore)
DEFAULT_COMPILE_CACHE = REPO_ROOT / ".jax_cache"

# staged GPU performance flags (jax.readthedocs.io gpu_performance_tips,
# via the bayespec snippet): applied by set_platform("gpu") so the future
# Triton lowering starts from a tuned baseline
GPU_XLA_FLAGS = (
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

_LOWERING = {"tpu": "mosaic", "gpu": "triton", "cpu": "interpret"}


def _append_xla_flags(flags) -> None:
    cur = os.environ.get("XLA_FLAGS", "")
    new = [f for f in flags if f.split("=")[0] not in cur]
    if new:
        os.environ["XLA_FLAGS"] = " ".join([cur, *new]).strip()


def resolve_platform(platform: Optional[str] = None) -> str:
    """'cpu' | 'gpu' | 'tpu': the explicit argument, else the
    ``GRIFFIN_PLATFORM`` env var, else whatever backend jax initialized."""
    platform = platform or os.environ.get("GRIFFIN_PLATFORM")
    if platform:
        platform = platform.lower()
        if platform not in _LOWERING:
            raise ValueError(f"unknown platform {platform!r} "
                             f"(known: {sorted(_LOWERING)})")
        return platform
    import jax
    return jax.default_backend()


def set_platform(platform: Optional[str] = None) -> str:
    """Pin jax to a platform and stage its XLA flags; returns the choice.

    Call before the first jax computation (backend selection is
    process-global, exactly as in the bayespec helper).  ``None`` resolves
    from ``GRIFFIN_PLATFORM`` / the default backend, so launch scripts can
    call this unconditionally.
    """
    import jax
    platform = resolve_platform(platform)
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        _append_xla_flags(GPU_XLA_FLAGS)
    return platform


def set_host_device_count(n: int) -> None:
    """Emulate ``n`` host devices (the olmax
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` export, done
    in-process).  Only effective before the backend initializes — warn,
    don't silently no-op, when it is already up."""
    import jax
    if jax._src.xla_bridge._backends:            # already initialized
        if len(jax.devices()) != n:
            warnings.warn(
                f"backend already initialized with {len(jax.devices())} "
                f"devices; --xla_force_host_platform_device_count={n} "
                "takes effect next process", stacklevel=2)
    _append_xla_flags((f"--xla_force_host_platform_device_count={n}",))


def kernel_lowering(platform: Optional[str] = None) -> str:
    """'mosaic' | 'triton' | 'interpret' — how pallas_call should lower on
    ``platform`` (default: the backend jax is running, never the
    ``GRIFFIN_PLATFORM`` override)."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    return _LOWERING[platform.lower()]


def kernel_interpret(platform: Optional[str] = None) -> bool:
    """True when Pallas kernels must run in interpret mode here (CPU).

    This is the trace-time default ``griffin_linear`` applies to the
    shard_map'd kernel calls under an ``spmd_mesh`` scope: the mesh
    engine's jit sets are traced after placement, where the backend is
    known, so sharded serving never needs the interpret flag threaded
    through by hand (single-device callers keep passing it explicitly).
    """
    return kernel_lowering(platform) == "interpret"


def checked_interpret(interpret: bool) -> bool:
    """``interpret`` for a ``pallas_call``, refused on a TPU backend:
    the interpreter there would hide that the kernel never reached the
    chip.  Every kernel wrapper passes its flag through here."""
    if interpret:
        import jax
        if jax.default_backend() == "tpu":
            raise RuntimeError("interpret-mode Pallas kernel requested on "
                               "a TPU backend")
    return interpret


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory and nothing here overrides it.  Otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE`` inside the checkout — the same path on every
    call and every run, so later runs hit it.  Call before the first
    compile.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
