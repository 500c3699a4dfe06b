"""configs.platform: platform selection, XLA flag staging and the kernel
lowering map the shard_map dispatch consults (DESIGN.md Section 10).

Everything here runs on one CPU device; the one process-global mutation
exercised is ``set_platform(None)`` / ``set_platform("cpu")`` (idempotent
on the CI backend).  GPU flag staging is tested through the pure
``_append_xla_flags`` helper against a monkeypatched environment so the
real backend never re-initializes mid-suite.
"""
import os
import warnings

import jax
import pytest

from repro.configs import platform as plat


def test_resolve_platform_precedence(monkeypatch):
    monkeypatch.delenv("GRIFFIN_PLATFORM", raising=False)
    assert plat.resolve_platform() == jax.default_backend()
    monkeypatch.setenv("GRIFFIN_PLATFORM", "TPU")
    assert plat.resolve_platform() == "tpu"          # env, case-folded
    assert plat.resolve_platform("cpu") == "cpu"     # arg beats env
    with pytest.raises(ValueError):
        plat.resolve_platform("rocm")
    monkeypatch.setenv("GRIFFIN_PLATFORM", "xpu")
    with pytest.raises(ValueError):
        plat.resolve_platform()


def test_kernel_lowering_map(monkeypatch):
    monkeypatch.delenv("GRIFFIN_PLATFORM", raising=False)
    assert plat.kernel_lowering("tpu") == "mosaic"
    assert plat.kernel_lowering("gpu") == "triton"
    assert plat.kernel_lowering("cpu") == "interpret"
    # only the interpret lowering forces interpret-mode pallas_call
    assert plat.kernel_interpret("cpu")
    assert not plat.kernel_interpret("tpu")
    assert not plat.kernel_interpret("gpu")
    # the CI backend is CPU: the no-arg form griffin_linear uses must say
    # interpret so shard_map'd kernels run on the emulated mesh
    if jax.default_backend() == "cpu":
        assert plat.kernel_interpret()


def test_append_xla_flags_deduplicates(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_gpu_triton_gemm_any=False")
    plat._append_xla_flags(plat.GPU_XLA_FLAGS)
    flags = os.environ["XLA_FLAGS"]
    # an already-present flag key is never overridden or duplicated
    assert flags.count("--xla_gpu_triton_gemm_any") == 1
    assert "--xla_gpu_triton_gemm_any=False" in flags
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in flags


def test_set_platform_default_is_idempotent(monkeypatch):
    monkeypatch.delenv("GRIFFIN_PLATFORM", raising=False)
    before = jax.default_backend()
    assert plat.set_platform() == before
    assert plat.set_platform(before) == before
    assert jax.default_backend() == before


def test_set_host_device_count(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    n = len(jax.devices())
    with warnings.catch_warnings():
        warnings.simplefilter("error")               # matching count: quiet
        plat.set_host_device_count(n)
    assert f"--xla_force_host_platform_device_count={n}" \
        in os.environ["XLA_FLAGS"]
    if n != 64:
        # backend is already up with a different count: warn, never no-op
        # silently — the flag still lands for child processes
        with pytest.warns(UserWarning, match="next process"):
            plat.set_host_device_count(64)


def test_interpret_follows_the_backend_not_the_override(monkeypatch):
    """GRIFFIN_PLATFORM picks what ``set_platform`` pins; it never decides
    how kernels lower on a backend that is already running.  On a TPU
    backend no override turns interpret mode on, and an explicit request
    for it is refused."""
    monkeypatch.setenv("GRIFFIN_PLATFORM", "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert plat.kernel_lowering() == "mosaic"
    assert not plat.kernel_interpret()
    assert plat.checked_interpret(False) is False
    with pytest.raises(RuntimeError, match="TPU backend"):
        plat.checked_interpret(True)
    # and the other way round: a CPU backend interprets whatever the
    # override says
    monkeypatch.setenv("GRIFFIN_PLATFORM", "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert plat.kernel_interpret()
    assert plat.checked_interpret(True) is True


def test_compile_cache_env_directory_is_left_alone(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the helper
    reports it and changes nothing."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert plat.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_checkout_path(monkeypatch):
    """Unset, the cache goes to the same in-checkout path on every call
    (the path is part of the cache key), and that path is git-ignored."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = plat.enable_compile_cache()
        assert plat.enable_compile_cache() == first
        assert first == str(plat.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (plat.REPO_ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
