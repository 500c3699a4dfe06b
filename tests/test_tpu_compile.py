"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler ships with libtpu, so a kernel or a whole decode step can
be compiled here for a ``v5e:2x2`` topology without a chip: what Mosaic or
XLA would refuse on the chip (a tile that is not lane-aligned, an i1
relayout, more memory than the device has) fails here.  Nothing runs, so
this says nothing about results or times.

The topology is described inside a module fixture (never at import, in a
``skipif`` or in a ``parametrize`` argument): only one process may load the
TPU library at a time, and the worker that runs this file keeps it.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

BF16 = jnp.bfloat16
# stablelm-1.6b GEMMs: the (d_model -> d_ff) and (d_ff -> d_model) sides
SHAPES = ((2048, 5632), (5632, 2048))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # described-device executables cannot be read back from the
        # persistent cache without a chip: keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def stablelm(one_chip):
    """stablelm-1.6b at published widths, as placed shapes: the model api,
    its params and the engine's 8-slot x 2048 KV arena."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.engine import _promote_arena

    api = build_model(get_config("stablelm-1.6b"))

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(
        lambda: _promote_arena(api.init_cache(8, 2048), 8)))
    return api, params, cache


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _live_bytes(compiled) -> int:
    """Device bytes the compiled program holds at once (donated inputs
    counted once)."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def _kernel_call(kernel, m, k, n, block_m, sharding):
    """(fn, operand shapes) for one kernel at (m, k) @ (k, n)."""
    from repro.kernels.dense_gemm.kernel import dense_matmul_kernel
    from repro.kernels.griffin_spmm.kernel import griffin_spmm_kernel
    from repro.kernels.sparse_a.kernel import sparse_a_gemm_kernel

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    blk = dict(block_m=block_m, block_k=128, block_n=128)
    a = sds((m, k))
    if kernel == "dense_gemm":
        return (functools.partial(dense_matmul_kernel, **blk),
                (a, sds((k, n))))
    if kernel == "sparse_a":
        kt = k // 128
        return (functools.partial(sparse_a_gemm_kernel, **blk),
                (a, sds((k, n)), sds((m // block_m, kt), jnp.int32),
                 sds((m // block_m,), jnp.int32)))
    depth = max(1, (k // 128) // 2)          # half the K blocks survive
    nt = n // 128
    return (functools.partial(griffin_spmm_kernel,
                              dual=kernel == "griffin_spmm_dual", **blk),
            (a, sds((depth * 128, n)), sds((nt, depth), jnp.int32),
             sds((nt,), jnp.int32)))


@pytest.mark.parametrize("block_m", [8, 128])
@pytest.mark.parametrize("kernel", ["dense_gemm", "sparse_a", "griffin_spmm",
                                    "griffin_spmm_dual"])
def test_kernel_compiles_for_v5e(one_chip, kernel, block_m):
    """Every kernel, bf16, at the model's GEMM shapes: decode-sized row
    tiles (8) and prefill-sized ones (128, M = 512).  The dual kernel's
    zero test is the one that needed an f32 widening to compile."""
    m = 8 if block_m == 8 else 512
    for k, n in SHAPES:
        fn, shapes = _kernel_call(kernel, m, k, n, block_m, one_chip)
        _compile(fn, *shapes)


@pytest.mark.parametrize("kernel,name", [
    ("dense_gemm", "dense_gemm"), ("sparse_a", "sparse_a"),
    ("griffin_spmm", "griffin_spmm"), ("griffin_spmm_dual", "griffin_spmm")])
def test_kernel_carries_its_name(one_chip, kernel, name):
    """Each kernel's custom call is the HLO instruction ``%<name>.N``: the
    name its op events carry in a chip profile."""
    fn, shapes = _kernel_call(kernel, 8, *SHAPES[0], 8, one_chip)
    calls = [ln for ln in _compile(fn, *shapes).as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(f"%{name}." in ln for ln in calls), calls


def test_full_width_decode_step_compiles_with_kernels(one_chip, stablelm):
    """stablelm-1.6b's decode step at published widths (24 layers, bf16,
    8 slots x 2048 cache) with every GEMM on the Pallas kernels: the
    compiled program carries the kernels and fits one 16 GB chip."""
    from repro.models.common import sparse_execution

    api, params, cache = stablelm
    token = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    with sparse_execution(use_kernels=True):
        compiled = jax.jit(api.decode_step, donate_argnums=(1,)).lower(
            params, cache, token).compile()
    # 7 GEMMs per layer in the scanned body + the unembedding
    assert compiled.as_text().count("tpu_custom_call") >= 8
    live = _live_bytes(compiled)
    assert live < 15.75e9, live


def test_full_width_decode_chunk_fits_one_chip(one_chip, stablelm):
    """The engine's fused 8-step decode chunk for stablelm-1.6b at 8 slots
    x 2048 cache, plain XLA: with the KV cache re-emitted per layer it
    needed about 6x the arena in temporaries and the chip's compiler
    refused it (18.4 GB of 15.75 GB); carried in place it fits."""
    from repro.runtime.serve import make_decode_chunk_fn

    api, params, cache = stablelm
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    remaining = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_decode_chunk_fn(api, 8),
                       donate_argnums=(1, 2, 3)).lower(
        params, cache, tokens, remaining).compile()
    live = _live_bytes(compiled)
    assert live < 15.75e9, live
