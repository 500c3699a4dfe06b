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


def _attention_call(sharding, arena=(24, 10, 2048, 32, 64), heads=32,
                    dtype=BF16, block_s=256):
    """(fn, operand shapes) for the live-KV decode attention kernel on an
    (L, B, S, KVH, hd) arena; fn takes per-row lengths and slots and
    builds the step's plan."""
    from repro.kernels.decode_attention.ops import live_kv_attention, \
        step_plan

    def fn(q, k, v, k_all, v_all, layer, lengths, slots):
        return live_kv_attention(q, k, v, k_all, v_all, layer,
                                 step_plan(lengths, slots, block_s),
                                 block_s=block_s)

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    L, B, S, KVH, hd = arena
    return fn, (sds((B, 1, heads, hd)), sds((B, 1, KVH, hd)),
                sds((B, 1, KVH, hd)), sds(arena), sds(arena),
                sds((), jnp.int32), sds((B,), jnp.int32),
                sds((B,), jnp.int32))


def _kernel_call(kernel, m, k, n, block_m, sharding):
    """(fn, operand shapes) for one kernel at (m, k) @ (k, n); the decode
    attention kernel at stablelm's arena (24 layers, 10 slots x 2048, 32
    heads of 64) instead, with KV blocks of 128 (``block_m`` 8) or 256."""
    from repro.kernels.dense_gemm.kernel import dense_matmul_kernel
    from repro.kernels.griffin_spmm.kernel import griffin_spmm_kernel
    from repro.kernels.sparse_a.kernel import sparse_a_gemm_kernel

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if kernel == "decode_attention":
        return _attention_call(sharding,
                               block_s=128 if block_m == 8 else 256)
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


def _assert_attention_in_place(fn, shapes):
    """The decode attention kernel compiles and takes its arena in place:
    no copy of the arena or of its position-minor view, and no temporary
    of its size."""
    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    arena = shapes[3]
    L, B, S, KVH, hd = arena.shape
    dt = "bf16" if arena.dtype == BF16 else "f32"
    views = (f"{dt}[{L},{B},{S},{KVH},{hd}]", f"{dt}[{L},{B},{KVH},{hd},{S}]")
    copies = [ln for ln in text.splitlines()
              if any(v in ln for v in views)
              and (" copy(" in ln or " copy-start(" in ln)]
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < \
        arena.size * arena.dtype.itemsize // 100


@pytest.mark.parametrize("arena", ["llama3.2-1b", "stablelm-1.6b-f32"])
def test_decode_attention_takes_other_arenas_in_place(one_chip, arena):
    """The kernel's view is a bitcast on more than the served arena:
    llama3.2-1b's (16 layers, 8 KV heads of 64, under 32 query heads) in
    bf16, and stablelm-1.6b's in f32."""
    if arena == "llama3.2-1b":
        call = _attention_call(one_chip, arena=(16, 10, 2048, 8, 64))
    else:
        call = _attention_call(one_chip, dtype=jnp.float32)
    _assert_attention_in_place(*call)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_arena_layout_follows_position_minor(one_chip, dtype):
    """``position_minor`` decides from the head size alone whether the
    kernel's position-minor view is the arena's own layout (a bitcast) or
    a copy of it.  The compiler's default layout of every transformer
    configuration's (L, B, S, KVH, hd) arena agrees: positions minor where
    the head size is not a multiple of 128, row-major where it is."""
    from repro.configs import all_configs
    from repro.kernels.decode_attention.ops import position_minor

    for cfg in all_configs().values():
        if cfg.family not in ("dense", "vlm", "moe"):
            continue
        arena = jax.ShapeDtypeStruct(
            (cfg.num_layers, 2, 256, cfg.num_kv_heads, cfg.hd), dtype,
            sharding=one_chip)
        compiled = jax.jit(lambda a: a[0, 0, 0] * 2).lower(arena).compile()
        order = compiled.input_formats[0][0].layout.major_to_minor
        assert (order[-1] == 2) == position_minor(cfg.hd), (cfg.name, order)
        assert order in ((0, 1, 3, 4, 2), (0, 1, 2, 3, 4)), (cfg.name, order)


@pytest.mark.parametrize("block_m", [8, 128])
@pytest.mark.parametrize("kernel", ["dense_gemm", "sparse_a", "griffin_spmm",
                                    "griffin_spmm_dual", "decode_attention"])
def test_kernel_compiles_for_v5e(one_chip, kernel, block_m):
    """Every kernel, bf16, at the model's GEMM shapes: decode-sized row
    tiles (8) and prefill-sized ones (128, M = 512).  The dual kernel's
    zero test is the one that needed an f32 widening to compile.  The
    decode attention kernel compiles at the served arena, and takes it
    in place: no copy of it and no temporary of its size."""
    if kernel == "decode_attention":
        _assert_attention_in_place(
            *_kernel_call(kernel, 0, 0, 0, block_m, one_chip))
        return
    m = 8 if block_m == 8 else 512
    for k, n in SHAPES:
        fn, shapes = _kernel_call(kernel, m, k, n, block_m, one_chip)
        _compile(fn, *shapes)


@pytest.mark.parametrize("kernel,name", [
    ("dense_gemm", "dense_gemm"), ("sparse_a", "sparse_a"),
    ("griffin_spmm", "griffin_spmm"), ("griffin_spmm_dual", "griffin_spmm"),
    ("decode_attention", "decode_attention")])
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
    refused it (18.4 GB of 15.75 GB); carried in place it fits.

    With the kernels, at the served 10 slots: the chunk used to copy the
    arena into another layout and back (14.01e9 B planned with compacted
    weights); the live-KV attention kernel takes it in place, so even with
    dense weights the plan is at least 3e9 B below that."""
    from repro.models.common import sparse_execution
    from repro.runtime.engine import _promote_arena
    from repro.runtime.serve import make_decode_chunk_fn

    api, params, cache = stablelm

    def chunk_bytes(cache, slots):
        i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                 sharding=one_chip)
        return _live_bytes(jax.jit(make_decode_chunk_fn(api, 8),
                                   donate_argnums=(1, 2, 3)).lower(
            params, cache, i32((slots, 1)), i32((slots,))).compile())

    live = chunk_bytes(cache, 8)
    assert live < 15.75e9, live
    cache10 = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: _promote_arena(api.init_cache(10, 2048), 10)))
    with sparse_execution(use_kernels=True):
        live = chunk_bytes(cache10, 10)
    assert live < 14.01e9 - 3e9, live
