"""Compile a cell's serving programs at full size for a described
``v5e:2x2``, with no chip attached: the fused decode chunk (8 steps) and
the prefill of the longest bucket the traffic uses, on the cell's mesh or
on one chip, with the Pallas kernels lowered for Mosaic.  A pruned
configuration compiles with the compacted weights' shapes, from the block
pattern its ``mask_seed`` gives every seed.  It prints each program's bytes
on the fullest chip; nothing runs.

Run it through ``python -m bench.rehearse --compile`` with
``JAX_PLATFORMS=cpu``.
"""
from __future__ import annotations

import contextlib

import numpy as np

from . import traffic, weights


@contextlib.contextmanager
def mosaic_kernels():
    """The program picks interpret-mode kernels when the running backend
    is the CPU; a compile for a described TPU needs the Mosaic ones."""
    from repro.models import common as model_common
    orig = model_common.kernel_interpret
    model_common.kernel_interpret = lambda platform=None: False
    try:
        yield
    finally:
        model_common.kernel_interpret = orig


def _live(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def compacted_shapes(shapes, conf: dict):
    """The parameter tree after the program's ``sparsify_params`` compacts
    it, as shapes: each pruned leaf a ``GriffinWeights`` whose grid depth
    is the most blocks any column tile of any layer keeps."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.griffin_spmm.ops import GriffinWeights
    pr = conf["pruning"]
    found = weights.masks(shapes, conf)

    def one(path, sd):
        name = weights.leaf_name(path)
        if name not in found:
            return sd
        *lead, k, n = sd.shape
        p = weights.block_plan(k, n, pr)
        depth = max(1, int(np.asarray(found[name]).sum(-2).max()))
        pn, un = p["nbn"] * p["bn"], min(pr["unit"], n)
        i32 = lambda *s: jax.ShapeDtypeStruct((*lead, *s), jnp.int32)
        balanced = pn > p["bn"] and pn % un == 0
        return GriffinWeights(
            b_comp=jax.ShapeDtypeStruct((*lead, depth * p["bk"], pn),
                                        sd.dtype),
            kidx=i32(p["nbn"], depth), cnt=i32(p["nbn"]),
            inv_perm=i32(pn) if balanced else None,
            k=p["nbk"] * p["bk"], n=n, block_k=p["bk"], block_n=p["bn"])

    return jax.tree_util.tree_map_with_path(one, shapes)


def compile_cell(conf: dict, mix: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding
    from repro.models import build_model
    from repro.models.common import sparse_execution
    from repro.runtime.engine import _promote_arena
    from repro.runtime.mesh_serve import serve_shardings
    from repro.runtime.serve import make_decode_chunk_fn
    from .loop import program_config

    d = conf["deployment"]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    api = build_model(program_config(conf))
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    arena = jax.eval_shape(lambda: _promote_arena(
        api.init_cache(d["slots"], d["cache_len"]), d["slots"]))
    if d["mesh"]:
        if conf["pruning"]["weight_sparsity"] > 0:
            return {"skipped": "pruned weights on a mesh"}
        data, model = (int(x) for x in d["mesh"].split("x"))
        mesh = Mesh(np.array(topo.devices[:data * model]).reshape(
            data, model), ("data", "model"))
        p_sh, c_sh, rep = serve_shardings(api, mesh, shapes, d["slots"],
                                          d["cache_len"])
        place = lambda tree, sh: jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)
        params, cache = place(shapes, p_sh), place(arena, c_sh)
        chunk_sh = dict(in_shardings=(p_sh, c_sh, rep, rep),
                        out_shardings=(c_sh, rep, rep, rep, rep, rep))
        prefill_sh = dict(in_shardings=(p_sh, rep), out_shardings=(rep, rep))
    else:
        mesh, rep = None, SingleDeviceSharding(topo.devices[0])
        if conf["pruning"]["weight_sparsity"] > 0:
            shapes = compacted_shapes(shapes, conf)
        place = lambda tree: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            tree)
        params, cache = place(shapes), place(arena)
        chunk_sh = prefill_sh = {}
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
    out = {}
    with mosaic_kernels(), sparse_execution(use_kernels=d["use_kernels"],
                                            spmd_mesh=mesh):
        chunk = jax.jit(make_decode_chunk_fn(api, d["decode_chunk"]),
                        donate_argnums=(1, 2, 3), **chunk_sh).lower(
            params, cache, i32((d["slots"], 1)), i32((d["slots"],))).compile()
        text = chunk.as_text()
        out["decode_chunk_bytes"] = _live(chunk)
        out["decode_chunk_kernels"] = text.count("tpu_custom_call")
        out["decode_chunk_collectives"] = sum(
            text.count(c) for c in ("all-gather", "all-reduce",
                                    "collective-permute"))
        bucket = traffic.prompt_buckets(mix["prompt_tokens"]["min"],
                                        mix["prompt_tokens"]["max"])[-1]
        batch = {"tokens": i32((1, bucket)), "lengths": i32((1,))}
        prefill = jax.jit(
            lambda p, b: api.prefill(p, b, cache_len=d["cache_len"]),
            **prefill_sh).lower(params, batch).compile()
        out["prefill_bytes"] = _live(prefill)
        out["prefill_bucket"] = bucket
    leaves = jax.tree.leaves(params)
    out["params_bytes_per_chip"] = int(sum(
        np.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for x in leaves))
    out["arena_bytes_per_chip"] = int(sum(
        np.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(cache)))
    return out
