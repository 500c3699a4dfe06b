"""Minitron-8B's block (reduced) served on one device and on a 1x4 mesh of
four virtual CPU devices, for tests/test_nemotron_block.py.

    python tests/nemotron_mesh.py

Serves one trace through ``ServeEngine`` and ``MeshServeEngine`` (1x4),
each on the fixed and on the paged arena, with the Pallas kernels on
(interpret mode), the mesh runs under the profiler.  Prints one JSON line:
every engine's tokens, the mesh engines' counters and the GEMM paths
they traced (``KERNEL_DISPATCH``), and the names and
arguments of the ``engine.*`` spans the fixed-arena mesh run recorded.
"""
import dataclasses
import glob
import json
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402


def config():
    """minitron-8b reduced, with 16 query and 8 KV heads of 16 channels:
    4 query and 2 KV heads a chip on four chips, as at published widths."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("minitron-8b").reduced(),
                               num_heads=16, num_kv_heads=8, head_dim=16)


def engine_config(page_size=None):
    from repro.runtime.config import ArenaConfig, EngineConfig
    return EngineConfig(arena=ArenaConfig(num_slots=4, cache_len=64,
                                          page_size=page_size)
                        ).with_fields(decode_chunk=4, use_kernels=True,
                                      interpret=True)


def trace(cfg):
    from repro.runtime.engine import synthetic_trace
    return synthetic_trace(cfg, num_requests=6, seed=7,
                           prompt_lens=(5, 13, 21), gen_lens=(6, 11),
                           arrival_every=1)


def params_of(api):
    """Norm scales and biases drawn away from their zero init, so the
    LayerNorm1p's scale and bias both reach the tokens."""
    params = api.init(jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    return jax.tree_util.tree_map_with_path(
        lambda p, x: 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)
        if "norm" in jax.tree_util.keystr(p)
        or "ln" in jax.tree_util.keystr(p) else x, params)


def spans(logdir):
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith("engine.")]


def main():
    from repro.launch.mesh import serve_mesh
    from repro.models import build_model
    from repro.models.common import (kernel_dispatch_counts,
                                     reset_kernel_dispatch)
    from repro.runtime.engine import ServeEngine
    from repro.runtime.mesh_serve import MeshServeEngine

    assert len(jax.devices()) == 4
    api = build_model(config())
    params = params_of(api)
    out = {}
    for arena, page_size in (("fixed", None), ("paged", 8)):
        one = ServeEngine(api, params, config=engine_config(page_size))
        out[f"one_{arena}"] = {r: o.tokens
                               for r, o in one.run(trace(api.cfg)).items()}
        mesh = MeshServeEngine(api, params, mesh=serve_mesh("1x4"),
                               config=engine_config(page_size))
        reset_kernel_dispatch()
        with tempfile.TemporaryDirectory() as logdir:
            with jax.profiler.trace(logdir):
                got = mesh.run(trace(api.cfg))
            if arena == "fixed":
                out["spans"] = spans(logdir)
        out[f"mesh_{arena}"] = {r: o.tokens for r, o in got.items()}
        out[f"dispatch_{arena}"] = kernel_dispatch_counts()
        out[f"stats_{arena}"] = dict(mesh.stats)
    print(json.dumps(out, default=str))


if __name__ == "__main__":
    sys.exit(main())
