"""Faults planted under the benchmark's timed path, for the tests that see
``correct`` come out false.

    python tests/chip_bench/bench_faults.py <cell> <fault>

runs the cell (or a configuration no cell uses yet) at the tiny rehearsal
size with the fault planted and prints the check as JSON (the four-chip
ones run so, on four virtual devices).
Faults:
  none              nothing planted
  token_altered     the host receives a different token than the engine
                    produced, once per request (its third token)
  state_unchanged   the fused decode chunk hands back the KV arena it was
                    given: its steps leave the serving state as it was
  exchange_left_out every sharded dense GEMM keeps its own chip's columns
                    and never gathers the others'
"""
import contextlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@contextlib.contextmanager
def planted(fault: str):
    from repro.runtime import engine as eng_mod
    from repro.runtime import serve as serve_mod
    from repro.models import common as model_common
    undo = []
    if fault == "token_altered":
        orig = eng_mod.ServeEngine._emit

        def emit(self, slot, token):
            req = self.sched.running[slot]
            if len(self.outputs[req.rid].tokens) == 2:
                token = (token + 1) % self.api.cfg.vocab_size
            return orig(self, slot, token)
        eng_mod.ServeEngine._emit = emit
        undo.append(lambda: setattr(eng_mod.ServeEngine, "_emit", orig))
    elif fault == "state_unchanged":
        orig = serve_mod.make_decode_chunk_fn

        def make(api, n):
            fn = orig(api, n)

            def chunk_fn(params, cache, tokens, remaining):
                return (cache,) + tuple(fn(params, cache, tokens,
                                           remaining)[1:])
            return chunk_fn
        serve_mod.make_decode_chunk_fn = make
        undo.append(lambda: setattr(serve_mod, "make_decode_chunk_fn", orig))
    elif fault == "exchange_left_out":
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        orig = model_common.dense_matmul

        def local_only(x2, w, block_m=128, interpret=False, mesh=None):
            if mesh is None:
                return orig(x2, w, block_m=block_m, interpret=interpret)
            own = jax.shard_map(lambda a, b: a @ b, mesh=mesh,
                                in_specs=(P(), P(None, "model")),
                                out_specs=P(), check_vma=False)(x2, w)
            return jnp.tile(own, (1, mesh.shape["model"]))
        model_common.dense_matmul = local_only
        undo.append(lambda: setattr(model_common, "dense_matmul", orig))
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        for u in undo:
            u()


def tiny_cycle(cell: str, fault: str, seed: int = 2 ** 31 + 5,
               seconds: float = 4.0, control: bool = False) -> dict:
    sys.path.insert(0, str(ROOT))
    from bench import common, loop, rehearse
    common.program_on_path()
    conf, mix = rehearse.pair(cell)
    conf, mix = rehearse.tiny_conf(conf), rehearse.tiny_mix(mix)
    with planted(fault):
        rec = loop.cycle(conf, mix, seed, seconds, common.CompileClock(),
                         control=control)
    return dict(rec["check"], failed=loop.failed(rec["reqs"]),
                attempted=len(rec["reqs"]), compiles=rec["compiles"])


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
    print(json.dumps(tiny_cycle(sys.argv[1], sys.argv[2])))
