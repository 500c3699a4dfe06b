"""Chip smoke: serve stablelm-1.6b at its published widths on one TPU.

    python chip_smoke.py               # one chip: phases (a)-(d) + op check
    python chip_smoke.py --four-chips  # minitron-8b on a 1x4 mesh, only

One chip, one process, in this order:

  device check   the first device must be a TPU; anything else exits 2
                 before any result is printed (no CPU fallback);
  phases (a)-(d) stablelm-1.6b (24 layers, d 2048, bf16, random weights
                 from seed 0) through ``repro.launch.serve``'s own
                 ``prepare`` / ``build_engine`` / ``parity_mismatches``:
                 8 slots x 2048 cache, 8 requests of 128-1000 prompt and
                 32-64 generated tokens, decode chunk 8 —
                   (a) dense, plain XLA
                   (b) dense through the Pallas dense_gemm (--use-kernels)
                   (c) --sparsity 0.8 --use-kernels: Sparse.B on compacted
                       GriffinWeights
                   (d) (c) on the paged arena (--page-size 16);
                 each checks tokens against the batch-1 greedy oracle and
                 the engine's prefill logits against a float32 plain-XLA
                 reference on the same weights; a token that differs from
                 the oracle must be a near tie under that reference;
  op check       ``auto_matmul`` in all four Modes on the model's GEMM
                 shapes against a float32 ``jnp.dot``.

``--four-chips`` runs only the mesh phase: minitron-8b (32 layers, d 4096,
16.5 GB in bf16 — more than one chip holds) created and compacted
straight into its 1x4 serving shardings, served dense and at
``--sparsity 0.5`` through the shard_map'd kernels, each compared by
tokens and by a logit gap with the plain-XLA GSPMD engine on the same
mesh, and every chip's peak memory held under 16 GB.

Every phase raises on failure.  The last line of standard output is the
JSON object ``{"ok": true, "device": {...}}``; the numbers on earlier
lines (seconds, tokens, host syncs per token, peak bytes) are bring-up
facts, not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

# serve: stablelm-1.6b, full width, 8 slots x 2048, 8 requests
ARCH = "stablelm-1.6b"
SERVE_ARGS = ["--slots", "8", "--cache-len", "2048", "--requests", "8",
              "--prompt-lens", "128,384,1000", "--gen-lens", "32,48,64",
              "--decode-chunk", "8"]
PHASES = (
    ("a", "dense, plain XLA", ["--sparsity", "0"]),
    ("b", "dense, Pallas dense_gemm", ["--sparsity", "0", "--use-kernels"]),
    ("c", "Sparse.B 0.8, griffin_spmm", ["--sparsity", "0.8",
                                         "--use-kernels"]),
    ("d", "Sparse.B 0.8, paged arena", ["--sparsity", "0.8",
                                        "--use-kernels", "--page-size",
                                        "16"]),
)
# four chips: minitron-8b, full width, on a 1x4 mesh
MESH_ARCH = "minitron-8b"
MESH_ARGS = ["--mesh", "1x4", "--slots", "8", "--cache-len", "1024",
             "--requests", "8", "--prompt-lens", "100,400,900",
             "--gen-lens", "24,32", "--decode-chunk", "8"]
HBM_LIMIT = 16e9

# logit gap: max |engine - reference| over max |reference|, per prefill.
# bf16 weights and activations against float32 math; the measured gap is
# recorded in PERF.md.  A token that differs from its oracle must be a
# near tie: both candidates within TIE_TOL of the reference's top logit.
LOGIT_TOL = 0.05
TIE_TOL = 2 * LOGIT_TOL
# op check: bf16 output rounding against a float32 product
OP_TOL = 1e-2


def require(ok, what) -> None:
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _device():
    import jax
    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


class CompileClock:
    """Seconds XLA spent compiling (JAX's own monitoring event), so a
    phase's wall time splits into compile and the rest.  Tracing stays in
    the rest: nested traces would count twice."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def peak_bytes(device=None) -> int:
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def rel_gap(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def dense_twin(params):
    """The dense block-pruned matrices the compacted ``GriffinWeights``
    leaves denote (``decompact_weights``, vmapped over stacks)."""
    import jax
    from repro.kernels.griffin_spmm.ops import GriffinWeights, \
        decompact_weights

    def conv(x):
        if not isinstance(x, GriffinWeights):
            return x
        f = decompact_weights
        for _ in range(x.b_comp.ndim - 2):
            f = jax.vmap(f)
        return f(x)

    return jax.tree.map(conv, params,
                        is_leaf=lambda x: isinstance(x, GriffinWeights))


class F32Reference:
    """Next-token logits of a context under plain XLA in float32 on the
    same weights: compacted leaves decompacted, every float leaf widened,
    matmuls at ``highest`` precision, no sparse-execution scope."""

    def __init__(self, api, params):
        import jax
        import jax.numpy as jnp
        self.api, self.params = api, params

        def fn(p, batch):
            p = jax.tree.map(
                lambda a: a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                dense_twin(p))
            return api.prefill(p, batch)[1]

        self._fn = jax.jit(fn)

    def __call__(self, tokens) -> np.ndarray:
        import jax
        from repro.runtime.engine import Request
        tokens = np.asarray(tokens, np.int32)
        bucket = 8
        while bucket < len(tokens):
            bucket *= 2
        batch = Request(-1, tokens, 1).as_batch(bucket)
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._fn(self.params, batch), np.float32)[0]


def engine_prefill_logits(engine, req) -> np.ndarray:
    """The engine's own prefill of ``req`` — its jit, its Mode scope, its
    bucket — as float32 logits."""
    with engine._scope():
        _, logits = engine._fns()[0](
            engine.params, req.as_batch(engine.bucket_for(req.prompt_len)))
    return np.asarray(logits, np.float32)[0]


def near_tie(ref_logits, a: int, b: int) -> float:
    """How far the weaker of two tokens sits below the reference's top
    logit, over the logit scale (0 = both are the argmax)."""
    top = float(ref_logits.max())
    scale = max(float(np.abs(ref_logits).max()), 1e-30)
    return max(top - float(ref_logits[a]), top - float(ref_logits[b])) / scale


def kernel_text(engine) -> str:
    """Compiled text of the engine's full-length fused decode chunk."""
    with engine._scope():
        fn = engine._fns()[1](engine.decode_chunk)
        return fn.lower(engine.params, engine.cache, engine._tokens,
                        engine._remaining).compile().as_text()


def serve_phase(tag, title, argv, clock):
    """One serving phase through launch/serve.py's own path, on the
    registry config itself (published widths, bf16); returns its facts
    and raises on any failed check."""
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models.common import kernel_dispatch_counts, \
        reset_kernel_dispatch

    t0 = time.perf_counter()
    prep = serve.prepare(argv)
    require(prep.cfg == get_config(prep.args.arch)
            and prep.cfg.dtype == "bfloat16",
            f"phase {tag}: not the published bf16 config")
    reset_kernel_dispatch()
    engine = serve.build_engine(prep.api, prep.params, prep.args, prep.mesh,
                                econf=prep.econf)
    setup_s = time.perf_counter() - t0
    t1, c1 = time.perf_counter(), clock.seconds
    outs = engine.run(prep.reqs)
    serve_s, compile_s = time.perf_counter() - t1, clock.seconds - c1
    toks = engine.stats["emitted"]
    expect = sum(r.max_new_tokens for r in prep.reqs)
    require(toks == expect, f"phase {tag}: {toks} tokens, expected {expect}")
    require(len(engine.mode_history) == 1,
            f"phase {tag}: Mode changed mid-run {engine.mode_history}")
    syncs = engine.stats["host_syncs"] / max(toks, 1)

    uses_kernels = prep.args.use_kernels
    dispatch = kernel_dispatch_counts()
    text = kernel_text(engine)
    custom = text.count("tpu_custom_call")
    require(not engine.interpret,
            f"phase {tag}: interpret-mode kernels on the chip")
    if uses_kernels:
        require(dispatch.get("kernel", 0) > 0 and custom > 0,
                f"phase {tag}: kernels did not reach the decode program "
                f"({dispatch}, {custom} tpu_custom_call)")
    else:
        require(dispatch.get("kernel", 0) == 0 and custom == 0,
                f"phase {tag}: plain-XLA phase ran kernels {dispatch} "
                f"{custom}")

    checked, bad = serve.parity_mismatches(engine, prep.api, prep.reqs, outs)
    require(checked == len(prep.reqs),
            f"phase {tag}: oracle checked {checked}/{len(prep.reqs)}")
    longest = max(prep.reqs, key=lambda r: r.prompt_len)
    got = engine_prefill_logits(engine, longest)
    params = engine.params
    del engine
    gc.collect()

    ref = F32Reference(prep.api, params)
    gap = rel_gap(got, ref(longest.tokens))
    require(gap <= LOGIT_TOL,
            f"phase {tag}: prefill logit gap {gap:.5f} > {LOGIT_TOL}")
    ties = []
    for rid, at, e_tok, o_tok in bad:
        req = prep.reqs[rid]
        ctx = np.concatenate([req.tokens, np.asarray(outs[rid].tokens[:at],
                                                     np.int32)])
        tie = near_tie(ref(ctx), e_tok, o_tok)
        print(f"  phase {tag}: request {rid} differs from the greedy "
              f"oracle at token {at} (engine {e_tok}, oracle {o_tok}); "
              f"reference tie gap {tie:.5f}")
        require(tie <= TIE_TOL,
                f"phase {tag}: request {rid} token {at} is not a near tie "
                f"({tie:.5f} > {TIE_TOL})")
        ties.append(tie)
    del params, prep, outs, ref
    gc.collect()
    facts = {
        "phase": tag, "what": title, "setup_s": setup_s,
        "compile_s": compile_s, "run_s": serve_s - compile_s,
        "tokens": toks, "host_syncs_per_token": syncs,
        "parity": f"{checked - len(bad)}/{checked}",
        "logit_gap": gap, "near_ties": ties,
        "tpu_custom_calls": custom, "dispatch": dispatch,
        "peak_bytes_in_use": peak_bytes()}
    print(f"phase {tag} ({title}) OK: " + json.dumps(facts), flush=True)
    return facts


def op_check(shapes=((2048, 2048), (2048, 5632), (5632, 2048)),
             ms=(8, 512), dtype_name="bfloat16", interpret=False):
    """``auto_matmul`` through dense, A, B and AB on the model's GEMM
    shapes, each against a float32 ``jnp.dot`` at ``highest`` precision."""
    import jax
    import jax.numpy as jnp
    from repro.core.hybrid import select_mode
    from repro.core.spec import Mode
    from repro.kernels.griffin_spmm.ops import auto_matmul, \
        preprocess_weights
    from repro.sparsity.pruning import block_prune

    dt = jnp.dtype(dtype_name)
    rng = np.random.default_rng(0)
    worst = {}
    for k, n in shapes:
        w = jnp.asarray(rng.standard_normal((k, n), np.float32), dt)
        wp = block_prune(w, 0.8, 128, 32)
        gw = preprocess_weights(np.asarray(wp))
        for m in ms:
            a = rng.standard_normal((m, k), np.float32)
            a_sp = a.copy()
            a_sp[:, : k // 2] = 0.0        # half the K blocks all-zero
            for a_s, b_s, x, mode in ((0.0, 0.0, a, Mode.DENSE),
                                      (0.5, 0.0, a_sp, Mode.A),
                                      (0.0, 0.8, a, Mode.B),
                                      (0.5, 0.8, a_sp, Mode.AB)):
                require(select_mode(a_s, b_s) == mode, (a_s, b_s, mode))
                xa = jnp.asarray(x, dt)
                wr = wp if mode in (Mode.B, Mode.AB) else w
                out = auto_matmul(xa, w, gw if b_s else None, a_sparsity=a_s,
                                  b_sparsity=b_s, interpret=interpret)
                with jax.default_matmul_precision("highest"):
                    ref = jnp.dot(xa.astype(jnp.float32),
                                  wr.astype(jnp.float32))
                gap = rel_gap(out, ref)
                require(out.shape == (m, n) and out.dtype == dt
                        and np.isfinite(np.asarray(out, np.float32)).all()
                        and gap <= OP_TOL,
                        f"op check {mode.value} M{m} {k}x{n}: gap {gap}")
                worst[mode.value] = max(worst.get(mode.value, 0.0), gap)
    print("op check OK: auto_matmul dense/A/B/AB on "
          f"{[f'{k}x{n}' for k, n in shapes]} x M{list(ms)} {dtype_name}, "
          f"worst gap per Mode {json.dumps(worst)}", flush=True)
    return worst


def one_chip(clock):
    for tag, title, extra in PHASES:
        serve_phase(tag, title, ["--arch", ARCH, *SERVE_ARGS, *extra], clock)
    op_check()


def mesh_engine_run(prep, econf, clock):
    """Build and run one mesh engine over ``prep``'s (shared) params."""
    import jax
    from repro.launch import serve
    from repro.models.common import kernel_dispatch_counts, \
        reset_kernel_dispatch
    t0, c0 = time.perf_counter(), clock.seconds
    reset_kernel_dispatch()
    engine = serve.build_engine(prep.api, prep.params, prep.args, prep.mesh,
                                econf=econf)
    # never whole on one chip: every large leaf is split over the mesh
    whole = [x.shape for x in jax.tree.leaves(engine.params)
             if x.size * x.dtype.itemsize > 64e6
             and x.sharding.is_fully_replicated]
    require(not whole, f"param leaves replicated on every chip: {whole[:4]}")
    outs = engine.run(prep.reqs)
    toks = engine.stats["emitted"]
    require(toks == sum(r.max_new_tokens for r in prep.reqs),
            f"mesh engine served {toks} tokens")
    logits = [engine_prefill_logits(engine, r) for r in prep.reqs]
    facts = {"wall_s": time.perf_counter() - t0,
             "compile_s": clock.seconds - c0, "tokens": toks,
             "host_syncs_per_token": engine.stats["host_syncs"] / toks,
             "dispatch": kernel_dispatch_counts(),
             "mode": engine.mode.value}
    return engine, {r.rid: list(outs[r.rid].tokens) for r in prep.reqs}, \
        logits, facts


def four_chips(clock):
    """minitron-8b on a 1x4 mesh: shard_map'd kernels against plain-XLA
    GSPMD on the same mesh, dense and Sparse.B 0.5."""
    import jax
    from repro.launch import serve
    from repro.runtime.engine import Request
    results = {}
    for tag, extra in (("dense", ["--sparsity", "0", "--use-kernels"]),
                       ("sparse0.5", ["--sparsity", "0.5",
                                      "--use-kernels"])):
        t0 = time.perf_counter()
        prep = serve.prepare(["--arch", MESH_ARCH, *MESH_ARGS, *extra])
        setup_s = time.perf_counter() - t0
        total = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(prep.params))
        kern = prep.econf
        # the same mesh and params through plain XLA: dense GEMMs as jnp
        # dots, compacted ones through the decompaction oracle
        plain = (kern.with_fields(use_kernels=False)
                 if tag == "dense" else
                 kern.replace(kernels=dataclasses.replace(
                     kern.kernels, spmd_kernels=False)))
        eng, toks_k, lg_k, f_k = mesh_engine_run(prep, kern, clock)
        require(f_k["dispatch"].get("shard_map", 0) > 0, f_k["dispatch"])
        text = kernel_text(eng)
        require("tpu_custom_call" in text, f"{tag}: no kernel in decode")
        del eng
        gc.collect()
        eng, toks_x, lg_x, f_x = mesh_engine_run(prep, plain, clock)
        require(f_x["dispatch"].get("shard_map", 0) == 0, f_x["dispatch"])
        gaps = [rel_gap(a, b) for a, b in zip(lg_k, lg_x)]
        gap = max(gaps)
        require(gap <= LOGIT_TOL, f"{tag}: logit gap {gap} > {LOGIT_TOL}")
        same = sum(toks_k[r] == toks_x[r] for r in toks_k)
        for r in toks_k:
            if toks_k[r] != toks_x[r]:
                at = next(i for i, (p, q) in enumerate(zip(toks_k[r],
                                                            toks_x[r]))
                          if p != q)
                ctx = Request(-1, np.concatenate(
                    [prep.reqs[r].tokens, np.asarray(toks_x[r][:at],
                                                     np.int32)]), 1)
                tie = near_tie(engine_prefill_logits(eng, ctx),
                               toks_k[r][at], toks_x[r][at])
                print(f"  {tag}: request {r} differs from plain XLA at "
                      f"token {at}; plain-XLA tie gap {tie:.5f}")
                require(tie <= TIE_TOL, (tag, r, at, tie))
        del eng
        gc.collect()
        peaks = [peak_bytes(d) for d in jax.devices()]
        require(max(peaks) < HBM_LIMIT, f"peak bytes per chip {peaks}")
        results[tag] = {"setup_s": setup_s, "param_bytes": total,
                        "kernels": f_k, "plain_xla": f_x,
                        "tokens_equal": f"{same}/{len(toks_k)}",
                        "logit_gap": gap, "peak_bytes_per_chip": peaks}
        print(f"four-chip {tag} OK: " + json.dumps(results[tag]),
              flush=True)
        del prep
        gc.collect()
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the minitron-8b 1x4 mesh phase")
    args = ap.parse_args(argv)

    platform, kind, count = _device()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r} "
              f"({kind}, {count} devices)", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if count < need:
        print(f"chip_smoke: needs {need} TPU chips, found {count}",
              file=sys.stderr)
        return 2
    print(f"device: {platform} {kind} x{count}", flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs.platform import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(clock)
    else:
        one_chip(clock)
    print(f"total {time.perf_counter() - t0:.1f}s, compile "
          f"{clock.seconds:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
