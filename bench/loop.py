"""Set-up, the open-loop window and the output check of one cell.

This is the one place the benchmark drives the program under test: it
builds the model and the engine through the program's own serving entry
points (``build_model``, ``sparsify_params``, ``launch.serve``'s parser and
``build_engine``), feeds the engine on the wall clock, and stamps what
comes back.  The weights come from ``bench.weights``; the check runs
``bench.reference`` with the configuration's block (``bench.blocks``),
which import nothing of the program.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from . import blocks, reference, traffic, weights

# a request in flight when the window closes gets this long to finish
DRAIN_S = 30.0
# rids of the warm-up burst, clear of the window's 0..n-1
WARM_RID = 10 ** 6


def program_config(conf: dict):
    """The program's model config with every ``arch`` key of the
    configuration file in the field of the same name (a JSON list as a
    tuple).  A key the program's config has no field for is refused, so
    no size or mechanism the file states is served at a default."""
    from repro.configs import get_config
    base = get_config(conf["program"])
    fields = {f.name for f in dataclasses.fields(base)}
    unknown = sorted(set(conf["arch"]) - fields)
    if unknown:
        raise ValueError(f"configuration {conf['name']!r} sets {unknown}, "
                         f"which the program's ModelConfig does not have")
    return dataclasses.replace(base, **{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in conf["arch"].items()})


def engine_argv(conf: dict) -> List[str]:
    d = conf["deployment"]
    argv = ["--arch", conf["program"], "--slots", str(d["slots"]),
            "--cache-len", str(d["cache_len"]),
            "--decode-chunk", str(d["decode_chunk"]),
            "--sparsity", str(conf["pruning"]["weight_sparsity"])]
    if d["use_kernels"]:
        argv.append("--use-kernels")
    if d["mesh"]:
        argv += ["--mesh", d["mesh"]]
    return argv


def param_layout(conf: dict, api):
    """(shapes, shardings) of the program's parameter tree: on a mesh the
    program's own serving layout, so handing the weights over moves no
    byte; on one chip the default device."""
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    mesh_spec = conf["deployment"]["mesh"]
    if not mesh_spec:
        return shapes, None
    from repro.launch.mesh import serve_mesh
    from repro.runtime.mesh_serve import serve_shardings
    d = conf["deployment"]
    p_sh = serve_shardings(api, serve_mesh(mesh_spec), shapes, d["slots"],
                           d["cache_len"])[0]
    return shapes, p_sh


def build(conf: dict, seed: int):
    """The engine the window drives, with weights from ``seed``."""
    from repro.launch import serve as cli
    from repro.models import build_model
    from repro.runtime.elastic import plan_mesh
    from repro.sparsity import sparsify_params

    api = build_model(program_config(conf))
    shapes, shardings = param_layout(conf, api)
    params = weights.make(shapes, conf, seed, shardings)
    pr = conf["pruning"]
    if pr["weight_sparsity"] > 0:
        params = sparsify_params(params, pr["weight_sparsity"], compact=True,
                                 block_k=pr["block_k"], block_n=pr["block_n"],
                                 unit=pr["unit"])
    args = cli.build_parser().parse_args(engine_argv(conf))
    mesh = plan_mesh(conf["deployment"]["chips"], 1)
    return cli.build_engine(api, params, args, mesh)


def warm(engine, mix: dict, vocab: int, plan: List[traffic.Planned]) -> None:
    """Compile every shape the window will use, and no other: one prompt
    per prefill bucket of the mix's prompt range, admitted together; then
    one request alone, long enough to run the whole chunk ladder (8, 4,
    2, 1); then the host-side padding of every prompt length the window
    will send (the engine pads each prompt to its bucket with a jitted
    pad, one program per length)."""
    from repro.runtime.engine import Request
    rng = np.random.default_rng(0)
    lens = traffic.warm_lengths(mix)
    ladder = 2 * engine.decode_chunk
    for i, n in enumerate(lens + [lens[0]]):
        engine.add(Request(WARM_RID + i, rng.integers(0, vocab, n,
                                                      dtype=np.int32),
                           16 if i < len(lens) else ladder,
                           arrival=engine.clock))
        if i >= len(lens) - 1:
            while engine.sched.has_work():
                engine.step()
    for n in sorted({len(p.prompt) for p in plan}):
        batch = Request(0, np.zeros(n, np.int32), 1).as_batch(
            engine.bucket_for(n))
        jax.block_until_ready(batch)
    jax.block_until_ready(engine.cache)


class Slice:
    """The traced part of the window: profiler on from ``start_s`` to
    ``stop_s``, with the host-side counts the per-layer metrics divide by."""

    def __init__(self, start_s: float, stop_s: float, logdir: str):
        self.start_s, self.stop_s, self.logdir = start_s, stop_s, logdir
        self.state = "before"
        self.span = None
        self.counts = {"prompt_tokens": 0, "admitted": 0, "emitted": 0}
        self.stats0 = self.stats1 = None
        self.t0 = self.t1 = None
        self.stall_s = 0.0          # host's wait while the trace is written

    def tick(self, now: float, engine, clock_s) -> None:
        if self.state == "before" and now >= self.start_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.slice")
            self.span.__enter__()
            self.stats0, self.t0 = dict(engine.stats), clock_s()
            self.state = "on"
        elif self.state == "on" and now >= self.stop_s:
            self.stats1, self.t1 = dict(engine.stats), clock_s()
            self.span.__exit__(None, None, None)
            jax.block_until_ready(engine.cache)
            jax.profiler.stop_trace()
            self.stall_s = clock_s() - self.t1
            self.state = "done"

    def inside(self, t: float) -> bool:
        return self.state == "on" and t >= self.t0


def run_window(engine, plan: List[traffic.Planned], seconds: float,
               compiles, sl: Optional[Slice] = None,
               drain_s: float = DRAIN_S) -> dict:
    """Open loop on the wall clock: each request is handed to the engine
    when it falls due (arrival = the engine's current tick), the engine
    steps while it has work and the loop sleeps to the next due time while
    it has none.  Every token is stamped when ``step`` returns it.  After
    ``seconds`` nothing more is sent and the requests in flight get
    ``drain_s`` to finish, plus however long the host waited for the
    profiler to write its trace."""
    from repro.runtime.engine import Request
    reqs: Dict[int, dict] = {
        p.rid: {"due": p.due_s, "added": None, "admit": None, "times": [],
                "tokens": [], "prompt": p.prompt, "max_new": p.max_new,
                "refused": False}
        for p in plan}
    seen = set(engine.outputs)
    c0 = compiles.count
    t0 = time.perf_counter()
    clock_s = lambda: time.perf_counter() - t0
    i = 0
    slowest = (0.0, 0.0)                     # longest tick and its start
    while True:
        now = clock_s()
        while i < len(plan) and plan[i].due_s <= now:
            p = plan[i]
            with jax.profiler.TraceAnnotation("bench.add"):
                try:
                    engine.add(Request(p.rid, p.prompt, p.max_new,
                                       arrival=engine.clock))
                except ValueError:
                    reqs[p.rid]["refused"] = True
            reqs[p.rid]["added"] = clock_s()
            i += 1
        if sl is not None:
            sl.tick(now, engine, clock_s)
        if engine.sched.has_work():
            start = clock_s()
            with jax.profiler.TraceAnnotation("bench.step"):
                events = engine.step()
            t = clock_s()
            slowest = max(slowest, (t - start, start))
            for _, rid, tok in events:
                r = reqs.get(rid)
                if r is not None:
                    r["times"].append(t)
                    r["tokens"].append(int(tok))
            if len(engine.outputs) > len(seen):
                for rid in list(engine.outputs)[len(seen):]:
                    seen.add(rid)
                    if rid in reqs:
                        reqs[rid]["admit"] = start
                        if sl is not None and sl.inside(start):
                            sl.counts["admitted"] += 1
                            sl.counts["prompt_tokens"] += \
                                len(reqs[rid]["prompt"])
            if sl is not None and sl.inside(start):
                sl.counts["emitted"] += sum(1 for e in events
                                            if e[1] in reqs)
        elif i < len(plan) or now < seconds:
            nxt = plan[i].due_s if i < len(plan) else seconds
            if sl is not None and sl.state != "done":
                nxt = min(nxt, sl.stop_s if sl.state == "on" else sl.start_s)
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(nxt - clock_s(), 0.05)))
        else:
            break
        if clock_s() >= seconds + drain_s + (sl.stall_s if sl else 0.0):
            break
    end = clock_s()
    if sl is not None and sl.state == "on":
        sl.tick(float("inf"), engine, clock_s)
    return {"reqs": reqs, "seconds": seconds, "end_s": end,
            "compiles": compiles.count - c0, "slowest_tick": slowest}


class GcPauses:
    """The longest pause of Python's cyclic garbage collector and the
    seconds spent in it, while installed."""

    def __init__(self):
        self.longest = self.total = 0.0
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.longest, self.total = max(self.longest, d), self.total + d

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def finished(rec: dict) -> bool:
    return len(rec["tokens"]) == rec["max_new"]


def failed(reqs: Dict[int, dict]) -> int:
    return sum(1 for r in reqs.values() if r["refused"] or not finished(r))


def sample(reqs: Dict[int, dict], seed: int, min_tokens: int) -> List[int]:
    """Finished requests to check, drawn from the seed: the longest first,
    then others in a seeded order until ``min_tokens`` served tokens."""
    done = sorted(rid for rid, r in reqs.items() if finished(r))
    if not done:
        return []
    size = lambda rid: len(reqs[rid]["prompt"]) + len(reqs[rid]["tokens"])
    first = max(done, key=size)
    rest = [done[j] for j in np.random.default_rng(seed).permutation(
        len(done)) if done[j] != first]
    out, total = [first], len(reqs[first]["tokens"])
    for rid in rest:
        if total >= min_tokens:
            break
        out.append(rid)
        total += len(reqs[rid]["tokens"])
    return out


def free() -> None:
    """Collect what a dropped engine held on the device."""
    gc.collect()
    jax.clear_caches()


def check(conf: dict, mix: dict, seed: int, reqs: Dict[int, dict],
          control: bool = False) -> dict:
    """Regenerate the weights from ``seed`` and compare the served tokens
    of a seeded sample of finished requests with the reference.  With
    ``control`` also read the float8 control on the same positions and
    judge its tokens by the same limit (``control_correct``, which has to
    come out false)."""
    from repro.models import build_model
    ck = conf["check"]
    api = build_model(program_config(conf))
    shapes, shardings = param_layout(conf, api)
    w = weights.make(shapes, conf, seed, shardings)
    rids = sample(reqs, seed, ck["min_tokens"])
    block = blocks.name_of(conf)
    seq_len = conf["deployment"]["cache_len"]
    n_max = mix["output_tokens"]["max"]
    out = {"requests": len(rids), "tokens": 0, "served_gap": 0.0}
    if control:
        out["control_gap"] = 0.0
    for rid in rids:
        r = reqs[rid]
        g = reference.served_gaps(w, block, conf["arch"], r["prompt"],
                                  r["tokens"], seq_len, n_max)
        out["tokens"] += len(g)
        out["served_gap"] = max(out["served_gap"], float(g.max()))
        if control:
            c = reference.served_gaps(w, block, conf["arch"], r["prompt"],
                                      r["tokens"], seq_len, n_max,
                                      control=True)
            out["control_gap"] = max(out["control_gap"], float(c.max()))
    del w
    gc.collect()
    judged = bool(rids) and out["tokens"] >= ck["min_tokens"]
    out["correct"] = judged and out["served_gap"] <= ck["served_gap_limit"]
    if control:
        out["control_correct"] = judged and \
            out["control_gap"] <= ck["served_gap_limit"]
    return out


def cycle(conf: dict, mix: dict, seed: int, seconds: float, compiles,
          control: bool = False, drain_s: float = DRAIN_S) -> dict:
    """Set-up, window, drain and check of one seed, with no timing kept
    beyond the window's record: the control's and the rehearsal's run."""
    vocab = conf["arch"]["vocab_size"]
    engine = build(conf, seed)
    plan = traffic.draw(mix, seed, seconds, vocab)
    warm(engine, mix, vocab, plan)
    rec = run_window(engine, plan, seconds, compiles, drain_s=drain_s)
    engine = None
    free()
    rec["check"] = check(conf, mix, seed, rec["reqs"], control=control)
    return rec
