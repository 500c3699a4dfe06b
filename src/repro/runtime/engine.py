"""Continuous-batching serving engine: slot-pool KV arena + FCFS scheduler
+ device-resident decode hot path (DESIGN.md Sections 8-9).

A fixed ``num_slots x cache_len`` cache arena is shared by all in-flight
requests.  Each engine tick admits waiting requests into freed slots
(prefilling them one at a time at a power-of-two *bucketed* prompt length,
interleaved with decode of the running slots) and then advances every
running slot by ``decode_chunk`` tokens with a single fused, donated scan
(``runtime.serve.make_decode_chunk_fn``): decode -> argmax -> token
feedback -> per-slot remaining/live update all stay on device, and only a
(chunk, B) token ring plus two measurement scalars return to the host —
one host sync per chunk instead of three dispatches and a sync per token.
Admission writes a freshly prefilled single-request cache into its slot in
place (``dynamic_update_slice`` along the per-leaf batch axis, positions
carried as a per-slot (B,) vector the model decode paths understand);
eviction is just marking the slot free — the stale rows are dead weight
until the next admission overwrites them, and the on-device live mask
keeps them out of the measurement.

The engine is the serving face of the paper's hybrid execution: it keeps a
running *measured* activation sparsity (exact-zero fraction of the live
rows of the fused chunk's decode logits, accumulated on device), re-invokes
``core.hybrid.select_mode`` against the offline weight sparsity, and runs
every prefill/decode under a ``sparse_execution`` scope for the selected
category.  Mode is a trace-time decision (DESIGN.md Section 5), so a
category flip swaps to a fresh set of jitted fns traced under the new
scope — the jit cache is keyed by ``Mode``, at most four entries.  A flip
can lag the measurement by up to ``decode_chunk`` steps (Section 9).

``greedy_generate`` (runtime/serve.py) is the parity oracle: per-slot
decode is row-wise independent (MoE decode runs drop-free for exactly this
reason, see ``models.moe.moe_ffn``), so the engine's generated tokens for a
request match a batch-1 greedy run of the same prompt — padded to the same
bucket — token for token.
"""
from __future__ import annotations

import copy
import dataclasses
import enum
import functools
import heapq
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.checkpoint import restore as ckpt_restore, save as ckpt_save
from ..core.hybrid import SPARSE_THRESHOLD, select_mode
from ..core.spec import Mode
from ..kernels.griffin_spmm.ops import GriffinWeights
from ..models.common import sparse_execution
from ..models.registry import ModelApi
from ..optim.compression import quantize_rows
from ..sparsity.pruning import GEMM_WEIGHTS, sparsity_of
from .config import EngineConfig, resolve_engine_config
from .fault import DeviceLoss, FaultInjector
from .paging import PageAllocator, PagedSpec, build_spec, paged_tree
from .serve import make_chunk_ladder, pad_prompt_batch
from .spans import span
from .straggler import StragglerDetector

# Category knob handed to the sparse_execution scope when the *measured*
# activation sparsity selects an A-side mode and no declared value exists:
# the scope only consumes the category bit (above/below SPARSE_THRESHOLD),
# so any representative sparse-side constant keeps the trace stable across
# measurement jitter (DESIGN.md Section 5).
DEFAULT_DECLARED_A = 0.5

# Smallest prefill bucket: prompts shorter than this share one padded shape,
# so the bucket set is {8, 16, ..., cache_len} — O(log cache_len) shapes.
MIN_BUCKET = 8


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival`` is the earliest engine step at
    which the scheduler may admit it; ``extras`` carries non-token model
    inputs (whisper frames).

    ``priority``/``deadline_ms``/``ttft_deadline_ms`` are the SLO fields
    the multi-replica router's admission control consumes (DESIGN.md
    Section 13): priority 0 is the most important class, deadlines count
    virtual ticks after ``arrival`` (None = best-effort).  The defaults
    are FCFS-compatible — a plain ``ServeEngine`` ignores all three, so
    pre-router traces behave exactly as before."""

    rid: int
    tokens: np.ndarray
    max_new_tokens: int
    arrival: int = 0
    extras: Optional[Dict[str, np.ndarray]] = None
    priority: int = 0
    deadline_ms: Optional[int] = None
    ttft_deadline_ms: Optional[int] = None

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.tokens).shape[-1])

    def as_batch(self, bucket: Optional[int] = None) -> Dict[str, jax.Array]:
        """The batch-1 model input this request prefills with — also what
        oracle replays (greedy_generate) must feed so they compare against
        the same computation.  ``bucket`` right-pads the prompt to the
        engine's bucketed-prefill shape (``ServeEngine.bucket_for``)."""
        batch = {"tokens": jnp.asarray(
            np.asarray(self.tokens, np.int32).reshape(1, -1))}
        for k, v in (self.extras or {}).items():
            batch[k] = jnp.asarray(v)[None]
        return pad_prompt_batch(batch, bucket)


class Attribution(str, enum.Enum):
    """How a request's output came to be (DESIGN.md Section 13): served
    normally, shed by admission control, replayed on a surviving replica
    after its first replica died, or won by a hedged duplicate.  Plain
    engine runs only ever produce ``NORMAL``; the router stamps the
    rest."""

    NORMAL = "normal"
    SHED = "shed"
    RETRIED = "retried"
    HEDGED = "hedged"


@dataclasses.dataclass
class RequestOutput:
    rid: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    admitted: int = -1
    finished: int = -1
    # engine clock at each token's emission — consecutive diffs are the
    # virtual inter-token latency the serve bench reports (Section 13)
    token_steps: List[int] = dataclasses.field(default_factory=list)
    attribution: Attribution = Attribution.NORMAL
    shed_reason: Optional[str] = None
    # host wall stamps (time.perf_counter): when ``engine.add`` was called,
    # when the scheduler admitted the request (before its prefill
    # dispatch), and when the tick's sync returned its first token
    t_added: Optional[float] = dataclasses.field(default=None, compare=False)
    t_admitted: Optional[float] = dataclasses.field(default=None,
                                                    compare=False)
    t_first: Optional[float] = dataclasses.field(default=None, compare=False)


# ---------------------------------------------------------------------------
# scheduler (pure bookkeeping — no jax; the hypothesis sweeps in
# tests/test_properties.py drive it directly against random traces)
# ---------------------------------------------------------------------------

class Scheduler:
    """FCFS slot scheduler.

    ``policy="continuous"``: waiting requests are admitted into freed slots
    every step, at most ``max_admissions_per_step`` per tick, so prefill
    work interleaves with decode of the running slots.
    ``policy="static"``: admission only when the pool has fully drained —
    the classic static-batching baseline whose stragglers idle the pool
    (benchmarks/bench_serve.py measures the gap).

    Admission is amortized O(1) per request: an arrival-ordered heap feeds
    a ready queue ordered by submission as the clock passes each arrival,
    so a tick never rescans the whole waiting set (the old list scan was
    O(waiting) per tick, O(n * steps) per trace).  The admitted order is
    exactly the scan's — FCFS by submission over the arrived portion — and
    tests/test_properties.py holds the two implementations equal under
    random traces.
    """

    def __init__(self, num_slots: int, policy: str = "continuous",
                 max_admissions_per_step: int = 1):
        if num_slots < 1:
            raise ValueError("need at least one slot")
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {policy!r}")
        self.num_slots = num_slots
        self.policy = policy
        self.max_admissions = max(1, max_admissions_per_step)
        self._seq = 0                             # submission order
        self._by_arrival: List[Tuple[int, int, Request]] = []
        self._ready: List[Tuple[int, Request]] = []
        self.running: Dict[int, Request] = {}
        self.remaining: Dict[int, int] = {}
        self.finished: List[int] = []
        self._free = list(range(num_slots - 1, -1, -1))   # pop() -> slot 0

    def add(self, req: Request) -> None:
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be >=1")
        heapq.heappush(self._by_arrival, (req.arrival, self._seq, req))
        self._seq += 1

    @property
    def waiting(self) -> List[Request]:
        """Not-yet-admitted requests in submission order (inspection only —
        built on demand; the hot path never materializes it)."""
        pend = [(s, r) for _, s, r in self._by_arrival] + list(self._ready)
        return [r for _, r in sorted(pend)]

    @property
    def waiting_count(self) -> int:
        return len(self._by_arrival) + len(self._ready)

    def admissions(self, step: int,
                   gate: Optional[Callable[[Request], bool]] = None
                   ) -> List[Tuple[int, Request]]:
        """Pop the (slot, request) pairs to admit at ``step`` — FCFS over
        the arrived portion of the queue, bounded by free slots and the
        per-step admission budget.  ``gate`` (the paged arena's page
        reservation, DESIGN.md Section 14) may veto the head request: it is
        pushed back to the front of the ready queue and admission stops —
        head-of-line blocking, so FCFS order is preserved while the pool
        drains.  The gate is only invoked when a slot and budget are
        available, so a True verdict (and any reservation it made) always
        commits."""
        while self._by_arrival and self._by_arrival[0][0] <= step:
            _, seq, req = heapq.heappop(self._by_arrival)
            heapq.heappush(self._ready, (seq, req))
        if self.policy == "static" and self.running:
            return []
        budget = (self.num_slots if self.policy == "static"
                  else self.max_admissions)
        out: List[Tuple[int, Request]] = []
        while self._free and self._ready and len(out) < budget:
            seq, req = heapq.heappop(self._ready)
            if gate is not None and not gate(req):
                heapq.heappush(self._ready, (seq, req))
                break
            slot = self._free.pop()
            self.running[slot] = req
            self.remaining[slot] = req.max_new_tokens
            out.append((slot, req))
        return out

    def emit(self, slot: int) -> bool:
        """Record one emitted token on ``slot``; frees the slot and returns
        True when that was the request's last token."""
        self.remaining[slot] -= 1
        if self.remaining[slot] > 0:
            return False
        req = self.running.pop(slot)
        del self.remaining[slot]
        self._free.append(slot)
        self.finished.append(req.rid)
        return True

    def would_admit(self, step: int,
                    gate: Optional[Callable[[Request], bool]] = None) -> bool:
        """Non-mutating peek: would ``admissions(step)`` pop at least one
        request?  The router classifies a replica's tick phase with it
        (prefill vs decode vs idle) without disturbing the queues.  Pass a
        *non-mutating* ``gate`` (``ServeEngine._admission_fit`` for paged
        arenas) to also account for page availability."""
        if not self._free:
            return False
        if self.policy == "static" and self.running:
            return False
        head = self._ready[0][1] if self._ready else None
        if head is None and self._by_arrival \
                and self._by_arrival[0][0] <= step:
            head = self._by_arrival[0][2]
        if head is None:
            return False
        return gate(head) if gate is not None else True

    def cancel_slot(self, slot: int) -> Request:
        """Free ``slot`` without crediting a finished request — the
        router's hedge-loser/cancel path.  The request is *not* appended
        to ``finished``."""
        req = self.running.pop(slot)
        del self.remaining[slot]
        self._free.append(slot)
        return req

    def remove_waiting(self, rid: int) -> bool:
        """Drop a not-yet-admitted request from the queues (heaps are
        rebuilt — cancellation is rare and off the hot path).  Returns
        True when something was removed."""
        n0 = self.waiting_count
        self._by_arrival = [(a, s, r) for a, s, r in self._by_arrival
                            if r.rid != rid]
        heapq.heapify(self._by_arrival)
        self._ready = [(s, r) for s, r in self._ready if r.rid != rid]
        heapq.heapify(self._ready)
        return self.waiting_count < n0

    @property
    def active(self) -> List[int]:
        return sorted(self.running)

    def next_arrival(self) -> Optional[int]:
        """Arrival step of the earliest not-yet-arrived request (None when
        every waiting request has already arrived or the queue is empty) —
        the engine caps its fused-chunk length with it so a free slot is
        not left idle past a known arrival."""
        return self._by_arrival[0][0] if self._by_arrival else None

    def deferred_ready(self) -> bool:
        """True when arrived requests are still waiting (admission budget
        exhausted this tick) — the engine then keeps chunks short so the
        backlog drains at the next boundary."""
        return bool(self._ready)

    def has_work(self) -> bool:
        return bool(self._by_arrival or self._ready or self.running)

    # -- snapshot plumbing (DESIGN.md Section 11) ---------------------------

    def state_dict(self) -> Dict:
        """JSON-serializable snapshot of every queue — rides a checkpoint
        manifest's ``extra`` (checkpoint.read_manifest) so a fresh process
        can rebuild the host side of an engine snapshot and resume the
        trace.  Request token arrays become int lists; ``extras`` arrays
        (whisper frames) nested float lists — exact round-trips, floats
        included (float32 -> Python float -> float32 is lossless)."""
        def req(r: Request) -> Dict:
            d = {"rid": r.rid, "tokens": np.asarray(r.tokens).tolist(),
                 "max_new_tokens": r.max_new_tokens, "arrival": r.arrival,
                 "priority": r.priority, "deadline_ms": r.deadline_ms,
                 "ttft_deadline_ms": r.ttft_deadline_ms}
            if r.extras:
                d["extras"] = {k: [str(np.asarray(v).dtype),
                                   np.asarray(v).tolist()]
                               for k, v in r.extras.items()}
            return d
        return {"num_slots": self.num_slots, "policy": self.policy,
                "max_admissions": self.max_admissions, "seq": self._seq,
                "by_arrival": [[a, s, req(r)]
                               for a, s, r in sorted(self._by_arrival)],
                "ready": [[s, req(r)] for s, r in sorted(self._ready)],
                "running": {str(slot): req(r)
                            for slot, r in self.running.items()},
                "remaining": {str(s): int(n)
                              for s, n in self.remaining.items()},
                "finished": list(self.finished),
                "free": list(self._free)}

    @classmethod
    def from_state_dict(cls, d: Dict) -> "Scheduler":
        """Inverse of ``state_dict`` — reconstructs the exact queue state
        (heap entries, submission counter, free-slot stack), so admission
        order after a restore equals the uninterrupted run's."""
        def req(rd: Dict) -> Request:
            extras = {k: np.asarray(v, np.dtype(dt))
                      for k, (dt, v) in rd.get("extras", {}).items()} or None
            return Request(rid=rd["rid"],
                           tokens=np.asarray(rd["tokens"], np.int32),
                           max_new_tokens=rd["max_new_tokens"],
                           arrival=rd["arrival"], extras=extras,
                           priority=rd.get("priority", 0),
                           deadline_ms=rd.get("deadline_ms"),
                           ttft_deadline_ms=rd.get("ttft_deadline_ms"))
        sched = cls(d["num_slots"], d["policy"], d["max_admissions"])
        sched._seq = d["seq"]
        sched._by_arrival = [(a, s, req(r)) for a, s, r in d["by_arrival"]]
        heapq.heapify(sched._by_arrival)
        sched._ready = [(s, req(r)) for s, r in d["ready"]]
        heapq.heapify(sched._ready)
        sched.running = {int(k): req(r) for k, r in d["running"].items()}
        sched.remaining = {int(k): int(n) for k, n in d["remaining"].items()}
        sched.finished = list(d["finished"])
        sched._free = list(d["free"])
        return sched


# ---------------------------------------------------------------------------
# cache-arena plumbing
# ---------------------------------------------------------------------------

def _promote_arena(cache: Any, num_slots: int) -> Any:
    """``init_cache``'s tree with scalar counters promoted to per-slot
    (B,) vectors — the decode paths' vector-pos branch.  The single
    definition of the arena's shape contract: both engines allocate with
    it and the mesh layer's jit in_shardings are derived from it
    (runtime.mesh_serve), so the promotion rule cannot drift."""
    return jax.tree.map(
        lambda leaf: jnp.zeros((num_slots,), leaf.dtype)
        if leaf.ndim == 0 else leaf, cache)


def _batch_axes(api: ModelApi, cache_len: int) -> Any:
    """Per-leaf batch-axis index of the cache tree (-1 for scalar position
    counters), discovered by diffing the shapes ``init_cache`` declares for
    batch sizes 2 and 1 — no per-family knowledge needed."""
    two = jax.eval_shape(lambda: api.init_cache(2, cache_len))
    one = jax.eval_shape(lambda: api.init_cache(1, cache_len))

    def axis(p, s):
        diffs = [i for i, (a, b) in enumerate(zip(p.shape, s.shape))
                 if a != b]
        if len(diffs) > 1:
            raise ValueError(f"ambiguous cache batch axis: {p.shape} vs "
                             f"{s.shape}")
        if not diffs:
            if p.shape != ():
                raise ValueError("cache leaf without a batch axis must be "
                                 f"a scalar counter, got shape {p.shape}")
            return -1
        return diffs[0]

    return jax.tree.map(axis, two, one)


def _make_insert(axes: Any, jit_wrap: Optional[Callable] = None) -> Callable:
    """Jitted in-place (donated) admission: writes a single-request cache
    into one slot of the pool arena, seeds the slot's feedback token from
    the prefill logits (argmax on device) and its owed-token counter — one
    dispatch per admission, no host sync.  Scalar counters (axis -1) land
    in the promoted per-slot (B,) vector.  Returns the (1,) first token so
    the host can emit it lazily with the next chunk's sync.

    ``jit_wrap`` supplies the jit policy: plain donation by default; the
    mesh-parallel engine (``runtime.mesh_serve``, DESIGN.md Section 10)
    passes donation *plus* the arena in/out shardings, so a sharded pool
    stays sharded across admissions and the replicated batch-1 prefill
    cache reshards on the way in."""
    wrap = jit_wrap or functools.partial(jax.jit, donate_argnums=(0, 1, 2))

    @wrap
    def insert(pool, tokens, remaining, sub, logits, slot, rem):
        def one(pl, sl, ax):
            if ax < 0:
                return jax.lax.dynamic_update_slice(
                    pl, sl.astype(pl.dtype).reshape(1), (slot,))
            starts = [0] * pl.ndim
            starts[ax] = slot
            return jax.lax.dynamic_update_slice(pl, sl.astype(pl.dtype),
                                                tuple(starts))
        pool = jax.tree.map(one, pool, sub, axes)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)          # (1,)
        tokens = jax.lax.dynamic_update_slice(tokens, tok[:, None], (slot, 0))
        remaining = jax.lax.dynamic_update_slice(
            remaining, rem.reshape(1), (slot,))
        return pool, tokens, remaining, tok

    return insert


def _make_paged_insert(axes: Any, spec: PagedSpec,
                       jit_wrap: Optional[Callable] = None) -> Callable:
    """Paged-arena admission (DESIGN.md Section 14): the prefilled
    single-request cache's pageable leaves are reshaped into (stack,
    max_pages, page_size, ...) token pages and scattered onto the slot's
    reserved physical pages (``page_row``); unreserved logical pages map to
    the DUMP page, so bucket padding beyond the reservation is discarded by
    construction.  The slot's page-table row is installed in the same
    dispatch, every non-pageable leaf takes the fixed-arena
    dynamic_update_slice path, and int8 pools quantize per token row on the
    way in (optim.compression.quantize_rows), storing the scales alongside.
    No resident page is ever copied — admission is one scatter per pageable
    leaf regardless of pool occupancy."""
    wrap = jit_wrap or functools.partial(jax.jit, donate_argnums=(0, 1, 2))

    @wrap
    def insert(pool, tokens, remaining, sub, logits, slot, rem, page_row):
        def one(pl, sl, ax):
            if ax < 0:
                return jax.lax.dynamic_update_slice(
                    pl, sl.astype(pl.dtype).reshape(1), (slot,))
            starts = [0] * pl.ndim
            starts[ax] = slot
            return jax.lax.dynamic_update_slice(pl, sl.astype(pl.dtype),
                                                tuple(starts))
        out = {}
        for key, pl in pool.items():
            if key == "pages":
                out[key] = pl.at[slot].set(page_row)
            elif key in spec.paged_keys or key.endswith("_scale"):
                pass                       # rewritten with their pool below
            else:
                out[key] = jax.tree.map(one, pl, sub[key], axes[key])
        for key in spec.paged_keys:
            x = sub[key][:, 0]                   # (stack, cache_len, *rest)
            x = x.reshape(x.shape[0], spec.max_pages, spec.page_size,
                          *x.shape[2:])
            if spec.kv_dtype == "int8":
                q, s = quantize_rows(x, 3)
                out[key] = pool[key].at[:, page_row].set(q)
                out[key + "_scale"] = \
                    pool[key + "_scale"].at[:, page_row].set(s)
            else:
                out[key] = pool[key].at[:, page_row].set(
                    x.astype(pool[key].dtype))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)          # (1,)
        tokens = jax.lax.dynamic_update_slice(tokens, tok[:, None], (slot, 0))
        remaining = jax.lax.dynamic_update_slice(
            remaining, rem.reshape(1), (slot,))
        return out, tokens, remaining, tok

    return insert


def _default_serve_fns(api: ModelApi, cache_len: int, decode_chunk: int = 8):
    """Unsharded single-host jits; the mesh-aware factory is
    ``runtime.serve.jit_serve_fns`` (launch/serve.py passes it in).  The
    third element is ``chunk_for(n)`` — a memoized fused-chunk jit per scan
    length on the engine's power-of-two ladder — with the cache/token/
    remaining carry donated so the pool arena updates in place."""
    prefill = jax.jit(lambda p, b: api.prefill(p, b, cache_len=cache_len))
    decode = jax.jit(lambda p, c, t: api.decode_step(p, c, t),
                     donate_argnums=(1,))
    chunk_for = make_chunk_ladder(
        api, decode_chunk, lambda fn: jax.jit(fn, donate_argnums=(1, 2, 3)))
    return prefill, decode, chunk_for


def weight_sparsity(params: Any,
                    names: Sequence[str] = GEMM_WEIGHTS) -> float:
    """Mean sparsity of the weight GEMM leaves ``griffin_linear`` executes
    (trailing-name selection as in ``sparsity.sparsify_params``):
    ``GriffinWeights`` leaves report ``1 - density`` (their zeros were
    physically dropped), plain leaves their exact zero fraction — the
    B-side input to ``select_mode``."""
    vals: List[float] = []

    def walk(t, name=""):
        if isinstance(t, GriffinWeights):
            vals.append(1.0 - t.density)
        elif isinstance(t, dict):
            for k, v in t.items():
                walk(v, k)
        elif isinstance(t, (list, tuple)):
            for v in t:
                walk(v, name)
        elif name in names and hasattr(t, "ndim") and t.ndim >= 2 and \
                t.size and jnp.issubdtype(t.dtype, jnp.floating):
            # t.size == 0: zero-length layer stacks (stack_layers(n=0),
            # e.g. the reduced hybrid's empty tail) have no zero fraction
            vals.append(float(sparsity_of(t)))

    walk(params)
    return float(np.mean(vals)) if vals else 0.0


# ---------------------------------------------------------------------------
# recovery snapshots (DESIGN.md Section 11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineSnapshot:
    """Host-side copy of everything one engine tick can mutate, captured at
    tick start while recovery is armed: the device buffers (arena, token
    feedback, per-slot remaining) as numpy trees, deep copies of the pure-
    Python scheduler/outputs, and the measurement/mode/clock scalars.
    Rolling an engine back to a snapshot and replaying is deterministic, so
    a tick interrupted by a fault finishes with the same tokens as an
    uninterrupted run (DESIGN.md Section 11).  ``ckpt_step`` is set when
    the snapshot also went to disk (``ServeEngine(snapshot_dir=...)``) —
    recovery then reloads the device state through ``checkpoint.restore``
    onto the post-loss shardings instead of from memory."""

    device: Dict[str, Any]
    sched: Scheduler
    outputs: Dict[int, RequestOutput]
    events_len: int
    clock: int
    mode: Mode
    a_measured: float
    since_measure: int
    mode_history: List[Tuple[int, Mode]]
    stats: Dict[str, int]
    prefill_buckets: set
    t_added: Dict[int, float]
    ckpt_step: Optional[int] = None
    # paged-arena host state (allocator free list, slot->pages map, dirty
    # slots pending reclamation) — the device-side pool/page-table/scale
    # arrays already ride ``device["cache"]``, so replay after a restore
    # reproduces the exact same page assignments (DESIGN.md Section 14)
    paging: Optional[Dict] = None


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class ServeEngine:
    """Continuous-batching driver over a ``ModelApi``.

    ``fns_factory`` returns (prefill_fn, decode_fn, decode_chunk_fn[, ...])
    — pass ``lambda: jit_serve_fns(api, mesh, num_slots, cache_len,
    decode_chunk=...)`` to serve on a mesh (launch/serve.py does); default
    is single-host jits.  The factory is invoked once per selected
    execution mode: the resulting jits are traced (and always called) under
    that mode's ``sparse_execution`` scope, which is how a workload-category
    flip reaches the kernels.

    Greedy decoding only (argmax), matching the ``greedy_generate`` oracle.
    Prompts prefill at power-of-two bucketed lengths (``bucket_for``), so
    prefill retraces are bounded O(log cache_len) per mode instead of one
    per distinct prompt length; decode runs ``decode_chunk`` fused steps
    per host round-trip (DESIGN.md Section 9).

    Failure handling (DESIGN.md Section 11) arms when a ``fault_injector``
    (deterministic chaos, ``runtime.fault``), a ``straggler`` detector, or
    a ``snapshot_dir`` is passed: every tick captures a host-side snapshot
    first, a ``DeviceLoss`` rolls back/remeshes/replays, and persistent
    stragglers are evicted into the same path at tick boundaries.
    ``recoveries``/``recovery_log`` record what happened.
    """

    def __init__(self, api: ModelApi, params: Any, *,
                 config: Optional[EngineConfig] = None,
                 fns_factory: Optional[Callable] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 straggler: Optional[StragglerDetector] = None,
                 plan: Any = None, **legacy: Any):
        # ``config=EngineConfig(...)`` is the construction path (DESIGN.md
        # Section 14); the old flat keywords (num_slots=, cache_len=, ...)
        # still work for one release via the deprecation shim.  Runtime
        # objects (fns_factory, fault_injector, straggler, the resolved
        # kernel plan) stay direct arguments — they are not serializable
        # configuration.
        config = resolve_engine_config(config, legacy, type(self).__name__)
        self.config = config
        self.api = api
        # one placement up front: compacted weights arrive as host numpy
        # (kernels.griffin_spmm.preprocess_weights) and would otherwise be
        # copied to the device on every jitted call; already-placed arrays
        # (the mesh engine's sharded params) are left where they are
        params = jax.device_put(params)
        self.params = params
        if config.arena.cache_len is None:
            raise ValueError("cache_len is required: set "
                             "ArenaConfig.cache_len (or legacy cache_len=)")
        # paged arena resolution: a page_size activates the paged pool when
        # the family exposes pageable leaves (runtime/paging.py discovery;
        # xlstm's recurrent state degrades to the fixed arena), and
        # cache_len rounds up to a page multiple so pooled views keep the
        # fixed arena's shapes (fp32 paging stays bit-exact)
        self._paged, cache_len = build_spec(
            api, config.arena.num_slots, config.arena.cache_len,
            config.arena.page_size, config.arena.num_pages,
            config.arena.kv_dtype)
        num_slots = config.arena.num_slots
        policy = config.sched.policy
        max_admissions_per_step = config.sched.max_admissions_per_step
        use_kernels = config.kernels.use_kernels
        interpret = config.kernels.interpret
        spmd_kernels = config.kernels.spmd_kernels
        a_sparsity = config.kernels.a_sparsity
        block_m = config.kernels.block_m
        measure_every = config.sched.measure_every
        decode_chunk = config.sched.decode_chunk
        bucket_prompts = config.sched.bucket_prompts
        fused = config.sched.fused
        snapshot_dir = config.fault.snapshot_dir
        # tuned kernel plan (repro.tuning, DESIGN.md Section 12): a
        # KernelPlan (resolved by this model's family) or a FamilyPlan.
        # Only the Mode-selection thresholds act here — compaction
        # granularity was already applied when the caller ran
        # sparsify_params(plan=...) over these params.  Thresholds change
        # which kernels trace, never what they compute, so a planned
        # engine stays token-identical to the default one.
        fam = plan
        if plan is not None and hasattr(plan, "families"):
            fam = plan.family(api.cfg.family)
        self.plan = fam
        self._a_threshold = (fam.a_threshold if fam is not None
                             and fam.a_threshold is not None
                             else SPARSE_THRESHOLD)
        self._b_threshold = (fam.b_threshold if fam is not None
                             and fam.b_threshold is not None
                             else SPARSE_THRESHOLD)
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.decode_chunk = max(1, decode_chunk)
        # router/SLO hooks (DESIGN.md Section 13): ``chunk_cap`` caps the
        # fused-chunk ladder (degradation level 1 — shorter ticks, faster
        # admission turnaround); ``degraded`` forces the cheaper Mode by
        # zeroing the B-side threshold (level 2).  Both default inert.
        self.chunk_cap: Optional[int] = None
        self.degraded = False
        self.bucket_prompts = bucket_prompts
        # fused=False keeps the PR 3 per-step hot path (one decode dispatch
        # + host argmax + sync per token, measurement gathering the full
        # logits): the benchmark baseline bench_serve.py measures the fused
        # scan against, and a regression reference for the parity suite
        self.fused = fused
        self.sched = Scheduler(num_slots, policy, max_admissions_per_step)
        self._fns_factory = fns_factory or (
            lambda: _default_serve_fns(api, cache_len, self.decode_chunk))
        self._mode_fns: Dict[Mode, Tuple[Callable, ...]] = {}
        self.use_kernels = use_kernels
        self.interpret = interpret
        # spmd_kernels=False forces the SPMD decompaction/dense-product
        # oracles on a multi-device mesh instead of the shard_map'd Pallas
        # kernels — the fallback-forced parity smoke (DESIGN.md Section 10)
        self.spmd_kernels = spmd_kernels
        self.block_m = block_m
        self.a_declared = a_sparsity
        self.measure_every = max(1, measure_every)
        self.b_sparsity = weight_sparsity(params)
        self.a_measured = 0.0
        self.mode = self._select_mode()
        self.mode_history: List[Tuple[int, Mode]] = [(0, self.mode)]
        self.clock = 0
        self._since_measure = 0
        self.outputs: Dict[int, RequestOutput] = {}
        self.events: List[Tuple[int, int, int]] = []    # (step, rid, token)
        # live_rows: live row-steps of the decode chunks (the chunk's
        # on-device live count, so emitted == prefill_calls + live_rows);
        # prefill_tokens / prefill_padded_tokens: prompt tokens prefilled
        # and the bucket lengths they ran at; kv_blocks_read /
        # kv_blocks_arena: per layer, the KV blocks the chunks' attention
        # reads and the blocks the arena holds over the same steps (the
        # chunk's on-device ``ModelApi.kv_blocks`` sums, fetched in the
        # tick's one sync: the live rows' blocks under the live-KV kernel,
        # the whole arena where a fixed arena keeps ``decode_attention``
        # and on a paged arena, whose gathered view is the whole arena)
        self.stats = {"decode_steps": 0, "prefill_calls": 0, "emitted": 0,
                      "retraces": 0, "chunk_calls": 0, "host_syncs": 0,
                      "live_rows": 0, "prefill_tokens": 0,
                      "prefill_padded_tokens": 0, "kv_blocks_read": 0,
                      "kv_blocks_arena": 0}
        self._t_added: Dict[int, float] = {}    # rid -> wall stamp of add
        self.prefill_buckets: set = set()       # distinct admitted shapes
        # prompt buckets longer than the usable cache window cannot be
        # right-padded (the window would evict real K/V); those prompts
        # fall back to exact-length prefill
        window = getattr(api.cfg, "window", None)
        self._bucket_cap = min(cache_len, window or cache_len)
        # failure handling (DESIGN.md Section 11): while any of these are
        # armed, every tick starts by capturing a host-side snapshot —
        # detection (an injected/real DeviceLoss, or the straggler
        # detector's eviction verdict) then rolls back, remeshes onto the
        # survivors, reshards, and replays
        self.faults = fault_injector
        self.straggler = straggler
        self.snapshot_dir = snapshot_dir
        self.recoveries = 0
        self.recovery_log: List[Dict] = []
        self._snapshot: Optional[EngineSnapshot] = None
        self._evicted: set = set()
        self._params_host = (jax.tree.map(np.asarray, params)
                             if self._recovery_armed() else None)
        # paged-arena host bookkeeping (DESIGN.md Section 14): the physical
        # page allocator, the slot -> reserved-pages map, reservations made
        # by the admission gate this tick, and dead slots whose page-table
        # rows await the tick-start DUMP redirect + page reclamation
        self._page_alloc = (PageAllocator(self._paged.num_pages)
                            if self._paged is not None else None)
        self._slot_pages: Dict[int, List[int]] = {}
        self._reserved_pages: Dict[int, List[int]] = {}
        self._dirty_slots: set = set()
        self._clear_pages = (jax.jit(
            lambda pages, mask: jnp.where(mask[:, None], 0, pages),
            donate_argnums=(0,)) if self._paged is not None else None)
        self._init_device_state()

    # device placement hooks: the mesh-parallel engine
    # (runtime.mesh_serve.MeshServeEngine, DESIGN.md Section 10) overrides
    # these to place the arena sharded and wrap _insert with shardings; the
    # host-side bookkeeping above (scheduler, remaining mirror, outputs) is
    # identical either way
    _spmd_mesh = None          # consumed by _scope(); None = single-device

    def _arena(self) -> Any:
        """The engine's device arena tree: ``_promote_arena`` over
        init_cache's tree, rewritten into pool + page-table form when the
        arena is paged (runtime.paging.paged_tree)."""
        base = _promote_arena(
            self.api.init_cache(self.num_slots, self.cache_len),
            self.num_slots)
        if self._paged is not None:
            return paged_tree(base, self.num_slots, self._paged)
        return base

    def _init_device_state(self) -> None:
        """Allocate the arena (``_arena``), the donated slot-insert jit,
        and the token/remaining device buffers."""
        self.cache = self._arena()
        self._build_insert()
        self._tokens = jnp.zeros((self.num_slots, 1), jnp.int32)
        self._remaining = jnp.zeros((self.num_slots,), jnp.int32)

    def _build_insert(self) -> None:
        """(Re)jit the donated slot-insert — recovery rebuilds it when the
        arena shardings changed with the mesh (runtime.mesh_serve)."""
        axes = _batch_axes(self.api, self.cache_len)
        self._insert = (_make_paged_insert(axes, self._paged)
                        if self._paged is not None else _make_insert(axes))

    # -- paged-arena bookkeeping (DESIGN.md Section 14) ---------------------

    def _page_gate(self, req: Request) -> bool:
        """Admission gate: reserve the physical pages covering prompt +
        generation before the scheduler commits the slot.  On pool
        exhaustion the request stays at the head of the ready queue
        (head-of-line blocking keeps FCFS order); pages free up as running
        requests finish."""
        need = self._paged.pages_needed(req.prompt_len + req.max_new_tokens)
        ids = self._page_alloc.reserve(need)
        if ids is None:
            return False
        self._reserved_pages[req.rid] = ids
        return True

    def _admission_gate(self) -> Optional[Callable[[Request], bool]]:
        return self._page_gate if self._paged is not None else None

    def _admission_fit(self, req: Request) -> bool:
        """Non-mutating twin of ``_page_gate`` for ``would_admit`` peeks
        (the router's phase classification)."""
        if self._paged is None:
            return True
        need = self._paged.pages_needed(req.prompt_len + req.max_new_tokens)
        return need <= self._page_alloc.free_pages

    def _flush_dirty(self) -> None:
        """Tick-start reclamation: dead slots' page-table rows are
        redirected to the DUMP page on device (so their garbage decode
        writes stop landing on reclaimable pages) and their physical pages
        return to the allocator, becoming reservable by this tick's
        admissions.  Release is O(max_pages) metadata — no page is
        copied."""
        if self._paged is None or not self._dirty_slots:
            return
        mask = np.zeros((self.num_slots,), bool)
        mask[sorted(self._dirty_slots)] = True
        self.cache = dict(self.cache, pages=self._clear_pages(
            self.cache["pages"], jnp.asarray(mask)))
        for slot in sorted(self._dirty_slots):
            self._page_alloc.free(self._slot_pages.pop(slot, ()))
        self._dirty_slots.clear()

    def _paging_state(self) -> Dict:
        """JSON-serializable snapshot of the paged host state — rides
        ``EngineSnapshot.paging`` and the checkpoint manifest so recovery
        (and fresh-process restarts) reproduce the exact page
        assignments."""
        return {"allocator": self._page_alloc.state_dict(),
                "slot_pages": {str(s): [int(i) for i in ids]
                               for s, ids in self._slot_pages.items()},
                "dirty": sorted(int(s) for s in self._dirty_slots)}

    def _restore_paging(self, state: Dict) -> None:
        self._page_alloc = PageAllocator.from_state_dict(state["allocator"])
        self._slot_pages = {int(s): [int(i) for i in ids]
                            for s, ids in state["slot_pages"].items()}
        self._dirty_slots = set(int(s) for s in state["dirty"])
        self._reserved_pages = {}

    # -- mode plumbing ------------------------------------------------------

    def _a_now(self) -> float:
        return (self.a_declared if self.a_declared is not None
                else self.a_measured)

    def _select_mode(self) -> Mode:
        return select_mode(self._a_now(), self.b_sparsity,
                           threshold=self._a_threshold,
                           b_threshold=(0.0 if self.degraded
                                        else self._b_threshold))

    def set_degraded(self, on: bool) -> None:
        """Degradation-ladder level 2 (DESIGN.md Section 13): force the
        cheaper execution Mode through the PR 8 threshold machinery —
        ``on`` zeroes the B-side threshold so any pruned weight selects
        the Sparse.B kernels even in the dense-preferred regime (dense
        weights stay dense: 0 > 0 is false either way).  Re-selects
        immediately; a flip swaps the Mode-keyed jit set like any
        measured flip."""
        if on == self.degraded:
            return
        self.degraded = on
        mode = self._select_mode()
        if mode != self.mode:
            self.mode = mode
            self.mode_history.append((self.clock, mode))

    def _scope(self):
        a_scope = 0.0
        if self.mode in (Mode.A, Mode.AB):
            a_scope = (self.a_declared
                       if self.a_declared is not None
                       and self.a_declared > self._a_threshold
                       else DEFAULT_DECLARED_A)
        return sparse_execution(use_kernels=self.use_kernels,
                                interpret=self.interpret,
                                a_sparsity=a_scope, block_m=self.block_m,
                                spmd_mesh=self._spmd_mesh,
                                spmd_kernels=self.spmd_kernels,
                                a_threshold=self._a_threshold)

    def _fns(self) -> Tuple[Callable, Callable, Callable]:
        fns = self._mode_fns.get(self.mode)
        if fns is None:
            made = self._fns_factory()
            fns = (made[0], made[1], made[2])
            self._mode_fns[self.mode] = fns
            self.stats["retraces"] += 1
        return fns

    def _measure(self, zero_frac: float) -> None:
        """Workload-category measurement from the fused chunk's on-device
        accumulator (exact-zero logit fraction of live rows only — the scan
        masks out freed/unadmitted slots, so their stale rows cannot skew
        the category); a flipped ``select_mode`` verdict swaps the
        jitted-fn set (mode is a trace-time decision, DESIGN.md Section 5)
        starting with the *next* chunk — flips lag the measurement by at
        most ``decode_chunk`` steps (Section 9)."""
        self._since_measure = 0
        self.a_measured = float(zero_frac)
        mode = self._select_mode()
        if mode != self.mode:
            self.mode = mode
            self.mode_history.append((self.clock, mode))

    # -- request lifecycle --------------------------------------------------

    def add(self, req: Request) -> None:
        if req.prompt_len + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + gen "
                f"{req.max_new_tokens} exceeds cache_len {self.cache_len}")
        if self.api.cfg.is_encdec and (req.extras or {}).get("frames") is None:
            raise ValueError(f"request {req.rid}: enc-dec model needs "
                             "extras['frames']")
        self.sched.add(req)
        self._t_added[req.rid] = time.perf_counter()

    def bucket_for(self, prompt_len: int) -> Optional[int]:
        """Power-of-two prefill bucket for a prompt length (min
        ``MIN_BUCKET``), or None when the bucket would overflow the usable
        cache window (exact-length prefill then; also when bucketing is
        disabled).  Bounds distinct admitted prefill shapes — hence prefill
        retraces per mode — to O(log cache_len)."""
        if not self.bucket_prompts:
            return None
        b = MIN_BUCKET
        while b < prompt_len:
            b *= 2
        return b if b <= self._bucket_cap else None

    def _chunk_len(self, admitted_slots: frozenset = frozenset()) -> int:
        """Fused-chunk length for this tick: the largest power of two
        <= ``decode_chunk`` that (a) no live slot finishes inside — the
        host mirror of ``remaining`` makes mid-chunk completions
        predictable, so finishing slots free exactly at a chunk boundary
        and no decode step is ever wasted on a dead row — and (b) does not
        overrun a known arrival (or an admission-budget backlog) while a
        slot sits free.  The completion bound (a) is exact — wasted decode
        steps cost real device work; the latency bounds (b) are floored at
        ``decode_chunk / 4``: shortening chunks further only shaves a few
        steps of admission latency while multiplying host syncs.  The
        ladder costs at most log2(decode_chunk)+1 traces per mode
        (DESIGN.md Section 9).

        ``admitted_slots``: slots admitted *this tick* — their scheduler
        ``remaining`` still includes the prefill-boundary token (emitted
        from the chunk's sync, not by a decode step), so they owe the
        device one step fewer."""
        cap = self.decode_chunk
        if self.chunk_cap is not None:      # degradation level 1 (Sec. 13)
            cap = max(1, min(cap, self.chunk_cap))
        bound = min(self.sched.remaining[s] - (s in admitted_slots)
                    for s in self.sched.active)
        bound = max(1, bound)      # a lone max_new_tokens=1 admission still
        #                            runs the 1-step chunk its sync rides on
        if self.sched._free and self.sched.policy == "continuous":
            floor = max(1, cap // 4)
            if self.sched.deferred_ready():
                bound = min(bound, floor)
            else:
                na = self.sched.next_arrival()
                if na is not None:
                    bound = min(bound, max(floor, na - self.clock))
        c = 1
        while c * 2 <= cap and c * 2 <= bound:
            c *= 2
        return c

    def _prefill(self, req: Request):
        prefill_fn = self._fns()[0]
        bucket = self.bucket_for(req.prompt_len)
        with span("prefill", rid=req.rid, prompt_len=req.prompt_len,
                  bucket=bucket or req.prompt_len):
            batch = req.as_batch(bucket)
            with self._scope():
                cache1, logits = prefill_fn(self.params, batch)
        padded = batch["tokens"].shape[-1]
        self.prefill_buckets.add(padded)
        self.stats["prefill_calls"] += 1
        self.stats["prefill_tokens"] += req.prompt_len
        self.stats["prefill_padded_tokens"] += padded
        return cache1, logits

    def _admit(self) -> List[Tuple[int, Request]]:
        """This tick's admissions (after reclaiming dead slots' pages), each
        with a fresh ``RequestOutput`` stamped as admitted now."""
        with span("admit", waiting=self.sched.waiting_count,
                  free=len(self.sched._free)):
            self._poll_fault("admission")
            self._flush_dirty()
            admitted = self.sched.admissions(self.clock,
                                             gate=self._admission_gate())
            now = time.perf_counter()
            for _, req in admitted:
                self.outputs[req.rid] = RequestOutput(
                    req.rid, admitted=self.clock,
                    t_added=self._t_added.pop(req.rid, None), t_admitted=now)
        return admitted

    def _insert_slot(self, slot: int, req: Request, cache1, logits):
        """Dispatch the slot insert of a prefilled request; returns its
        (1,) first token, still on the device."""
        with span("insert", rid=req.rid, slot=slot):
            rem = jnp.asarray(req.max_new_tokens - 1, jnp.int32)
            args = (self.cache, self._tokens, self._remaining, cache1,
                    logits, jnp.asarray(slot, jnp.int32), rem)
            if self._paged is not None:
                ids = self._reserved_pages.pop(req.rid)
                self._slot_pages[slot] = ids
                args += (jnp.asarray(self._paged.page_row(ids)),)
            self.cache, self._tokens, self._remaining, tok = \
                self._insert(*args)
        return tok

    def _emit(self, slot: int, token: int) -> None:
        req = self.sched.running[slot]
        out = self.outputs[req.rid]
        if not out.tokens:
            out.t_first = time.perf_counter()
        out.tokens.append(token)
        out.token_steps.append(self.clock)
        self.events.append((self.clock, req.rid, token))
        self.stats["emitted"] += 1
        if self.sched.emit(slot):
            out.finished = self.clock
            if self._paged is not None:
                # pages stay owned (the slot may still see garbage decode
                # writes until the chunk ends) — reclaimed at the next
                # tick's _flush_dirty, before any admission can reuse them
                self._dirty_slots.add(slot)

    def cancel(self, rid: int) -> bool:
        """Withdraw a request — the router's hedge-loser / drain hook.
        A running request's slot is freed and its on-device ``remaining``
        zeroed (the live mask drops it, the chunk ladder stops waiting on
        it — the stale rows are the usual dead weight until the next
        admission); a waiting request just leaves the queues.  Call at
        tick boundaries only.  Returns False when ``rid`` is unknown or
        already finished."""
        for slot, req in sorted(self.sched.running.items()):
            if req.rid == rid:
                self._remaining = self._remaining.at[slot].set(0)
                self.sched.cancel_slot(slot)
                if self._paged is not None:
                    self._dirty_slots.add(slot)
                return True
        self._t_added.pop(rid, None)
        return self.sched.remove_waiting(rid)

    @property
    def load(self) -> int:
        """Requests this engine currently owns (running + queued) — the
        router's deterministic least-loaded dispatch signal."""
        return len(self.sched.running) + self.sched.waiting_count

    def step(self) -> List[Tuple[int, int, int]]:
        """One engine tick: admissions (each prefilled at its bucketed
        length and written into its slot with first token + owed-token
        counter seeded on device) followed by one fused ``decode_chunk``-
        step scan advancing every running slot.  The single host sync per
        tick fetches the (chunk, B) token ring, the admissions' first
        tokens, and the measurement scalars together; the ring is then
        drained against the scheduler, the clock advancing one step per
        executed chunk row.  Returns the tick's (step, rid, token) events.

        Slots freed mid-chunk idle until the next tick, and newly arrived
        requests wait for the chunk boundary — admission latency is bounded
        by ``decode_chunk`` steps (DESIGN.md Section 9, though the
        chunk-length ladder caps chunks at known completions/arrivals so
        neither happens on predictable traces).

        While recovery is armed (a ``FaultInjector``, a
        ``StragglerDetector``, or a ``snapshot_dir``), the tick starts by
        capturing a host-side snapshot; a ``DeviceLoss`` detected anywhere
        inside the tick rolls back to it, remeshes onto the survivors, and
        replays the tick — deterministically, so the finished trace is
        token-identical to an uninterrupted run (DESIGN.md Section 11).
        """
        t0 = time.perf_counter()
        with span("tick", clock=self.clock, mode=self.mode.value):
            if self._recovery_armed():
                self._snapshot = self._capture()
            impl = self._step_fused if self.fused else self._step_stepwise
            try:
                events = impl()
            except DeviceLoss as loss:
                self._recover(list(loss.lost), self._snapshot)
                events = impl()
            self._observe_hosts(time.perf_counter() - t0)
        return events

    def _step_fused(self) -> List[Tuple[int, int, int]]:
        ev_start = len(self.events)
        pending: List[Tuple[int, int, jax.Array]] = []  # slot, rid, dev tok
        for slot, req in self._admit():
            cache1, logits = self._prefill(req)
            self._poll_fault("prefill")
            pending.append((slot, req.rid,
                            self._insert_slot(slot, req, cache1, logits)))
        admitted = frozenset(s for s, _, _ in pending)
        if self.sched.active and all(
                self.sched.remaining[s] - (s in admitted) <= 0
                for s in self.sched.active):
            # pure-admission tick: every live slot is a fresh single-token
            # request — nothing owes a decode step, so fetch the prefill
            # tokens without dispatching a dead chunk
            with span("sync"):
                first_toks = jax.device_get([t for _, _, t in pending])
            self.stats["host_syncs"] += 1
            with span("emit", tokens=len(pending)):
                for (slot, rid, _), tok in zip(pending, first_toks):
                    self._emit(slot, int(tok[0]))
                self.clock += 1
        elif self.sched.active:
            with span("chunk") as sp:
                chunk = self._chunk_len(admitted)
                sp.set_metadata(chunk=chunk, live=len(self.sched.running))
                chunk_fn = self._fns()[2](chunk)
                with self._scope():
                    (self.cache, self._tokens, self._remaining, ring,
                     zf_num, zf_den, kv) = chunk_fn(self.params, self.cache,
                                                    self._tokens,
                                                    self._remaining)
                self._poll_fault("decode")
            with span("sync"):
                ring, first_toks, zf_num, zf_den, kv = jax.device_get(
                    (ring, [t for _, _, t in pending], zf_num, zf_den, kv))
            self.stats["host_syncs"] += 1
            with span("emit") as sp:
                self.stats["chunk_calls"] += 1
                self.stats["decode_steps"] += chunk
                self.stats["live_rows"] += int(zf_den)
                self.stats["kv_blocks_read"] += int(kv[0])
                self.stats["kv_blocks_arena"] += int(kv[1])
                # prefill-boundary emissions first: the chunk consumed these
                # tokens as its first feedback, so they precede the ring rows
                for (slot, rid, _), tok in zip(pending, first_toks):
                    self._emit(slot, int(tok[0]))
                for t in range(chunk):
                    live = self.sched.active
                    if not live:
                        break
                    for slot in live:
                        self._emit(slot, int(ring[t, slot]))
                    self.clock += 1
                self._since_measure += chunk
                if zf_den > 0 and self._since_measure >= self.measure_every:
                    self._measure(float(zf_num) / float(zf_den))
                sp.set_metadata(tokens=len(self.events) - ev_start,
                                kv_blocks_read=int(kv[0]),
                                kv_blocks_arena=int(kv[1]))
        else:
            self.clock += 1
        return self.events[ev_start:]

    def _step_stepwise(self) -> List[Tuple[int, int, int]]:
        """The PR 3 per-step hot path (``fused=False``): one pooled decode
        dispatch, argmax and ``np.asarray`` sync per token, measurement
        gathering the live rows of the full (B, vocab) logits.  Kept as the
        benchmark baseline (bench_serve.py times the fused scan against it)
        and as a behavioural reference — token output is identical to the
        fused path by construction."""
        ev_start = len(self.events)
        for slot, req in self._admit():
            cache1, logits = self._prefill(req)
            self._poll_fault("prefill")
            tok = self._insert_slot(slot, req, cache1, logits)
            self.stats["host_syncs"] += 1
            self._emit(slot, int(tok[0]))
        active = self.sched.active
        if active:
            decode_fn = self._fns()[1]
            with self._scope():
                logits, self.cache = decode_fn(self.params, self.cache,
                                               self._tokens)
            self._poll_fault("decode")
            toks = jnp.argmax(logits, -1).astype(jnp.int32)    # (B,)
            self._tokens = toks[:, None]
            host = np.asarray(toks)
            self.stats["host_syncs"] += 1
            self.stats["decode_steps"] += 1
            self.stats["live_rows"] += len(active)
            self._since_measure += 1
            if self._since_measure >= self.measure_every:
                self._measure(float(sparsity_of(
                    logits[jnp.asarray(active)])))
                self.stats["host_syncs"] += 1
            for slot in active:
                self._emit(slot, int(host[slot]))
        self.clock += 1
        return self.events[ev_start:]

    # -- failure handling (DESIGN.md Section 11) ----------------------------

    def _recovery_armed(self) -> bool:
        return (self.faults is not None or self.straggler is not None
                or self.snapshot_dir is not None)

    def _poll_fault(self, phase: str) -> None:
        if self.faults is not None:
            self.faults.poll(phase, self.clock)

    def _capture(self) -> EngineSnapshot:
        """Consistent host-side snapshot of the tick-mutable state — one
        extra device_get per tick while recovery is armed, the price of
        rollback consistency (DESIGN.md Section 11).  With a
        ``snapshot_dir`` the device state (plus the compacted params and
        the scheduler queues) also goes to disk through
        ``checkpoint.save``, so recovery — or a fresh process — can restore
        through ``checkpoint.restore`` onto any mesh's shardings."""
        device = jax.device_get({"cache": self.cache,
                                 "tokens": self._tokens,
                                 "remaining": self._remaining})
        snap = EngineSnapshot(
            device=device, sched=copy.deepcopy(self.sched),
            outputs=copy.deepcopy(self.outputs),
            events_len=len(self.events), clock=self.clock, mode=self.mode,
            a_measured=self.a_measured, since_measure=self._since_measure,
            mode_history=list(self.mode_history), stats=dict(self.stats),
            prefill_buckets=set(self.prefill_buckets),
            t_added=dict(self._t_added),
            paging=(self._paging_state() if self._paged is not None
                    else None))
        if self.snapshot_dir is not None:
            extra = {"scheduler": self.sched.state_dict(),
                     "clock": self.clock, "mode": self.mode.value}
            if snap.paging is not None:
                extra["paging"] = snap.paging
            ckpt_save(self.snapshot_dir, self.clock,
                      dict(device, params=self._params_host), keep=2,
                      extra=extra)
            snap.ckpt_step = self.clock
        return snap

    def _recover(self, lost: List[int], snap: Optional[EngineSnapshot]) -> None:
        """Device loss detected (an injected/real ``DeviceLoss`` mid-tick,
        or a straggler eviction at a tick boundary): remesh onto the
        survivors, roll every host structure back to ``snap``, and rebuild
        the device state from it on the new mesh.  The caller then replays
        from the snapshot's clock; replay is deterministic and the sharded
        layouts are reduction-order-preserving (DESIGN.md Section 10), so
        the finished trace is token-identical to an uninterrupted run."""
        if snap is None:
            raise RuntimeError("device loss with no snapshot armed")
        self._remesh(lost)
        self.sched = copy.deepcopy(snap.sched)
        self.outputs = copy.deepcopy(snap.outputs)
        del self.events[snap.events_len:]
        self.clock = snap.clock
        self.mode = snap.mode
        self.a_measured = snap.a_measured
        self._since_measure = snap.since_measure
        self.mode_history = list(snap.mode_history)
        self.stats = dict(snap.stats)
        self.prefill_buckets = set(snap.prefill_buckets)
        self._t_added = dict(snap.t_added)
        if self._paged is not None:
            if snap.paging is None:
                raise RuntimeError("paged engine snapshot lacks paging state")
            self._restore_paging(snap.paging)
        self._restore_device(snap)
        self.recoveries += 1
        self.recovery_log.append({"step": snap.clock, "lost": sorted(lost),
                                  "mesh": self._mesh_desc()})

    def _remesh(self, lost: List[int]) -> None:
        """A single-device engine has no mesh to shrink: recovery is a
        restart in place (the snapshot rebuilds the device state, the jits
        stay valid).  The mesh engine overrides this with plan_mesh on the
        survivors plus a sharding-spec / Mode-keyed-jit rebuild."""

    def _mesh_desc(self) -> str:
        return "unsharded"

    def _host_device_ids(self, host: int) -> List[int]:
        """Device ids owned by straggler host ``host`` — the single-device
        engine has one host and nothing to evict onto, so evictions only
        land in the recovery log.  The mesh engine maps hosts to data-rows
        of its device array."""
        return []

    def _snapshot_state(self, snap: EngineSnapshot, shardings: Optional[Any]):
        """The snapshot's device-state tree, from disk (through
        ``checkpoint.restore``, placing onto ``shardings``) when the
        snapshot was checkpointed, else from the in-memory copy (placement
        left to the caller)."""
        if snap.ckpt_step is None:
            return dict(snap.device)
        state = dict(snap.device)
        if self._params_host is not None:
            state["params"] = self._params_host
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
            state)
        return ckpt_restore(self.snapshot_dir, template, step=snap.ckpt_step,
                            shardings=shardings)

    def _restore_device(self, snap: EngineSnapshot) -> None:
        state = self._snapshot_state(snap, shardings=None)
        self.cache = jax.tree.map(jnp.asarray, state["cache"])
        self._tokens = jnp.asarray(state["tokens"])
        self._remaining = jnp.asarray(state["remaining"])

    def _observe_hosts(self, dt: float) -> None:
        """Feed per-host step timings to the ``StragglerDetector`` (the
        injector's ``delay_host`` inflates one host's reading — a simulated
        persistent straggler) and route its eviction verdict into the same
        snapshot → remesh → reshard path as a detected device loss.  Runs
        at the tick boundary, where the state is already consistent: the
        recovery snapshot is captured on the spot and nothing is replayed."""
        if self.straggler is None:
            return
        for h in range(self.straggler.num_hosts):
            f = (self.faults.host_delay(h, self.clock)
                 if self.faults is not None else 1.0)
            self.straggler.record(h, dt * f)
        self.straggler.observe()
        evict = [h for h in self.straggler.evictions()
                 if h not in self._evicted]
        if not evict:
            return
        self._evicted.update(evict)
        lost = sorted({d for h in evict for d in self._host_device_ids(h)})
        if not lost or not self._survivors_exist(lost):
            self.recovery_log.append({"step": self.clock, "evicted": evict,
                                      "lost": [], "mesh": self._mesh_desc()})
            return
        self._recover(lost, self._capture())

    def _survivors_exist(self, lost: List[int]) -> bool:
        return True     # mesh engine checks against its device array

    def run(self, requests: Sequence[Request] = (),
            max_steps: Optional[int] = None) -> Dict[int, RequestOutput]:
        """Drain: add ``requests``, tick until every request finished (or
        ``max_steps``), return rid -> RequestOutput."""
        for r in requests:
            self.add(r)
        steps = 0
        while self.sched.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.outputs


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------

def synthetic_trace(cfg, *, num_requests: int, seed: int = 0,
                    prompt_lens: Sequence[int] = (8, 16, 24),
                    gen_lens: Sequence[int] = (4, 8, 16),
                    arrival_every: int = 0,
                    arrival_process: str = "fixed",
                    rate: float = 0.5, burst_rate: float = 4.0,
                    burst_switch: float = 0.15,
                    length_dist: str = "choice",
                    heavy_alpha: float = 1.6,
                    max_gen: Optional[int] = None,
                    priorities: Sequence[int] = (0,),
                    deadline_slack: Optional[float] = None,
                    ttft_deadline: Optional[int] = None) -> List[Request]:
    """Deterministic mixed prompt/gen-length request trace — the
    benchmarks/bench_serve.py workload.

    Arrival processes (all seeded, so routing decisions replay exactly):
    ``"fixed"`` staggers arrivals (request i at step i * arrival_every —
    the pre-router behaviour, and the default); ``"bursty"`` is a
    two-state Markov-modulated process — each request flips the
    calm/burst state with probability ``burst_switch``, then advances
    the arrival clock by an exponential gap at the state's rate
    (``rate`` / ``burst_rate`` requests per step) — the heavy-tailed
    overload workload of DESIGN.md Section 13.

    ``length_dist="heavy"`` replaces the uniform gen-length choice with
    a Pareto draw (shape ``heavy_alpha``) floored at ``min(gen_lens)``
    and capped at ``max_gen`` (default ``8 * max(gen_lens)``) — most
    requests stay short, stragglers dominate the tail.

    SLO fields: ``priorities`` draws each request's priority class,
    ``deadline_slack`` attaches a completion deadline proportional to
    the request's own expected service (slack x (gen + prefill share)),
    and ``ttft_deadline`` a flat first-token deadline.  The defaults
    attach nothing, keeping the trace FCFS-compatible.
    """
    if arrival_process not in ("fixed", "bursty"):
        raise ValueError(f"unknown arrival process {arrival_process!r}")
    if length_dist not in ("choice", "heavy"):
        raise ValueError(f"unknown length distribution {length_dist!r}")
    rng = np.random.default_rng(seed)
    reqs: List[Request] = []
    t, burst = 0, False
    for i in range(num_requests):
        plen = int(rng.choice(np.asarray(prompt_lens)))
        if length_dist == "heavy":
            gmin = int(min(gen_lens))
            cap = int(max_gen) if max_gen else 8 * int(max(gen_lens))
            glen = min(cap, max(1, int(gmin * (1.0
                                               + rng.pareto(heavy_alpha)))))
        else:
            glen = int(rng.choice(np.asarray(gen_lens)))
        toks = rng.integers(1, cfg.vocab_size, (plen,), dtype=np.int32)
        extras = None
        if cfg.is_encdec:
            extras = {"frames": rng.standard_normal(
                (cfg.enc_frames, cfg.d_model)).astype(np.float32)}
        if arrival_process == "bursty":
            if rng.random() < burst_switch:
                burst = not burst
            r = burst_rate if burst else rate
            t += int(round(rng.exponential(1.0 / max(r, 1e-6))))
            arrival = t
        else:
            arrival = i * arrival_every
        priority = (int(rng.choice(np.asarray(priorities)))
                    if len(priorities) > 1 else int(priorities[0]))
        deadline = None
        if deadline_slack is not None:
            deadline = int(np.ceil(deadline_slack
                                   * (glen + max(1, plen // 8))))
        reqs.append(Request(rid=i, tokens=toks, max_new_tokens=glen,
                            arrival=arrival, extras=extras,
                            priority=priority, deadline_ms=deadline,
                            ttft_deadline_ms=ttft_deadline))
    return reqs
