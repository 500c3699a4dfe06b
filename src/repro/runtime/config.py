"""Unified engine configuration (DESIGN.md Section 14).

Nine PRs of growth left the engines with sprawling constructors (a 20-kwarg
``ServeEngine.__init__``, a near-duplicate ``MeshServeEngine`` signature and
33 CLI flags in launch/serve.py).  ``EngineConfig`` is the one home for all
of it: a frozen dataclass of frozen sections —

* ``ArenaConfig``  — KV arena: slots, cache_len, paging (page_size/num_pages)
  and KV dtype (``"fp32"`` | ``"int8"``, runtime/paging.py);
* ``SchedConfig``  — admission policy, fused-chunk ladder, bucketed prefill;
* ``KernelConfig`` — Pallas kernel dispatch knobs;
* ``FaultConfig``  — snapshots, fault-injection spec, straggler eviction;
* ``RouterConfig`` — multi-replica routing (replicas, queue bound, hedging).

``ServeEngine(api, params, config=EngineConfig(...))`` is the documented
construction path; the old keyword arguments still work for one release
through a deprecation shim (``resolve_engine_config`` maps them onto the
nested fields and warns).  ``to_json``/``from_json`` round-trip the whole
config, powering ``launch/serve.py --config engine.json`` (explicit CLI
flags override file values).  ``derive_cache_len`` is the single source of
truth for the trace-driven arena bound that used to be duplicated between
``build_engine`` and ``main()``.
"""
from __future__ import annotations

import dataclasses
import json
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class ArenaConfig:
    """KV arena shape.  ``page_size=None`` keeps the fixed
    ``num_slots x cache_len`` arena; setting it (power of two) activates the
    paged pool of runtime/paging.py — ``num_pages`` physical pages shared by
    all slots (default: fixed-arena capacity + the DUMP page), ``kv_dtype``
    selecting fp32 (bit-exact) or int8 (per-row scales, gated tolerance)
    pages.  ``cache_len=None`` means "derive from the trace" via
    :meth:`EngineConfig.derive_cache_len`."""

    num_slots: int = 4
    cache_len: Optional[int] = None
    page_size: Optional[int] = None
    num_pages: Optional[int] = None
    kv_dtype: str = "fp32"


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    policy: str = "continuous"
    max_admissions_per_step: int = 1
    decode_chunk: int = 8
    measure_every: int = 8
    bucket_prompts: bool = True


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    use_kernels: bool = False
    interpret: bool = False
    spmd_kernels: bool = True
    a_sparsity: Optional[float] = None
    block_m: int = 128


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    inject: Optional[str] = None        # fault spec string (runtime.fault)
    snapshot_dir: Optional[str] = None
    recovery_model_parallel: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    replicas: int = 0                   # 0 = plain single-engine serving
    queue_bound: Optional[int] = None
    hedge_after: Optional[int] = None
    shed_policy: str = "shed"


_SECTIONS = {"arena": ArenaConfig, "sched": SchedConfig,
             "kernels": KernelConfig, "fault": FaultConfig,
             "router": RouterConfig}

# legacy ServeEngine/MeshServeEngine keyword -> (section, field)
_LEGACY = {
    "num_slots": ("arena", "num_slots"),
    "cache_len": ("arena", "cache_len"),
    "page_size": ("arena", "page_size"),
    "num_pages": ("arena", "num_pages"),
    "kv_dtype": ("arena", "kv_dtype"),
    "policy": ("sched", "policy"),
    "max_admissions_per_step": ("sched", "max_admissions_per_step"),
    "decode_chunk": ("sched", "decode_chunk"),
    "measure_every": ("sched", "measure_every"),
    "bucket_prompts": ("sched", "bucket_prompts"),
    "use_kernels": ("kernels", "use_kernels"),
    "interpret": ("kernels", "interpret"),
    "spmd_kernels": ("kernels", "spmd_kernels"),
    "a_sparsity": ("kernels", "a_sparsity"),
    "block_m": ("kernels", "block_m"),
    "snapshot_dir": ("fault", "snapshot_dir"),
    "recovery_model_parallel": ("fault", "recovery_model_parallel"),
}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    arena: ArenaConfig = dataclasses.field(default_factory=ArenaConfig)
    sched: SchedConfig = dataclasses.field(default_factory=SchedConfig)
    kernels: KernelConfig = dataclasses.field(default_factory=KernelConfig)
    fault: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    router: RouterConfig = dataclasses.field(default_factory=RouterConfig)
    mesh: Optional[str] = None          # "DxM" data x model mesh, None = off

    # -- construction helpers ----------------------------------------------

    def replace(self, **sections: Any) -> "EngineConfig":
        return dataclasses.replace(self, **sections)

    def with_fields(self, **kv: Any) -> "EngineConfig":
        """Functional update by flat field name (``num_slots=8``,
        ``kv_dtype="int8"``, ``mesh="2x2"``): each key is routed to its
        section via the same map the legacy-kwarg shim uses."""
        out = self
        for key, val in kv.items():
            if key == "mesh":
                out = dataclasses.replace(out, mesh=val)
                continue
            if key not in _LEGACY:
                raise TypeError(f"unknown engine config field {key!r}")
            section, field = _LEGACY[key]
            sec = dataclasses.replace(getattr(out, section), **{field: val})
            out = dataclasses.replace(out, **{section: sec})
        return out

    @staticmethod
    def heavy_gen_cap(gen_lens: Sequence[int]) -> int:
        """Generation cap for ``length_dist="heavy"`` traces: the Pareto
        draw is capped at 2x the largest nominal gen length, so the arena
        bound stays finite.  Shared by :meth:`derive_cache_len` and the
        trace construction in launch/serve.py — one definition, no drift."""
        return 2 * max(gen_lens)

    @classmethod
    def derive_cache_len(cls, prompt_lens: Sequence[int],
                         gen_lens: Sequence[int],
                         length_dist: str = "choice") -> int:
        """The trace-driven arena bound: longest prompt + the generation
        cap + 1 feedback token.  Single source of truth for what
        ``build_engine`` and ``main()`` in launch/serve.py used to compute
        independently (with a hand-maintained heavy-tail 2x special case
        that had to match)."""
        gen_cap = (cls.heavy_gen_cap(gen_lens) if length_dist == "heavy"
                   else max(gen_lens))
        return max(prompt_lens) + gen_cap + 1

    @classmethod
    def from_args(cls, args: Any, defaults: Optional[Dict[str, Any]] = None
                  ) -> "EngineConfig":
        """EngineConfig from launch/serve.py's argparse namespace.

        ``--config <json>`` (when present on ``args``) sets the baseline;
        every CLI flag whose value differs from its parser default
        (``defaults``, a dest -> default map) is laid on top.  argparse
        cannot distinguish "absent" from "passed the default", so a flag
        explicitly set *to* its default never clobbers a file value — the
        documented override rule.  With ``defaults=None`` every present
        flag counts as explicit."""
        path = getattr(args, "config", None)
        if path:
            with open(path) as f:
                base = cls.from_json(f.read())
        else:
            base = cls()

        def explicit(dest: str) -> bool:
            if not hasattr(args, dest):
                return False
            if defaults is None or dest not in defaults:
                return True
            return getattr(args, dest) != defaults[dest]

        flat = {"slots": "num_slots", "cache_len": "cache_len",
                "page_size": "page_size", "num_pages": "num_pages",
                "kv_dtype": "kv_dtype", "policy": "policy",
                "measure_every": "measure_every",
                "decode_chunk": "decode_chunk", "use_kernels": "use_kernels",
                "snapshot_dir": "snapshot_dir",
                "remesh_model_parallel": "recovery_model_parallel",
                "mesh": "mesh"}
        kv = {field: getattr(args, dest) for dest, field in flat.items()
              if explicit(dest)}
        out = base.with_fields(**kv) if kv else base
        if explicit("spmd_fallback"):
            out = out.replace(kernels=dataclasses.replace(
                out.kernels, spmd_kernels=not args.spmd_fallback))
        if explicit("inject_fault"):
            out = out.replace(fault=dataclasses.replace(
                out.fault, inject=args.inject_fault))
        router: Dict[str, Any] = {}
        if explicit("replicas"):
            router["replicas"] = args.replicas
        if explicit("queue_bound"):
            router["queue_bound"] = args.queue_bound or None
        if explicit("hedge_ms"):
            router["hedge_after"] = args.hedge_ms or None
        if explicit("shed_policy"):
            router["shed_policy"] = args.shed_policy
        if router:
            out = out.replace(router=dataclasses.replace(out.router,
                                                         **router))
        return out

    # -- json round-trip ----------------------------------------------------

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "EngineConfig":
        raw = json.loads(text)
        if not isinstance(raw, dict):
            raise ValueError("engine config json must be an object")
        kw: Dict[str, Any] = {}
        for name, val in raw.items():
            if name == "mesh":
                kw["mesh"] = val
            elif name in _SECTIONS:
                sec_cls = _SECTIONS[name]
                fields = {f.name for f in dataclasses.fields(sec_cls)}
                unknown = set(val) - fields
                if unknown:
                    raise ValueError(f"unknown {name} config fields: "
                                     f"{sorted(unknown)}")
                kw[name] = sec_cls(**val)
            else:
                raise ValueError(f"unknown engine config section {name!r}")
        return cls(**kw)


def resolve_engine_config(config: Optional[EngineConfig],
                          legacy: Dict[str, Any], owner: str
                          ) -> EngineConfig:
    """The engines' deprecation shim: merge old-style keyword arguments
    into ``config`` (legacy values win — they are the more explicit call),
    warning once per construction.  Unknown keywords raise ``TypeError``
    exactly as the old signatures did."""
    cfg = config or EngineConfig()
    if legacy:
        unknown = set(legacy) - set(_LEGACY)
        if unknown:
            raise TypeError(f"{owner} got unexpected keyword arguments "
                            f"{sorted(unknown)}")
        warnings.warn(
            f"{owner}(**kwargs) is deprecated; pass "
            f"config=EngineConfig(...) (keywords {sorted(legacy)} were "
            "mapped onto it)", DeprecationWarning, stacklevel=3)
        cfg = cfg.with_fields(**legacy)
    return cfg
