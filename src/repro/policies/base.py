"""Policy interfaces, registry, and the :class:`PolicySet` compile contract.

The paper's central contribution is a *comparison of policies* — DRAM-cache
prefetching (§III), memory-node scheduling (§IV-A), compute-node rate
adaptation (§IV-B) — and the reproduction makes each of the four decision
points a first-class, pluggable module:

* :class:`PrefetchPolicy`     — DRAM-cache prefetcher (state / train / predict);
* :class:`SchedulerPolicy`    — FAM-controller issue arbitration;
* :class:`ReplacementPolicy`  — victim selection inside the DRAM cache;
* :class:`AdaptationPolicy`   — compute-node prefetch rate control.

Implementations are registered **by name** (:func:`register` /
:func:`get_policy`) and selected through a :class:`PolicySet` — a frozen,
hashable value object the experiment planner treats exactly like a static
shape parameter.

The static/dynamic contract
---------------------------
Each policy splits into two halves, mirroring ``FamConfig`` vs
``FamParams``:

* its **choice** is static: :meth:`PolicySet.compile_tags` feeds the
  planner's compile key, so switching to a policy with a different traced
  program recompiles (and plans into its own group);
* its **numeric parameters** are dynamic: :meth:`~PolicySet.numeric_params`
  builds a per-policy pytree of traced scalars that rides on
  ``FamParams.policy`` — a WFQ weight, an SPP confidence threshold, or a
  static issue rate sweeps *without* recompiling, like any other
  ``FamParams`` scalar.

Policies engineered to share one traced program share one ``compile_tag``
(e.g. ``fifo`` and ``wfq`` both tag ``scheduler:chain``: the fused
service-chain kernel evaluates both disciplines and selects per element,
which is what lets a FIFO baseline and its WFQ variants share a compile
group — the paper's Fig. 12/16 pattern). Same tag MUST mean same traced
step code; only ``params_of`` may differ between same-tag policies.

``SimFlags`` lives here too (re-exported from ``repro.core.famsim`` for
compatibility): the legacy boolean surface is now a *shim* over the policy
layer — :meth:`PolicySet.from_flags` maps ``wfq=True`` to the ``wfq``
scheduler policy (with the flag weight as a numeric-param override) while
the remaining booleans stay dynamic ``FamParams`` feature gates.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import (Any, Dict, Mapping, NamedTuple, Optional, Protocol,
                    Tuple, runtime_checkable)

import numpy as np

POLICY_KINDS = ("prefetch", "scheduler", "replacement", "adaptation")


# ---------------------------------------------------------------------------
# Legacy boolean surface (deprecation shim target — see PolicySet.from_flags)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SimFlags:
    """Feature toggles of the original simulator API.

    Kept working as a shim: ``core_prefetch`` / ``dram_prefetch`` /
    ``bw_adapt`` / ``all_local`` remain dynamic ``FamParams`` gates (a
    baseline and its variants share one compile), while ``wfq`` /
    ``wfq_weight`` now *select the scheduler policy* through
    :meth:`PolicySet.from_flags`. New code should pass a
    :class:`PolicySet` instead of spelling scheduler choice as a boolean.
    """

    core_prefetch: bool = True
    dram_prefetch: bool = True
    bw_adapt: bool = False
    wfq: bool = False
    wfq_weight: int = 2
    all_local: bool = False


# ---------------------------------------------------------------------------
# The four policy interfaces
# ---------------------------------------------------------------------------

@runtime_checkable
class Policy(Protocol):
    """Common surface every policy implementation exposes."""

    kind: str          # one of POLICY_KINDS
    name: str          # registry key
    compile_tag: str   # static identity entering the compile key

    def params_of(self, cfg) -> Dict[str, Any]:
        """Declarative numeric-param pytree (name -> numpy scalar), sourced
        from ``FamConfig`` defaults; every leaf is traced at run time."""
        ...


class PrefetchPolicy(Policy, Protocol):
    """DRAM-cache prefetcher: functional state + train + predict."""

    def init(self, cfg):
        """Fresh per-node state pytree (fixed shapes from ``cfg``)."""
        ...

    def train(self, cfg, pol, state, page, block, enable):
        """Observe one FAM-bound access. Returns ``(state, ctx)`` where
        ``ctx`` is whatever predict needs from this access (e.g. the SPP
        signature). ``enable`` masks every write."""
        ...

    def predict(self, cfg, pol, state, page, block, ctx, degree, bpp):
        """Candidate blocks after the access: ``(gblocks (degree,),
        valid (degree,))`` — global block addresses, in-page (``bpp``
        blocks per page, possibly traced)."""
        ...


class SchedulerPolicy(Policy, Protocol):
    """FAM-controller issue arbitration (one step's arrivals)."""

    def arbitrate(self, p, pol, busy0, d_arr, d_valid, d_bytes,
                  p_arr, p_valid, p_bytes):
        """Time the step's demand + prefetch arrivals through the DDR
        service model. Returns ``repro.core.fam_controller.FamTimings``."""
        ...

    def backlog_ok(self, p, pol, fam_busy, clock):
        """Per-node gate: may this node issue NEW prefetches given the
        controller-side prefetch backlog? (CXL backpressure model.)"""
        ...


class ReplacementPolicy(Policy, Protocol):
    """Victim selection inside the DRAM cache.

    ``bind(pol)`` closes the traced numeric params over a small object the
    cache ops consume — or returns ``None`` to select the classic in-place
    LRU fast path in ``repro.core.dram_cache`` (the bit-exact default).
    The bound object provides ``on_hit(old, stamp)``,
    ``evict(row_lru, wmask, stamp, set_idx, eff_ways) -> (aged_row, way)``
    and ``insert_value(stamp)``.
    """

    def bind(self, pol):
        ...


class AdaptationPolicy(Policy, Protocol):
    """Compute-node prefetch rate control (issue enforcement + adaptation)."""

    def gate(self, p):
        """Traced activation gate: when False, ``take`` grants everything
        and ``adapt`` is a no-op. The token bucket keeps the legacy
        ``bw_adapt`` feature flag here (the paper's with/without
        comparison under one compile); an explicitly chosen baseline like
        ``static`` returns True unconditionally."""
        ...

    def init(self, p, pol):
        """Fresh controller state (a ``ThrottleState``-shaped pytree whose
        ``issue_rate`` leaf feeds the figure metrics)."""
        ...

    def take(self, p, pol, state, want, enable):
        """Grant up to ``want`` prefetch issues. Returns (state, grant)."""
        ...

    def observe(self, p, pol, state, demand_latency, is_fam_demand,
                was_pf_hit, pf_issued_now, enable):
        ...

    def adapt(self, p, pol, state, enable):
        ...


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Dict[str, Any]] = {k: {} for k in POLICY_KINDS}


def register(policy):
    """Register a policy instance under ``(policy.kind, policy.name)``.

    Usable as a plain call or a class-instance decorator; returns the
    policy so modules can do ``SPP = register(SppPrefetch())``.
    """
    if policy.kind not in _REGISTRY:
        raise ValueError(f"unknown policy kind {policy.kind!r} "
                         f"(kinds: {POLICY_KINDS})")
    _REGISTRY[policy.kind][policy.name] = policy
    return policy


def get_policy(kind: str, name: str):
    try:
        return _REGISTRY[kind][name]
    except KeyError:
        raise KeyError(
            f"no {kind!r} policy named {name!r}; available: "
            f"{available(kind)}") from None


def available(kind: str) -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY[kind]))


#: param_schema cache: ``(kind, policy name) -> sorted param names`` —
#: ``params_of`` keys are config-independent, so one probe per policy
_SCHEMA_CACHE: Dict[Tuple[str, str], Tuple[str, ...]] = {}


class ResolvedPolicies(NamedTuple):
    """The four implementation objects a :class:`PolicySet` names."""

    prefetch: Any
    scheduler: Any
    replacement: Any
    adaptation: Any


# ---------------------------------------------------------------------------
# PolicySet
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PolicySet:
    """One named policy per decision point + numeric-param overrides.

    Frozen and hashable (overrides are nested tuples), so it can ride on
    ``ResolvedPoint``, key executor caches, and serve as a dataclass
    default. ``overrides`` maps a kind to ``(param, value)`` pairs applied
    over the policy's ``params_of(cfg)`` defaults — overriding a *value*
    never changes the compile key; choosing a different *policy* does
    (unless the two share a ``compile_tag``).
    """

    prefetch: str = "spp"
    scheduler: str = "fifo"
    replacement: str = "lru"
    adaptation: str = "token_bucket"
    overrides: Tuple[Tuple[str, Tuple[Tuple[str, float], ...]], ...] = ()

    def impl(self, kind: str):
        return get_policy(kind, getattr(self, kind))

    def impls(self) -> ResolvedPolicies:
        return ResolvedPolicies(*(self.impl(k) for k in POLICY_KINDS))

    def compile_tags(self) -> Tuple[str, ...]:
        """The static compile-key contribution: one tag per kind."""
        return tuple(self.impl(k).compile_tag for k in POLICY_KINDS)

    def numeric_params(self, cfg) -> Dict[str, Dict[str, Any]]:
        """The per-policy traced-scalar pytree carried on
        ``FamParams.policy``: ``{kind: {param: numpy scalar}}``, defaults
        from each policy's ``params_of(cfg)`` with ``overrides`` applied
        (cast to the default leaf's dtype)."""
        ov = dict((k, dict(v)) for k, v in self.overrides)
        out: Dict[str, Dict[str, Any]] = {}
        for kind in POLICY_KINDS:
            params = dict(self.impl(kind).params_of(cfg))
            for name, value in ov.pop(kind, {}).items():
                if name not in params:
                    raise ValueError(
                        f"{kind} policy {getattr(self, kind)!r} has no "
                        f"numeric param {name!r}; schema: "
                        f"{sorted(params)}")
                params[name] = np.asarray(value, params[name].dtype)
            out[kind] = params
        if ov:
            raise ValueError(f"overrides for unknown policy kinds: "
                             f"{sorted(ov)} (kinds: {POLICY_KINDS})")
        return out

    def param_schema(self, kind: str) -> Tuple[str, ...]:
        """The valid numeric-param names of ``kind``'s chosen policy —
        the keys of ``params_of`` (config-independent), cached per
        policy."""
        if kind not in POLICY_KINDS:
            raise ValueError(f"unknown policy kind {kind!r} "
                             f"(kinds: {POLICY_KINDS})")
        impl = self.impl(kind)
        cached = _SCHEMA_CACHE.get((kind, impl.name))
        if cached is None:
            from repro.configs.base import FamConfig
            cached = tuple(sorted(impl.params_of(FamConfig())))
            _SCHEMA_CACHE[(kind, impl.name)] = cached
        return cached

    def override(self, kind: str, **values) -> "PolicySet":
        """A copy with ``values`` merged into ``kind``'s param overrides.

        Param names validate EAGERLY against the chosen policy's
        ``params_of`` schema — a typo'd knob raises here, at the call
        site, instead of silently riding along as an inert dimension
        until ``numeric_params`` (or never, for a caller that only
        serializes the set)."""
        if kind not in POLICY_KINDS:
            raise ValueError(f"unknown policy kind {kind!r}")
        schema = self.param_schema(kind)
        bad = sorted(set(values) - set(schema))
        if bad:
            raise ValueError(
                f"{kind} policy {getattr(self, kind)!r} has no numeric "
                f"param(s) {bad}; valid params: {list(schema)}")
        merged = dict((k, dict(v)) for k, v in self.overrides)
        merged.setdefault(kind, {}).update(values)
        canon = tuple(sorted(
            (k, tuple(sorted(v.items()))) for k, v in merged.items() if v))
        return replace(self, overrides=canon)

    def as_dict(self) -> Dict[str, Any]:
        """JSON-able serialization: the four choice names + overrides as
        nested dicts. Round-trips through :meth:`from_dict` — the search
        layer's candidate/`best.json` format."""
        return {
            **{k: getattr(self, k) for k in POLICY_KINDS},
            "overrides": {k: dict(v) for k, v in self.overrides},
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "PolicySet":
        """Inverse of :meth:`as_dict` (override params re-validate
        against the chosen policies' schemas on the way in)."""
        unknown = set(d) - set(POLICY_KINDS) - {"overrides"}
        if unknown:
            raise ValueError(f"PolicySet.from_dict: unknown keys "
                             f"{sorted(unknown)}")
        ps = cls(**{k: str(d[k]) for k in POLICY_KINDS if k in d})
        for kind, params in dict(d.get("overrides", {})).items():
            ps = ps.override(kind, **params)
        return ps

    @classmethod
    def from_flags(cls, flags: Optional[SimFlags]) -> "PolicySet":
        """The SimFlags deprecation mapping: ``wfq=True`` selects the
        ``wfq`` scheduler policy (``wfq_weight`` becomes its ``weight``
        numeric param — both tags are ``scheduler:chain``, so FIFO and
        WFQ variants still share one compile group); everything else is
        the default set. The remaining flag booleans stay dynamic
        ``FamParams`` gates and never touch the policy choice."""
        if flags is None:
            flags = SimFlags()
        ps = cls(scheduler="wfq" if flags.wfq else "fifo")
        return ps.override("scheduler", weight=float(flags.wfq_weight))

    def describe(self) -> str:
        return "+".join(getattr(self, k) for k in POLICY_KINDS)


#: The paper's default configuration: SPP prefetching, FIFO service order
#: (WFQ selectable dynamically within the same fused kernel), LRU
#: replacement, token-bucket MIMD rate adaptation.
DEFAULT_POLICY_SET = PolicySet()
