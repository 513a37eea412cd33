"""Compute-node prefetch rate-control policies (paper §IV-B).

* ``token_bucket`` — the paper's sampling-based MIMD congestion control
  over a deterministic token bucket, delegating to ``repro.core.throttle``
  (the default; byte-identical to the pre-policy simulator). Its five
  tuning knobs — previously loose ``FamParams`` fields — are now the
  policy's numeric-param pytree, traced and sweepable without recompiling.
* ``static`` — the no-adaptation baseline: the issue rate is pinned at the
  ``rate`` numeric param and enforced through the same token bucket, so a
  rate sweep isolates the value of *adapting* from the value of
  *limiting*.

Both keep a ``ThrottleState`` (its ``issue_rate`` leaf feeds the figure
metrics), and every state write is gated by ``enable`` so non-live steps
stay exact no-ops.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.throttle import (init_throttle, maybe_adapt, observe,
                                 take_tokens)
from repro.policies.base import register


class _AdaptCfg(NamedTuple):
    """Duck-typed view handing the policy's traced params to
    ``throttle.maybe_adapt`` (which reads them off a FamConfig-shaped
    object)."""

    sample_interval: object
    latency_noise_threshold: object
    mimd_increase: object
    ema_alpha: object
    min_issue_rate: object


class TokenBucketAdaptation:
    """MIMD/RED adaptation over a token bucket (``repro.core.throttle``)."""

    kind = "adaptation"
    name = "token_bucket"
    compile_tag = "adaptation:throttle"

    def params_of(self, cfg):
        return {"sample_interval": np.int32(cfg.sample_interval),
                "latency_noise_threshold":
                    np.float32(cfg.latency_noise_threshold),
                "mimd_increase": np.float32(cfg.mimd_increase),
                "ema_alpha": np.float32(cfg.ema_alpha),
                "min_issue_rate": np.float32(cfg.min_issue_rate)}

    def gate(self, p):
        """Active only under the legacy ``bw_adapt`` feature flag (the
        paper's with/without-adaptation comparison stays a dynamic gate
        sharing one compile)."""
        return p.bw_adapt

    def init(self, p, pol):
        return init_throttle(p)

    def take(self, p, pol, state, want, enable):
        return take_tokens(state, want, enable)

    def observe(self, p, pol, state, demand_latency, is_fam_demand,
                was_pf_hit, pf_issued_now, enable):
        return observe(state, demand_latency, is_fam_demand, was_pf_hit,
                       pf_issued_now, enable=enable)

    def adapt(self, p, pol, state, enable):
        view = _AdaptCfg(pol["sample_interval"],
                         pol["latency_noise_threshold"],
                         pol["mimd_increase"], pol["ema_alpha"],
                         pol["min_issue_rate"])
        return maybe_adapt(view, state, enabled=enable)


class StaticRateAdaptation:
    """Fixed issue rate: enforcement without adaptation. ``rate`` is a
    traced param, so a rate sweep (0.05 .. 1.0) shares one compile."""

    kind = "adaptation"
    name = "static"
    compile_tag = "adaptation:static"

    def params_of(self, cfg):
        return {"rate": np.float32(1.0)}

    def gate(self, p):
        """Always active: choosing the static policy IS the opt-in — its
        whole point is the pinned rate, independent of the legacy
        ``bw_adapt`` flag (which only selects the paper's
        adaptation-on/off comparison for the token bucket)."""
        return jnp.bool_(True)

    def init(self, p, pol):
        return init_throttle(p)._replace(
            issue_rate=jnp.asarray(pol["rate"], jnp.float32))

    def take(self, p, pol, state, want, enable):
        return take_tokens(state, want, enable)

    def observe(self, p, pol, state, demand_latency, is_fam_demand,
                was_pf_hit, pf_issued_now, enable):
        return state                     # nothing to learn

    def adapt(self, p, pol, state, enable):
        return state                     # nothing to adapt


TOKEN_BUCKET = register(TokenBucketAdaptation())
STATIC = register(StaticRateAdaptation())
