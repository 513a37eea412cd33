"""The one traffic generator: a traffic file + a configuration -> systems.

A traffic file (``bench/traffic/<name>.json``) is data: the trace length
``T``, the warm-up fraction, the node count, and a list of axes whose
Cartesian product (first axis outermost) is the grid of simulated systems
that one call of the executor runs. Axis kinds:

* ``system``   -- one configuration key swept over ``values``;
* ``workload`` -- one workload, replicated over the node count;
* ``mix``      -- named per-node workload tuples;
* ``flags``    -- named feature-flag variants (``core_prefetch``,
  ``dram_prefetch``, ``bw_adapt``, ``wfq``, ``wfq_weight``);
* ``proposals``-- ``proposals.count`` candidates sampled from the seed over
  ``proposals.dims`` (a search generation).

A system is a plain dict. :func:`to_experiment` turns a call's systems into
the program's ``Experiment``; :mod:`reference` reads the same dicts and
never the program's objects. Call ``i`` of a run with seed ``s`` uses the
trace seed :func:`call_seed` ``(s, i)``, so every call simulates new traces
and the same seed gives the same calls.
"""
from __future__ import annotations

import itertools
import json
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
FLAG_DEFAULTS = {"core_prefetch": True, "dram_prefetch": True,
                 "bw_adapt": False, "wfq": False, "wfq_weight": 2,
                 "all_local": False}


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def call_seed(seed: int, call: int) -> int:
    """The trace seed of call ``call`` of a run seeded ``seed``."""
    return int(seed) * 4096 + int(call)


def _proposals(traffic: dict, seed: int, call: int) -> Dict[str, dict]:
    """``count`` candidates drawn from the seed over ``dims``."""
    spec = traffic["proposals"]
    rng = np.random.default_rng([int(seed) % 2**63, int(call), 0x5EA4C4])
    out = {}
    for c in range(spec["count"]):
        sample = {}
        for d in spec["dims"]:
            if "choices" in d:
                sample[d["name"]] = d["choices"][int(rng.integers(
                    len(d["choices"])))]
            elif d.get("log"):
                lo, hi = np.log(d["lo"]), np.log(d["hi"])
                sample[d["name"]] = float(np.exp(rng.uniform(lo, hi)))
            else:
                sample[d["name"]] = float(rng.uniform(d["lo"], d["hi"]))
        out[f"cand{c}"] = sample
    return out


def _axis_values(axis: dict, traffic: dict, seed: int, call: int):
    """[(label, contribution dict)] for one axis."""
    kind = axis["kind"]
    if kind == "system":
        return [(str(v), {"system": {axis["key"]: v}})
                for v in axis["values"]]
    if kind == "workload":
        n = traffic["nodes"]
        return [(w, {"workloads": (w,) * n}) for w in axis["values"]]
    if kind == "mix":
        return [(k, {"workloads": tuple(v)})
                for k, v in axis["values"].items()]
    if kind == "flags":
        return [(k, {"flags": dict(v)}) for k, v in axis["values"].items()]
    if kind == "proposals":
        dims = {d["name"]: d for d in traffic["proposals"]["dims"]}
        vals = []
        for label, sample in _proposals(traffic, seed, call).items():
            contrib = {"flags": {}, "policy": {}, "params": {}}
            for name, v in sample.items():
                t = dims[name]["target"]
                if t[0] == "policy":
                    contrib["policy"][t[1]] = v
                elif t[0] == "policy_param":
                    contrib["params"].setdefault(t[1], {})[t[2]] = v
                else:
                    contrib["flags"][t[1]] = v
            vals.append((label, contrib))
        return vals
    raise ValueError(f"unknown axis kind {kind!r}")


def systems(traffic: dict, config: dict, seed: int, call: int
            ) -> List[dict]:
    """Every simulated system of one call, in grid order."""
    axes = [_axis_values(a, traffic, seed, call) for a in traffic["axes"]]
    out = []
    for combo in itertools.product(*axes):
        s = {"coords": {}, "system": dict(config["system"]),
             "flags": dict(FLAG_DEFAULTS), "policy": {}, "params": {},
             "workloads": None, "T": traffic["T"],
             "warmup_frac": traffic["warmup_frac"],
             "seed": call_seed(seed, call), "explicit_policy": False}
        for ax, (label, contrib) in zip(traffic["axes"], combo):
            s["coords"][ax["name"]] = label
            s["system"].update(contrib.get("system", {}))
            s["flags"].update(contrib.get("flags", {}))
            if contrib.get("policy") or contrib.get("params"):
                s["explicit_policy"] = True
            s["policy"].update(contrib.get("policy", {}))
            for kind, kv in contrib.get("params", {}).items():
                s["params"].setdefault(kind, {}).update(kv)
            if "workloads" in contrib:
                s["workloads"] = contrib["workloads"]
        if len(s["workloads"]) != traffic["nodes"]:
            raise ValueError(f"system {s['coords']} has "
                             f"{len(s['workloads'])} nodes, traffic says "
                             f"{traffic['nodes']}")
        out.append(s)
    return out


# ---------------------------------------------------------------------------
# The program side: a call's systems as the program's Experiment
# ---------------------------------------------------------------------------

def base_config(config: dict):
    """The program's ``FamConfig`` for a configuration file: every
    ``system`` key must exist; ``program`` keys apply where they exist."""
    import dataclasses

    from repro.configs.base import FamConfig
    fields = {f.name for f in dataclasses.fields(FamConfig)}
    missing = sorted(set(config["system"]) - fields)
    if missing:
        raise ValueError(f"the program's FamConfig lacks {missing}")
    kw = dict(config["system"])
    kw.update({k: v for k, v in config.get("program", {}).items()
               if k in fields})
    return FamConfig(**kw)


def to_experiment(systems_: Sequence[dict], config: dict, name: str):
    """One ``repro.experiments.Experiment`` whose points are ``systems_``
    in order (one grid axis, labelled by index)."""
    from repro.core.famsim import SimFlags
    from repro.experiments import Experiment, grid_axis
    from repro.policies import PolicySet

    base = base_config(config)
    values = {}
    for i, s in enumerate(systems_):
        flags = SimFlags(**s["flags"])
        fields = {"workloads": tuple(s["workloads"]), "flags": flags}
        over = {k: v for k, v in s["system"].items()
                if config["system"].get(k) != v}
        if over:
            fields["cfg"] = over
        if s["explicit_policy"]:
            pol = PolicySet(**s["policy"])
            for kind, kv in s["params"].items():
                pol = pol.override(kind, **kv)
            fields["policies"] = pol
        values[str(i)] = fields
    seeds = {s["seed"] for s in systems_}
    Ts = {s["T"] for s in systems_}
    if len(seeds) != 1 or len(Ts) != 1:
        raise ValueError("one call has one seed and one T")
    return Experiment(name=name, base=base, T=Ts.pop(), seed=seeds.pop(),
                      nodes=len(systems_[0]["workloads"]),
                      axes=(grid_axis("system", values),))
