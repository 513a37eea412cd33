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
  ``proposals.dims`` (a search generation);
* ``replicates`` -- ``count`` copies of the grid, each on traces of its own
  seed (:func:`replicate_seed`; copy 0 is the grid without the axis).

A system is a plain dict. :func:`to_experiment` turns a call's systems into
the program's ``Experiment``, each system with its own seed; the
configuration's reference (its ``reference`` key, a module in ``bench/``)
reads the same dicts and never the program's objects. Call ``i`` of a run
with seed ``s`` uses the trace seed :func:`call_seed` ``(s, i)``, so every
call simulates new traces and the same seed gives the same calls.

A traffic file whose axes these kinds cannot express names, under
``expand``, a module in ``bench/`` that exports ``systems(traffic, config,
seed, call)`` and ``to_experiment(systems, config, name)`` in their place;
:func:`expansion` picks the pair a traffic file asks for.
"""
from __future__ import annotations

import importlib.util
import itertools
import json
import re
import sys
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
FLAG_DEFAULTS = {"core_prefetch": True, "dram_prefetch": True,
                 "bw_adapt": False, "wfq": False, "wfq_weight": 2,
                 "all_local": False}


#: calls a run may make: call seeds of one run lie within this of each other
MAX_CALLS = 4096
#: the step between the trace seeds of a system's nodes, in the program
#: (``repro.traces.node_seed``) and in the reference (``node_seed``)
NODE_STRIDE = 1_000_003
#: nodes a replicated system may have: a replicate's seed steps past the
#: node seeds of this many nodes
MAX_NODES = 64


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


@lru_cache(maxsize=None)
def bench_module(name: str):
    """The module in file ``name`` under ``bench/`` (``reference.py``,
    ``metrics/step_us.py``), loaded once a process."""
    path = (BENCH / name).resolve()
    if BENCH not in path.parents or path.suffix != ".py":
        raise ValueError(f"{name!r} is not a module file under {BENCH}")
    mod_name = "bench_" + re.sub(r"\W", "_", name[:-3])
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def expansion(traffic: dict):
    """``(systems, to_experiment)`` of a traffic file: those of the module
    its ``expand`` key names, else this module's own."""
    if "expand" not in traffic:
        return systems, to_experiment
    mod = bench_module(traffic["expand"])
    return mod.systems, mod.to_experiment


def call_seed(seed: int, call: int) -> int:
    """The trace seed of call ``call`` of a run seeded ``seed``."""
    if not 0 <= int(call) < MAX_CALLS:
        raise ValueError(f"call {call} outside [0, {MAX_CALLS})")
    return int(seed) * MAX_CALLS + int(call)


def replicate_seed(seed: int, call: int, replicate: int) -> int:
    """The trace seed of replicate ``replicate`` of call ``call``.

    Node ``n`` of a system seeded ``x`` draws its trace from ``x +
    NODE_STRIDE * n``. Replicate ``r`` adds ``NODE_STRIDE * MAX_NODES * r``,
    so its node ``n`` lies at ``call_seed + NODE_STRIDE * (MAX_NODES * r +
    n)``: as the call seeds of a run differ by less than ``NODE_STRIDE``, no
    two (call, replicate, node) of the run share a seed."""
    return call_seed(seed, call) + NODE_STRIDE * MAX_NODES * int(replicate)


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
    if kind == "replicates":
        if traffic["nodes"] > MAX_NODES:
            raise ValueError(f"replicates need at most {MAX_NODES} nodes, "
                             f"traffic says {traffic['nodes']}")
        return [(str(r), {"replicate": r}) for r in range(axis["count"])]
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
            if "replicate" in contrib:
                s["seed"] = replicate_seed(seed, call, contrib["replicate"])
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
    in order (one grid axis, labelled by index), each on its own seed."""
    from repro.core.famsim import SimFlags
    from repro.experiments import Experiment, grid_axis
    from repro.policies import PolicySet

    base = base_config(config)
    values = {}
    for i, s in enumerate(systems_):
        flags = SimFlags(**s["flags"])
        fields = {"workloads": tuple(s["workloads"]), "flags": flags,
                  "seed": s["seed"]}
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
    Ts = {s["T"] for s in systems_}
    if len(Ts) != 1:
        raise ValueError("one call has one T")
    return Experiment(name=name, base=base, T=Ts.pop(),
                      nodes=len(systems_[0]["workloads"]),
                      axes=(grid_axis("system", values),))
