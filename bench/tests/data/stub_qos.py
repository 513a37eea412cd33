"""A stand-in traffic expansion with an axis kind that ``grid`` does not
know: ``qos``, named service classes, each a WFQ weight and a floor on the
prefetch issue rate that every node of the system gets. The other axes are
``grid``'s own; the class is the first coordinate of each system."""
import copy

import grid


def systems(traffic, config, seed, call):
    (qos,) = [a for a in traffic["axes"] if a["kind"] == "qos"]
    rest = dict(traffic, axes=[a for a in traffic["axes"] if a is not qos])
    base = grid.systems(rest, config, seed, call)
    out = []
    for label, cls in qos["classes"].items():
        for s in base:
            s = copy.deepcopy(s)
            s["coords"] = {qos["name"]: label, **s["coords"]}
            s["flags"].update(wfq=True, wfq_weight=cls["weight"])
            s["system"]["min_issue_rate"] = cls["min_issue_rate"]
            out.append(s)
    return out


to_experiment = grid.to_experiment
