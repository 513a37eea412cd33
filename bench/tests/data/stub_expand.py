"""A stand-in traffic expansion for the tests: ``grid``'s own, each call
recorded in ``CALLS``."""
import grid

CALLS = []


def systems(traffic, config, seed, call):
    CALLS.append(("systems", call))
    return grid.systems(traffic, config, seed, call)


def to_experiment(systems_, config, name):
    CALLS.append(("to_experiment", len(systems_)))
    return grid.to_experiment(systems_, config, name)
