"""The part of the collectives' device time during which no other
operation ran on that device, per step, in ms."""


def read(params, observed, traced, peaks):
    if traced is None or not traced["steps"]:
        return None
    return traced["collective_exposed_s"] / traced["steps"] * 1e3
