"""A count the program or the harness keeps, over the window; ``per``
divides it by the window's steps or passes."""


def read(params, observed, traced, peaks):
    value = observed["counters"].get(params["counter"])
    if value is None:
        return None
    per = params.get("per")
    if per is not None:
        if not observed.get(per):
            return None
        value = value / observed[per]
    return value
