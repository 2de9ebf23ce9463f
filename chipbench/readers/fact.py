"""A number the run recorded as it is: params {"path", optional "scale"}."""

from chipbench.readers import dig


def read(facts: dict, params: dict):
    value = dig(facts, params["path"])
    return None if value is None else value * params.get("scale", 1.0)
