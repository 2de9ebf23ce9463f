"""100 * num / den of two recorded numbers: params {"num", "den" or
"den_sum": [paths], optional "complement": 100 minus that}."""

from chipbench.readers import dig


def read(facts: dict, params: dict):
    num = dig(facts, params["num"])
    dens = [dig(facts, p) for p in params.get("den_sum", [params.get("den")])]
    if num is None or any(d is None for d in dens) or not sum(dens):
        return None
    pct = 100.0 * num / sum(dens)
    return 100.0 - pct if params.get("complement") else pct
