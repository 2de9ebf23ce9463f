"""A recorded number less the sum of others: params {"path", "less": [paths]}.
None when any of them is missing."""

from chipbench.readers import dig


def read(facts: dict, params: dict):
    values = [dig(facts, p) for p in [params["path"], *params["less"]]]
    return None if None in values else values[0] - sum(values[1:])
