"""One small reader per kind of per-layer metric: `read(facts, params)`
returns the number, or None when there is nothing to read."""


def dig(facts: dict, path: str):
    """`facts["a"]["b"]` for the path "a.b"; None when any part is missing."""
    node = facts
    for key in path.split("."):
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return node
