"""A number the driver took itself (host clock, or a counter of the
program), found by its path in the run's facts."""


def read(facts, path, scale=1.0):
    node = facts
    for key in path:
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return float(node) * float(scale)
