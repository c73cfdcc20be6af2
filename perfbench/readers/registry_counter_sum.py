"""The sum of one of the program's counter families over the children
whose labels are among those the metric names: ``args["family"]``, and
``args["labels"]``, a table from a label's name to the values that count
(a label not named counts whatever its value; no table: every child).
``args["less"]``, where given, is a second such table whose children's sum
is taken off: a part the first sum holds (the seconds of the backend spent
loading from the cache, off the backend's seconds). Read from the
program's own registry as ``registry_seconds.py`` reads it. None where the
program keeps no such family (an older program: the line leaves the metric
out); 0 where it keeps one and no child matches."""


def _sum(family, table):
    total = 0.0
    for values, child in family.children():
        labels = dict(zip(family.labelnames, values))
        if all(labels.get(k) in allowed for k, allowed in table.items()):
            total += child.value
    return total


def read(obs, trace, cell, args):
    try:
        from deeplearning4j_tpu.monitor.metrics import get_registry
    except ImportError:
        return None
    family = get_registry().get(args["family"])
    if family is None:
        return None
    total = _sum(family, args.get("labels", {}))
    if "less" in args:
        total -= _sum(family, args["less"])
    return float(total)
