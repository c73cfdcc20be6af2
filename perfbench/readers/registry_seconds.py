"""Seconds of set-up spent bringing step programs into being, from the
program's own registries: the containers' ``counter`` (wall seconds of
train calls that traced a new program: tracing, lowering, and compiling
or loading from the persistent cache) plus every registered program's
``aot_seconds`` (the second lowering and compile its registration costs).
None where the program keeps either not."""


def read(obs, trace, cell, args):
    try:
        from deeplearning4j_tpu.exec.programs import get_programs
        from deeplearning4j_tpu.monitor.metrics import get_registry
    except ImportError:
        return None
    family = get_registry().get(args["counter"])
    aot = [p["aot_seconds"] for p in get_programs().entries()
           if p.get("aot_seconds") is not None]
    if family is None or not aot:
        return None
    return float(sum(c.value for _, c in family.children()) + sum(aot))
