"""A share of the traced window from the trace reduction
(perfbench/lib/trace.py): 100 * num / window_s, or the idle share."""


def read(obs, trace, cell, args):
    if not trace:
        return None
    if args["num"] == "idle":
        return 100.0 * trace["idle_share"]
    if args.get("needs") and not trace.get(args["needs"]):
        return None
    return 100.0 * trace[args["num"]] / trace["window_s"]
