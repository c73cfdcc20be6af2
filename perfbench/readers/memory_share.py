"""Peak bytes in use on the fullest chip over that chip's limit, from
``device.memory_stats()`` after the window."""


def read(obs, trace, cell, args):
    if not obs.get("memory_limit_bytes") or not obs.get("memory_peak_bytes"):
        return None
    return 100.0 * obs["memory_peak_bytes"] / obs["memory_limit_bytes"]
