"""What the compiled step program needs of one chip's memory by XLA's own
``memory_analysis()``: ``memory_bytes`` of the newest entry of the
program's registry whose key starts with ``key_prefix`` (arguments,
outputs, temporaries and code, a donated argument counted once), over the
chip's ``bytes_limit``. ``peak_bytes_in_use`` (``hbm_peak_share``) is what
the process held; this is what the program was compiled to hold."""


def read(obs, trace, cell, args):
    limit = obs.get("memory_limit_bytes")
    if not limit:
        return None
    try:
        from deeplearning4j_tpu.exec.programs import get_programs
    except ImportError:
        return None
    mine = [p for p in get_programs().entries()
            if p["key"].startswith(args["key_prefix"])
            and p.get("memory_bytes")]
    if not mine:
        return None
    return 100.0 * mine[-1]["memory_bytes"] / limit
