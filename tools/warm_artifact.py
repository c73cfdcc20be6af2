"""Pre-build the AOT program artifact for a replica checkpoint.

Runs the exact warmups a replica runs at boot — the bucketed ladder rungs
for /predict and the full decode-engine program set for /generate — with
``warmup(aot=...)`` pointed at the output artifact, so every program is
traced ONCE here and every later cold-start is a millisecond
``deserialize_and_load`` (docs/AUTOSCALING.md "Artifact format").

    JAX_PLATFORMS=cpu python tools/warm_artifact.py \
        --model charlstm --out /ckpts/model.aot.zip --rungs 4 8

With ``--checkpoint`` the artifact is written as that checkpoint's
companion (``model.zip`` → ``model.aot.zip``) unless ``--out`` overrides;
the model signature covers shapes/dtypes only, so the artifact stays
valid across weight-only checkpoint updates of the same architecture.

The bench cold-start row imports ``build_artifact`` directly; the CLI is
the standalone/CI entry.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def build_artifact(model: str, out: str, precision=None, rungs=(4,),
                   slots: int = 4, max_len: int = 64,
                   checkpoint=None, decode_kw=None) -> dict:
    """Trace + serialize every hot program for ``model`` into ``out``.

    ``rungs`` are batch-bucket sizes for the InferenceEngine ladder;
    ``decode_kw`` forwards DecodeEngine config (kv=, chunk_tokens=,
    spec=...) so paged/spec deployments warm their side programs too.
    Returns a summary dict (program keys, wall seconds)."""
    from deeplearning4j_tpu.exec.aot import AotBundle
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    from deeplearning4j_tpu.serving.replica import build_model, CHAR_VOCAB

    net = build_model(model)
    if checkpoint:
        from deeplearning4j_tpu.util import model_serializer
        model_serializer.restore_into(net, os.fspath(checkpoint),
                                      load_updater=False)

    t0 = time.perf_counter()
    eng = InferenceEngine(net, precision=precision)
    # warmup walks the whole bucket ladder up to the cap, so the largest
    # requested rung covers the smaller ones
    shape = (4,) if model == "mlp" else (8, CHAR_VOCAB)
    eng.warmup(shape, max_batch=int(max(rungs)), aot=out)
    dec = None
    if model == "charlstm":
        dec = DecodeEngine(net, slots=slots, max_len=max_len,
                           precision=precision, **(decode_kw or {}))
        dec.warmup(aot=out)
    wall = time.perf_counter() - t0

    bundle = AotBundle.load(out)
    return {"artifact": os.path.abspath(out),
            "model": model,
            "model_sig": bundle.model_sig,
            "precision": bundle.precision,
            "backend": bundle.backend,
            "programs": sorted(bundle.keys()),
            "build_seconds": round(wall, 3)}


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="pre-build the AOT program artifact for a replica")
    parser.add_argument("--model", default="charlstm",
                        choices=("mlp", "charlstm"))
    parser.add_argument("--precision", default=None,
                        choices=("f32", "int8", "fp8"))
    parser.add_argument("--rungs", type=int, nargs="+", default=[4],
                        help="batch-bucket rungs to warm for /predict")
    parser.add_argument("--checkpoint", default=None,
                        help="load these weights; default output becomes "
                             "the checkpoint's .aot.zip companion")
    parser.add_argument("--out", default=None,
                        help="artifact path (required without --checkpoint)")
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--max-len", type=int, default=64)
    args = parser.parse_args(argv)

    out = args.out
    if out is None:
        if args.checkpoint is None:
            parser.error("--out is required without --checkpoint")
        from deeplearning4j_tpu.exec.aot import companion_path
        out = companion_path(args.checkpoint)

    # builds for the backend the environment gives this process (the
    # artifact is keyed on it) and names the device in the summary
    from deeplearning4j_tpu.exec.mesh import device_info
    from deeplearning4j_tpu.util.compile_cache import setup_compile_cache
    setup_compile_cache()

    summary = build_artifact(args.model, out, precision=args.precision,
                             rungs=tuple(args.rungs),
                             slots=args.slots, max_len=args.max_len,
                             checkpoint=args.checkpoint)
    summary["device"] = device_info()
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
