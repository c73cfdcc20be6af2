"""Offline autotune sweep: pre-warm the per-backend routing table.

The runtime harness (exec/autotune.py, ``DL4JTPU_AUTOTUNE=1``) measures
each (kernel, shape, dtype) lazily on first use — which puts one
benchmark pause inside the first training step that hits a new shape.
This CLI runs the same measurements ahead of time and writes them to
the table file ``--out`` names (KERNELS_TPU.json schema), which a process
loads with ``exec.routing.load_measurements_file(path)`` and so never
pays the first-use pause:

    python tools/autotune.py --out autotune_tpu.json \
        --lstm 32x64x256:float32 --lstm 64x128x512 \
        --flash 8x1024x64 --flash 8x2048x64:causal

Shape syntax — LSTM: ``BxTxH[:dtype]`` (dtype defaults to float32);
flash attention: ``BHxTxDh[:causal]``. ``--interpret`` forces the
Pallas interpret path (the default off-TPU); ``--dry-run`` parses and
prints the plan without measuring.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def parse_lstm(spec: str):
    """``BxTxH[:dtype]`` -> (B, T, H, dtype)."""
    dims, _, dtype = spec.partition(":")
    parts = dims.lower().split("x")
    if len(parts) != 3:
        raise argparse.ArgumentTypeError(
            f"--lstm wants BxTxH[:dtype], got {spec!r}")
    b, t, h = (int(p) for p in parts)
    return (b, t, h, dtype or "float32")


def parse_flash(spec: str):
    """``BHxTxDh[:causal]`` -> (BH, T, Dh, causal)."""
    dims, _, flag = spec.partition(":")
    parts = dims.lower().split("x")
    if len(parts) != 3:
        raise argparse.ArgumentTypeError(
            f"--flash wants BHxTxDh[:causal], got {spec!r}")
    if flag and flag != "causal":
        raise argparse.ArgumentTypeError(
            f"--flash modifier must be 'causal', got {flag!r}")
    bh, t, dh = (int(p) for p in parts)
    return (bh, t, dh, bool(flag))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="autotune.py",
        description="Measure kernel-vs-reference routes on this backend "
                    "and persist them to the autotune table.")
    ap.add_argument("--lstm", action="append", default=[], type=parse_lstm,
                    metavar="BxTxH[:dtype]",
                    help="fused-LSTM shape to measure (repeatable)")
    ap.add_argument("--flash", action="append", default=[], type=parse_flash,
                    metavar="BHxTxDh[:causal]",
                    help="flash-attention shape to measure (repeatable)")
    ap.add_argument("--iters", type=int, default=3,
                    help="timing iterations per side (min taken; default 3)")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="table file to merge the rows into (required "
                         "unless --dry-run)")
    ap.add_argument("--interpret", action="store_true",
                    help="force Pallas interpret mode (default off-TPU)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the plan without measuring")
    args = ap.parse_args(argv)

    if not args.lstm and not args.flash:
        ap.error("nothing to measure: pass at least one --lstm or --flash")

    if args.dry_run:
        for b, t, h, dt in args.lstm:
            print(f"fused_lstm B={b} T={t} H={h} dtype={dt}")
        for bh, t, dh, causal in args.flash:
            print(f"flash_attention BH={bh} T={t} Dh={dh} causal={causal}")
        return 0

    if not args.out:
        ap.error("--out PATH is required: no table is written to, or read "
                 "from, a default location")

    from deeplearning4j_tpu.exec import autotune

    rows = autotune.sweep(args.out, lstm_shapes=args.lstm,
                          flash_shapes=args.flash, iters=args.iters,
                          interpret=args.interpret or None)
    path = args.out
    skipped = (len(args.lstm) + len(args.flash)) - len(rows)
    for r in rows:
        print(json.dumps(r, sort_keys=True))
    print(f"{len(rows)} row(s) -> {path}"
          + (f" ({skipped} shape(s) unsupported, skipped)" if skipped else ""),
          file=sys.stderr)
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
