#!/usr/bin/env python3
"""Cut the first ``--modules`` program calls of chip 0 out of an xplane
file into the small JSON that perfbench/tests/test_trace.py reduces, with
the reduction's own result beside it:

    python3 perfbench/tools/cut_trace.py <file.xplane.pb> <out.json> --modules 2
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import trace as tr


def main():
    p = argparse.ArgumentParser()
    p.add_argument("xplane")
    p.add_argument("out")
    p.add_argument("--modules", type=int, default=2)
    a = p.parse_args()
    planes, _ = tr.read_xplane(a.xplane)
    name = sorted(planes)[0]
    mods = sorted(planes[name][tr.MODULES_LINE])
    big = [m for m in mods if m[1] > 1e6][: a.modules]
    lo, hi = big[0][0], big[-1][0] + big[-1][1]
    t0 = lo
    cut = {name: {
        tr.MODULES_LINE: [[s - t0, d, tr.short_name(n)] for s, d, n in mods
                          if lo <= s and s + d <= hi],
        tr.OPS_LINE: [[s - t0, d, tr.short_name(n)]
                      for s, d, n in planes[name][tr.OPS_LINE]
                      if lo <= s and s + d <= hi]}}
    red = tr.reduce_chips(tr.chips_from_events(
        {k: {l: [tuple(e) for e in evs] for l, evs in v.items()}
         for k, v in cut.items()}))
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump({"from": os.path.basename(a.xplane), "planes": cut,
                   "expect": {"busy_s": red["busy_s"],
                              "window_s": red["window_s"]}}, f)
    print(f"cut {len(cut[name][tr.OPS_LINE])} ops, "
          f"{os.path.getsize(a.out)} bytes, idle {red['idle_share']:.4f}")


if __name__ == "__main__":
    main()
