#!/usr/bin/env python3
"""A control of a cell's correctness check: the reference below bfloat16.

  python3 bench/control.py --precision int8 --workload qwen3_30b_a3b.chat \
      --seed 5 --seconds 51 --trace 0

The same run as ``run.py``, but the check reads, in place of the tokens the
program served, the tokens the plain reference puts first when every
projection is computed in ``--precision`` (``fp8``: float8 e4m3, or
``int8``; the two steps below the bfloat16 the configurations serve), at
the same positions of the same sequences.  Its readings are the upper end
a limit is set below: a sound limit reads the control as not correct.  The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import sys

import run

if __name__ == "__main__":
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--precision", choices=("fp8", "int8"), required=True)
    args, rest = ap.parse_known_args()
    sys.exit(run.main(rest, control=args.precision))
