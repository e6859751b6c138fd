#!/usr/bin/env python3
"""A cell run under a planted fault (perfbench/faults.py), at the cell's
own size on the chip:

    python3 perfbench/control.py --fault <name> --workload <cell> --seed <n> --seconds <s>

Prints what a run prints. The run has to come out `correct: false`; this
script exits 0 when it does and 1 when the fault went unseen. Not part of
the benchmark's own runs."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    if len(argv) < 2 or argv[0] != "--fault":
        print(__doc__, file=sys.stderr)
        return 2
    from perfbench import faults, run
    if argv[1] not in faults.FAULTS:
        print(f"control: no fault {argv[1]!r}; there are "
              f"{sorted(faults.FAULTS)}", file=sys.stderr)
        return 2
    with faults.FAULTS[argv[1]]():
        rc, res = run.execute(argv[2:])
    if res is None:
        print(f"control: the run under {argv[1]} gave no result (rc {rc}): "
              f"a run that crashes has failed, but sets no reading",
              file=sys.stderr)
        return 1
    run.report(res)
    seen = res["correct"] is False
    print(f"control: fault {argv[1]} "
          + ("seen: correct came out false" if seen else "NOT SEEN"),
          file=sys.stderr)
    return 0 if seen else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
