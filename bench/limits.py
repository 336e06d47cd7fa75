#!/usr/bin/env python3
"""Read the numbers that a cell's limits are set from: run the cell on
several seeds in one process (set-up is paid once for the chip) and print
each seed's checks, for the program or for a ``--check`` (the control, or
a planted fault).

    python3 bench/limits.py --workload vnet-serve-poisson --seconds 2 \\
        --seeds 11 12 13 [--check control]

The limits in ``bench/checks/<workload>.json`` are set from these readings
as ``PERF.md`` records: above the largest reading of the program, below the
smallest of the control.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--check", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    worst: dict[str, float] = {}
    for seed in args.seeds:
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = run.main(["--workload", args.workload, "--seed", str(seed),
                           "--seconds", str(args.seconds)]
                          + (["--check", args.check] if args.check else [])
                          + (["--rehearse"] if args.rehearse else []))
        if rc != 0:
            print(f"seed {seed}: rc {rc}\n{err.getvalue()[-3000:]}",
                  flush=True)
            continue
        res = json.loads(out.getvalue().strip().splitlines()[-1])
        got = {k: c["value"] for k, c in res["checks"].items()}
        for k, v in got.items():
            worst[k] = max(worst.get(k, v), v)
        phases = [l for l in err.getvalue().splitlines()
                  if l.startswith("phases")]
        print(f"seed {seed}: correct {res['correct']} "
              + " ".join(f"{k} {v!r}" for k, v in got.items())
              + f" | {phases[-1] if phases else ''}", flush=True)
    print(f"largest over {len(args.seeds)} seeds "
          f"({args.check or 'program'}): {json.dumps(worst)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
