#!/usr/bin/env python3
"""Find a serving cell's knee: run it at several offered rates in one
process and print, per rate, the latency percentiles and the mean latency
by quarter of the window (a queue that grows makes the quarters rise).

    python3 bench/sweep.py --workload vnet-serve-poisson --seconds 10 \\
        --rates 10 15 20 25 30

The knee is the highest rate whose quarters stay flat; a cell runs at
about four fifths of it, written into its traffic file.
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
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for rate in args.rates:
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = run.main(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--rate", str(rate)]
                          + (["--rehearse"] if args.rehearse else []))
        notes = [l for l in err.getvalue().splitlines()
                 if l.startswith(("mean latency by quarter",
                                  "generator lateness", "garbage collection"))]
        last = out.getvalue().strip().splitlines()[-1] if rc == 0 else "{}"
        res = json.loads(last)
        m = {k: v["value"] for k, v in res.get("metrics", {}).items()}
        print(f"rate {rate}: rc {rc} failed {res.get('failed')} of "
              f"{res.get('attempted')} p50 {m.get('serve_p50_ms')} "
              f"p95 {m.get('serve_p95_ms')} | " + " | ".join(notes),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
