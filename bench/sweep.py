"""Find a serving cell's knee: the highest offered rate whose backlog does
not grow over a window.

    python3 bench/sweep.py --workload <serving cell> --rates 4 8 12 --seconds 10

One process builds and warms the server once, then offers each rate's
open-loop traffic in turn and prints, per rate, the requests due in the
window, those unfinished when it closed, how long the drain took, and the
tails.  The benchmark's own runs never run this; the chosen rate goes into
the cell's traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import prepare
    from bench.common import require_chips
    from bench import serve

    cell = prepare(args.workload)
    require_chips(cell.chips)
    server, _, _, mb = serve.build(cell, args.seed)
    serve.warm(server, cell, args.seed)
    t = cell.traffic
    for rate in args.rates:
        reqs = cell.inputs(args.seed, args.seconds,
                           dict(t, params=dict(t["params"], rate_per_s=rate)))
        res = serve.drive(server, reqs, args.seconds)
        s = serve.summarize(res, args.seconds)
        print(json.dumps({"rate_per_s": rate, "max_batch": mb, "due": s["attempted"],
                          "unfinished": s["failed"], "backlog_at_close": res["backlog"],
                          "drain_s": res["end_s"] - args.seconds, **s}), flush=True)
        while server.pending() or server._occupied:  # let the pool empty between rates
            server.step()


if __name__ == "__main__":
    main()
