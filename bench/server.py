"""The sweep server as the benchmark runs it, in a process of its own.

    python -m bench.server --cache DIR [--fault alter]

Starts :class:`repro.serve.SweepServer` as deployed on one chip: one
worker seat, batch mode, the default chunk size, a result cache in
``DIR``.  Its pool is :class:`bench.seat.SeatPool` around the program's
own ``WorkerPool``.  Every line it prints for the client starts with
``BENCH ``: first the server's address, then one reply per command read
from standard input, one JSON object per line:

- ``{"cmd": "seat", "fn": NAME, "args": [...]}`` runs ``bench.seat.NAME``
  inside the seat and replies with its result;
- ``{"cmd": "spans"}`` replies with the seat's chunk spans so far;
- ``{"cmd": "stop"}`` drains the server and exits.
"""
from __future__ import annotations

import argparse
import json
import sys


def reply(obj) -> None:
    print("BENCH " + json.dumps(obj), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cache", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from bench import seat
    from repro.distributed.workpool import WorkerPool
    from repro.serve import SweepServer
    from repro.serve import worker as worker_mod

    pools = []

    def pool_factory():
        pools.append(seat.SeatPool(
            WorkerPool(1, initializer=worker_mod.init_worker,
                       task_deadline_s=300.0), fault=args.fault))
        return pools[0]

    server = SweepServer(port=0, cache_dir=args.cache, workers=1,
                         mode="batch", pool_factory=pool_factory)
    server.start()
    reply(dict(address=server.address))
    pool = pools[0]
    for line in sys.stdin:
        cmd = json.loads(line)
        try:
            if cmd["cmd"] == "seat":
                reply(dict(ok=True, result=pool.call(
                    getattr(seat, cmd["fn"]), *cmd.get("args", ()))))
            elif cmd["cmd"] == "spans":
                reply(dict(ok=True, result=pool.spans()))
            elif cmd["cmd"] == "stop":
                server.shutdown()
                reply(dict(ok=True, result=None))
                return 0
        except Exception as e:  # the client decides; the server stays up
            reply(dict(ok=False, error=f"{type(e).__name__}: {e}"))
    server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
