"""The tx generator: a process of its own, so that it shares no interpreter
lock with the node. It imports the program's RPC client only (that import
does not load jax, so the chip stays with the node's process).

Open loop over one connection (loadtime's ``-c 1``, fixed ``-r``): tx ``i``
is due at ``t0 + i / rate`` on CLOCK_MONOTONIC, which parent and child share
on one host. ``broadcast_tx_sync`` returns when CheckTx has answered; a tx
whose turn comes late is sent at once and its lateness recorded. One JSON
line per tx goes to stdout: [seq, sent, acked, code].
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import sys
import time


def make_tx(seed: int, seq: int, size: int) -> bytes:
    """A seeded kvstore tx ``key=value`` of exactly ``size`` bytes."""
    key = b"bk-%d-%08d-" % (seed, seq)
    pad = hashlib.sha256(b"%d|tx|%d" % (seed, seq)).hexdigest().encode()
    pad = pad * (size // len(pad) + 1)
    tail = b"=v%08d" % seq
    return key + pad[: size - len(key) - len(tail)] + tail


def main() -> int:
    spec = json.loads(sys.argv[1])
    if spec.get("cpus"):
        os.sched_setaffinity(0, spec["cpus"])
    sys.path.insert(0, spec["root"])
    from cometbft_tpu.rpc.client import HTTPClient

    if "jax" in sys.modules:
        print("txgen: the RPC client import loaded jax", file=sys.stderr)
        return 5
    client = HTTPClient(spec["rpc"], timeout=30.0)
    seed, size, rate, n = spec["seed"], spec["tx_bytes"], spec["rate"], spec["n"]
    txs = [base64.b64encode(make_tx(seed, i, size)).decode() for i in range(n)]
    deadline = time.monotonic() + 120
    while True:  # the node boots meanwhile; the connection is up before t0
        try:
            client.call("health")
            break
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.1)
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())  # the parent fires the start
    out = []
    for i in range(n):
        due = t0 + i / rate
        now = time.monotonic()
        if now < due:
            time.sleep(due - now)
        sent = time.monotonic()
        try:
            res = client.call("broadcast_tx_sync", tx=txs[i])
            code = int(res["code"])
        except Exception as e:  # a refused or failed tx is counted, not fatal
            code = -1
            print(f"txgen: tx {i}: {e!r}"[:200], file=sys.stderr)
        out.append([i, sent, time.monotonic(), code])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
