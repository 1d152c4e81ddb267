"""A solo kvstore node under load: home from ``cmd init``, node from
``default_new_node(cfg)`` exactly as ``cmd start`` builds it (file stores,
WAL fsync on, default consensus timeouts, both planes in ``auto``), loaded
over its RPC server by the tx generator child (drivers/txgen.py).

A tx is timed from the instant it was due at the generator to the instant
this harness saw the block that holds it committed (the node's NewBlock
event, taken from the event bus by a thread that does nothing else); both
instants are CLOCK_MONOTONIC readings on one host. After the window a bounded
drain waits for the txs that were due inside it.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time
from argparse import Namespace

from ..harness import spec, stats
from ..reference import rfc6962
from . import txgen


def _free_port() -> int:
    """A loopback port nothing listens on (deployment setting, not a size)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Driver:
    def __init__(self, cell, seed: int, tracer):
        self.cell, self.seed, self.tracer = cell, seed, tracer
        self.cfg, self.mix = cell.config, cell.mix
        self.node = None
        self.child = None
        self.sub = None
        self.marks = stats.Marks()
        self.home = os.path.join(spec.ROOT, ".bench_home", cell.name)

    # -- set-up ----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from cometbft_tpu.cmd.__main__ import _config
        from cometbft_tpu.cmd.__main__ import main as cli_main
        from cometbft_tpu.node import default_new_node
        from cometbft_tpu.types.event_bus import QUERY_NEW_BLOCK

        pinned = sorted(k for k in os.environ if k.startswith("COMETBFT_TPU_"))
        if pinned:
            raise RuntimeError(f"{pinned} set: the node runs on its defaults")
        t = time.monotonic()
        shutil.rmtree(self.home, ignore_errors=True)
        if cli_main(["--home", self.home, "init"]) != 0:
            raise RuntimeError("cmd init failed")
        self.rpc_addr = f"tcp://127.0.0.1:{_free_port()}"
        cfg = _config(Namespace(
            home=self.home, rpc_laddr=self.rpc_addr,
            p2p_laddr=f"tcp://127.0.0.1:{_free_port()}",
        ))
        if cfg.base.db_backend != "file":
            raise RuntimeError("the stores are not file-backed")
        self.n_tx = max(1, int(round(self.mix["rate"] * seconds)))
        self._spawn_child()
        self.node = default_new_node(cfg)
        self.node.start()
        self.sub = self.node.event_bus.subscribe(
            "benchmark", QUERY_NEW_BLOCK, capacity=10_000
        )
        self.blocks: list = []  # (seen at, height, [tx bytes])
        self._watch = threading.Thread(
            target=self._watch_blocks, name="bench-blocks", daemon=True
        )
        self._watch.start()
        t = self.marks.add("cmd init, node boot", t)
        self._warm(seconds)
        self.marks.add("warm-up blocks", t)
        if self.child.stdout.readline().strip() != "ready":
            raise RuntimeError("the tx generator did not come up")

    def _spawn_child(self) -> None:
        cpus = sorted(os.sched_getaffinity(0))
        child_cpus = []
        if len(cpus) >= 4:
            # the generator gets the last two CPUs, the node the others
            child_cpus = cpus[-2:]
            os.sched_setaffinity(0, cpus[:-2])
        self.child_cpus = child_cpus
        job = {
            "root": spec.ROOT, "rpc": self.rpc_addr, "seed": self.seed,
            "tx_bytes": self.cfg["tx_bytes"], "rate": self.mix["rate"],
            "n": self.n_tx, "cpus": child_cpus,
        }
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # never reached: no jax
        self.child = subprocess.Popen(
            [sys.executable, os.path.abspath(txgen.__file__), json.dumps(job)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
        )

    def _watch_blocks(self) -> None:
        while not self.sub.canceled.is_set():
            try:
                msg = self.sub.out.get(timeout=0.2)
            except Exception:  # queue.Empty: look at the cancel flag again
                continue
            seen = time.monotonic()
            block = msg.data.block
            self.blocks.append(
                (seen, block.header.height, [bytes(t) for t in block.data.txs])
            )

    def _warm(self, seconds: float) -> None:
        """Blocks of the sizes the window will carry, sent by this process
        before the window: the hash plane's shapes (tx keys, part-set
        leaves, Merkle levels) compile in its background worker while the
        host serves; then wait for that worker to go idle."""
        from cometbft_tpu.ops import sha256 as osha
        from cometbft_tpu.ops import verify as ov
        from cometbft_tpu.rpc.client import HTTPClient

        # the FSM stages its validator's key table off-thread at the first
        # round; loading that builder takes ~10 s, so it is waited for here
        # (the same public call, a no-op once the table is resident)
        from cometbft_tpu.crypto import batch as crypto_batch

        stager = threading.Thread(
            target=crypto_batch.prestage_validators,
            args=(self.node.state.validators,), name="bench-prestage",
        )
        stager.start()
        client = HTTPClient(self.rpc_addr, timeout=30.0)
        per_block = self.mix["rate"] * self.mix["warmup_block_s"]
        seq = 10_000_000  # outside the window's sequence numbers
        for factor in self.mix["warmup_blocks"]:
            h0 = self.node.block_store.height()
            for _ in range(max(1, int(round(per_block * factor)))):
                tx = txgen.make_tx(self.seed, seq, self.cfg["tx_bytes"])
                client.call("broadcast_tx_sync",
                            tx=base64.b64encode(tx).decode())
                seq += 1
            deadline = time.monotonic() + 30
            while (self.node.mempool.size() > 0
                   or self.node.block_store.height() <= h0):
                if time.monotonic() > deadline:
                    raise RuntimeError("warm-up txs were not committed")
                time.sleep(0.05)
            if not (osha.WARM.wait_idle(600) and ov.WARM.wait_idle(600)):
                raise RuntimeError("background compiles did not finish")
        stager.join(timeout=600)
        if stager.is_alive():
            raise RuntimeError("the validator's key table was not staged")

    def counters(self) -> dict:
        out = {}
        if self.node is not None:
            hp, vc = self.node.hash_plane, self.node.verify_coalescer
            if hp is not None:
                out["hash_plane"] = {
                    "windows": hp.windows, "device_windows": hp.device_windows,
                    "cold_buckets": hp.cold_buckets, "trips": hp.trips,
                }
            if vc is not None:
                out["coalescer"] = {
                    "windows": vc.windows, "device_windows": vc.device_windows,
                    "cold_windows": vc.cold_windows, "trips": vc.trips,
                }
        return out

    # -- the measured window ---------------------------------------------

    def run_window(self, seconds: float) -> dict:
        rate, n = self.mix["rate"], self.n_tx
        size = self.cfg["tx_bytes"]
        self.txs = [txgen.make_tx(self.seed, i, size) for i in range(n)]
        seq_of = {t: i for i, t in enumerate(self.txs)}
        h_start = self.node.block_store.height()
        self.tracer.start()
        t0 = time.monotonic() + 0.05
        self.child.stdin.write(f"{t0!r}\n")
        self.child.stdin.flush()
        t_end = t0 + seconds
        time.sleep(max(0.0, t_end - time.monotonic()))
        budget = self._budget(h_start)  # before the ring moves on
        self.tracer.stop()
        line = self.child.stdout.readline()  # the generator has sent all
        self.child.wait(timeout=60)
        sends = json.loads(line)
        acked = {i for i, _s, _a, code in sends if code == 0}
        # bounded drain: txs due inside the window may still be in flight
        drain_end = time.monotonic() + self.mix["drain_seconds"]
        committed: dict[int, tuple] = {}
        while True:
            for seen, height, btxs in list(self.blocks):
                for t in btxs:
                    i = seq_of.get(t)
                    if i is not None and i not in committed:
                        committed[i] = (seen, height)
            if acked <= set(committed) or time.monotonic() > drain_end:
                break
            time.sleep(0.05)
        lat_ms, ack_ms, late_ms, failed = [], [], [], 0
        drain_ms = (seconds + self.mix["drain_seconds"]) * 1e3
        for i, sent, ack, code in sends:
            due = t0 + i / rate
            late_ms.append((sent - due) * 1e3)
            if code == 0:
                ack_ms.append((ack - sent) * 1e3)
            if code == 0 and i in committed:
                lat_ms.append((committed[i][0] - due) * 1e3)
            else:
                failed += 1
                lat_ms.append(drain_ms)
        heights = sorted({h for _s, h in committed.values()})
        in_window = [
            (seen, h) for seen, h, _t in self.blocks if t0 <= seen <= t_end
        ]
        return {
            "end_to_end": {"tx_commit_p95_ms": stats.percentile(lat_ms, 95)},
            "attempted": n,
            "failed": failed,
            "sends": sends, "acked": acked, "committed": committed,
            "h_start": h_start,
            "stats": {
                "tx_commit_p50_ms": stats.percentile(lat_ms, 50),
                "rpc_ack_p50_ms": stats.percentile(ack_ms, 50),
                "rpc_ack_p95_ms": stats.percentile(ack_ms, 95),
                "generator_late_p95_ms": stats.percentile(late_ms, 95),
                "generator_late_max_ms": max(late_ms, default=None),
                "heights_in_window": len(in_window),
                "height_ms": (
                    1e3 * (in_window[-1][0] - in_window[0][0])
                    / (len(in_window) - 1) if len(in_window) > 1 else None
                ),
                "committed_tx_per_s": sum(
                    1 for s, _h in committed.values() if s <= t_end
                ) / seconds,
                **budget,
            },
            "notes": {"child_cpus": self.child_cpus,
                      "heights_with_window_txs": len(heights)},
        }

    def _budget(self, h_start: int) -> dict:
        """The program's own per-height budget (libs/health) over the
        window's heights that its flight ring still holds when the window
        closes (the ring keeps 4,096 events, some seconds of a loaded
        node)."""
        from cometbft_tpu.libs import health

        rows = [r for r in health.budget()["heights"]
                if r.get("height", 0) > h_start]
        if not rows:
            return {}
        n = len(rows)
        return {
            "wal_fsync_ms_per_height":
                1e3 * sum(r["stages"]["wal_fsync"] for r in rows) / n,
            "apply_ms_per_height":
                1e3 * sum(r["stages"]["apply"] for r in rows) / n,
            "budget_height_ms": 1e3 * sum(r["latency_s"] for r in rows) / n,
        }

    def close(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
            self.child.wait(timeout=10)
        if self.sub is not None:
            self.sub.canceled.set()
        if self.node is not None:
            try:
                self.node.stop()
            finally:
                self.node = None
                shutil.rmtree(self.home, ignore_errors=True)

    # -- correctness -----------------------------------------------------

    def check(self, window: dict, control: str, ctx) -> dict:
        """Every acknowledged tx is committed and read back; ``data_hash``
        and the part-set header of every block equal a hashlib / RFC-6962
        recomputation. ``control`` breaks one guarantee in what is read."""
        from cometbft_tpu.rpc.client import HTTPClient

        # the node stays up for this: the comparison reads its stores and
        # asks its RPC server; run.py closes it afterwards
        store = self.node.block_store
        acked, committed = window["acked"], window["committed"]
        top = store.height()
        found: dict[int, int] = {}
        hash_bad = foreign = 0
        seq_of = {t: i for i, t in enumerate(self.txs)}
        for h in range(window["h_start"] + 1, top + 1):
            blk = store.load_block(h)
            meta = store.load_block_meta(h)
            btxs = [bytes(t) for t in blk.data.txs]
            if control == "lose_acked":
                btxs = [t for t in btxs if seq_of.get(t, 1) % 50 != 0]
            want = rfc6962.data_hash(btxs)
            if control == "flat_hash":
                want = hashlib.sha256(b"".join(btxs)).digest()
            if blk.header.data_hash != want and control != "lose_acked":
                hash_bad += 1
            psh = meta.block_id.part_set_header
            parts = [bytes(store.load_block_part(h, i).bytes_)
                     for i in range(psh.total)]
            if psh.hash != rfc6962.root(parts):
                hash_bad += 1
            for t in btxs:
                i = seq_of.get(t)
                if i is None:
                    foreign += 1  # a tx nobody sent
                else:
                    found[i] = h
        lost = sorted(i for i in acked if i not in found)
        moved = sum(1 for i, (_s, h) in committed.items() if found.get(i) != h)
        # a seeded sample through the application: the value is there
        rpc = HTTPClient(self.rpc_addr, timeout=30.0)
        rng = random.Random(self.seed + 1)
        pool = sorted(set(acked) - set(lost))
        sample = rng.sample(pool, min(200, len(pool)))
        app_bad = 0
        for i in sample:
            key, _, value = self.txs[i].partition(b"=")
            res = rpc.call("abci_query", data=key.hex())["response"]
            if res["code"] != 0 or base64.b64decode(res["value"]) != value:
                app_bad += 1
        window["notes"].update(
            blocks_checked=top - window["h_start"], sampled=len(sample),
            lost_first=lost[:5],
        )
        return {
            "acked_not_committed": {"value": len(lost), "limit": 0},
            "block_hash_mismatches": {"value": hash_bad, "limit": 0},
            "foreign_or_moved_txs": {"value": foreign + moved, "limit": 0},
            "app_readback_mismatches": {"value": app_bad, "limit": 0},
        }
