"""Process-level testnet runner with perturbations
(reference: test/e2e/runner — main.go orchestration, perturb.go:16-31
{disconnect, kill, pause, restart}, tests/ invariant checks).

Containers are replaced by child processes of ``cometbft-tpu start``:

  kill    -> SIGKILL + restart          (docker kill / start)
  pause   -> SIGSTOP ... SIGCONT        (docker pause / unpause)
  restart -> SIGTERM + restart          (docker restart)

The ``disconnect`` perturbation (perturb.go's docker network
disconnect) is realized WITHOUT root: a relayed testnet routes every
inter-node TCP link through an in-runner :class:`LinkRelay` the runner
can sever (drop live connections, refuse new ones) and heal. PEX is
disabled in relayed nets so nodes only ever dial the configured
(relayed) addresses — a learned direct address would tunnel under the
partition. Finer link faults (drop/duplicate/reorder of individual
messages) remain in the in-process tier (FuzzedConnection,
tests/test_fault_injection.py).

Invariant checks after perturbations mirror test/e2e/tests/block_test.go:
all nodes agree on the app hash at every common height, and heights
keep advancing.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import threading
from ..libs import sync as libsync
import time

from ..rpc.client import HTTPClient


class LinkRelay:
    """Severable TCP forwarder for ONE directed peer link.

    The process-tier analog of `docker network disconnect`
    (test/e2e/runner/perturb.go:16-31): while severed, established
    connections are torn down and new dials are accepted-then-closed, so
    the dialer sees a live listener with a dead peer — the same
    observable as a dropped container link, without root.
    """

    def __init__(self, target_host: str, target_port: int):
        self._target = (target_host, target_port)
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(16)
        self.port = self._lsock.getsockname()[1]
        self._severed = threading.Event()
        self._closed = False
        self._conns: set[socket.socket] = set()
        self._mtx = libsync.Mutex("e2e.runner._mtx")
        threading.Thread(
            target=self._accept_loop, name=f"relay-{self.port}", daemon=True
        ).start()

    def _accept_loop(self) -> None:
        while True:
            try:
                client, _ = self._lsock.accept()
            except OSError:
                return  # listener closed
            if self._severed.is_set():
                client.close()
                continue
            try:
                upstream = socket.create_connection(self._target, timeout=5)
            except OSError:
                client.close()
                continue
            with self._mtx:
                # re-check under the same lock sever() snapshots with: a
                # dial that raced past the first check must not survive
                # the partition
                if self._severed.is_set():
                    client.close()
                    upstream.close()
                    continue
                self._conns.update((client, upstream))
            for a, b in ((client, upstream), (upstream, client)):
                threading.Thread(
                    target=self._pump, args=(a, b), daemon=True
                ).start()

    def _pump(self, src: socket.socket, dst: socket.socket) -> None:
        try:
            while True:
                data = src.recv(65536)
                if not data:
                    break
                dst.sendall(data)
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.close()
                except OSError:
                    pass
            with self._mtx:
                self._conns.discard(src)
                self._conns.discard(dst)

    def sever(self) -> None:
        self._severed.set()
        with self._mtx:
            conns = list(self._conns)
            self._conns.clear()
        for s in conns:
            try:
                s.close()
            except OSError:
                pass

    def heal(self) -> None:
        self._severed.clear()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.sever()
        try:
            self._lsock.close()
        except OSError:
            pass


def child_env(chip_owner: bool = False) -> dict:
    """Environment of one ``cometbft-tpu start`` child.

    A chip belongs to one process. Every child is pinned to the CPU
    (``JAX_PLATFORMS=cpu``) except the one the launcher names as the
    chip's owner, which keeps the launcher's own setting — so which
    node gets the device is stated, never a race between children that
    each probe it in ``auto`` and go host-only when they lose. The
    launcher itself must stay off jax while an owner child runs.
    """
    env = dict(os.environ)
    if not chip_owner:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class ProcessNode:
    """One ``cometbft-tpu start`` child process + its home dir."""

    def __init__(
        self,
        home: str,
        rpc_addr: str,
        env: dict | None = None,
        chip_owner: bool = False,
    ):
        self.home = home
        self.rpc_addr = rpc_addr
        self.env = env if env is not None else child_env(chip_owner)
        self.proc: subprocess.Popen | None = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        assert self.proc is None or self.proc.poll() is not None
        # Logs go to a file, not a pipe: an undrained 64 KB pipe buffer
        # would freeze a chatty node mid-run (the docker tier's log-driver
        # role). Append mode keeps pre-restart history.
        self.log_path = os.path.join(self.home, "node.log")
        self._log_f = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "cometbft_tpu.cmd",
                "--home",
                self.home,
                "start",
            ],
            stdout=self._log_f,
            stderr=subprocess.STDOUT,
            env=self.env,
        )

    def stop(self, timeout: float = 10.0) -> None:
        if self.proc is None or self.proc.poll() is not None:
            self._close_log()
            return
        self.proc.terminate()
        try:
            self.proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.communicate(timeout=timeout)
        self._close_log()

    def _close_log(self) -> None:
        f = getattr(self, "_log_f", None)
        if f is not None and not f.closed:
            f.close()

    def log_tail(self, n_bytes: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, 2)
                f.seek(max(0, f.tell() - n_bytes))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    # -- perturbations (perturb.go:16-31) ----------------------------------

    def kill(self) -> None:
        """SIGKILL: no cleanup, no flushes — crash semantics."""
        assert self.proc is not None
        self.proc.kill()
        self.proc.communicate(timeout=10)
        self._close_log()

    def pause(self) -> None:
        """SIGSTOP: the node freezes mid-whatever (docker pause)."""
        assert self.proc is not None and self.proc.poll() is None
        os.kill(self.proc.pid, signal.SIGSTOP)

    def unpause(self) -> None:
        os.kill(self.proc.pid, signal.SIGCONT)

    def restart(self) -> None:
        self.stop()
        self.start()

    def upgrade(self, version: str, config_mutator=None) -> None:
        """The ``upgrade`` perturbation (runner/perturb.go:16-31): clean
        stop, swap the "image" — here the advertised software version
        (env override) plus optional config changes the new version
        ships — and start over the SAME data dir. Chain continuity is
        the caller's invariant: the node must handshake-replay its
        store, rejoin, and keep signing."""
        self.stop()
        self.env = dict(self.env)
        self.env["COMETBFT_TPU_SOFTWARE_VERSION"] = version
        if config_mutator is not None:
            from ..config_file import load_toml, save_toml

            path = os.path.join(self.home, "config", "config.toml")
            cfg = load_toml(path)
            cfg.base.home = self.home
            config_mutator(cfg)
            save_toml(cfg, path)
        self.start()

    def advertised_version(self) -> str:
        return self.client().call("status")["node_info"]["version"]

    # -- observation -------------------------------------------------------

    def client(self) -> HTTPClient:
        return HTTPClient(self.rpc_addr)

    def height(self) -> int:
        st = self.client().call("status")
        return int(st["sync_info"]["latest_block_height"])

    def app_hash_at(self, height: int) -> str:
        blk = self.client().call("block", height=height)
        return blk["block"]["header"]["app_hash"]

    def wait_rpc(self, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                self.client().call("health")
                return True
            except Exception:
                time.sleep(0.3)
        return False

    def wait_height(self, target: int, timeout: float = 60.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                if self.height() >= target:
                    return True
            except Exception:
                pass
            time.sleep(0.3)
        return False


class Testnet:
    """N ProcessNodes over home dirs laid out by ``cometbft-tpu testnet``
    (cmd/__main__.py cmd_testnet; reference testnet.go)."""

    __test__ = False  # not a pytest class despite the name

    def __init__(
        self,
        out_dir: str,
        n_vals: int,
        starting_port: int,
        chip_owner: int | None = None,
    ):
        """``chip_owner`` names the ONE node index allowed to open the
        accelerator; every other child runs ``JAX_PLATFORMS=cpu``
        (:func:`child_env`). None = an all-CPU net."""
        self.out_dir = out_dir
        self.starting_port = starting_port
        self.relays: dict[tuple[int, int], LinkRelay] = {}
        self.nodes = [
            ProcessNode(
                home=os.path.join(out_dir, f"node{i}"),
                rpc_addr=f"tcp://127.0.0.1:{starting_port + 2 * i + 1}",
                chip_owner=(i == chip_owner),
            )
            for i in range(n_vals)
        ]

    @classmethod
    def generate(
        cls,
        out_dir: str,
        n_vals: int,
        starting_port: int,
        chip_owner: int | None = None,
    ) -> "Testnet":
        from ..cmd.__main__ import main as cli_main

        rc = cli_main(
            [
                "testnet",
                "--v",
                str(n_vals),
                "--o",
                out_dir,
                "--starting-port",
                str(starting_port),
            ]
        )
        if rc != 0:
            raise RuntimeError("testnet generation failed")
        return cls(out_dir, n_vals, starting_port, chip_owner)

    @classmethod
    def generate_randomized(
        cls, out_dir: str, seed: int, starting_port: int
    ) -> "Testnet":
        """Seeded randomized-manifest generator (the reference's
        ``e2e generator``, test/e2e/README.md:36-60 + pkg/testnet.go):
        draws validator count, consensus timeouts, topology (full mesh
        vs ring of persistent peers, PEX on/off), storage backend and
        block-production mode from ``seed``, writes the manifest next to
        the node homes for reproduction, and post-edits each generated
        config accordingly."""
        import json
        import random

        from ..config_file import load_toml, save_toml

        rng = random.Random(seed)
        n_vals = rng.choice([2, 3, 4])
        manifest = {
            "seed": seed,
            "validators": n_vals,
            "topology": rng.choice(["mesh", "ring"]),
            "pex": rng.random() < 0.5,
            "db_backend": rng.choice(["file", "native"]),
            "timeout_commit_ms": rng.choice([100, 250, 500]),
            "timeout_propose_ms": rng.choice([400, 800]),
            "create_empty_blocks": rng.random() < 0.8,
        }
        net = cls.generate(out_dir, n_vals, starting_port)
        with open(os.path.join(out_dir, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        ms = 1_000_000
        for i, node in enumerate(net.nodes):
            path = os.path.join(node.home, "config", "config.toml")
            cfg = load_toml(path)
            cfg.base.home = node.home
            cfg.base.db_backend = manifest["db_backend"]
            cfg.p2p.pex = manifest["pex"]
            if manifest["topology"] == "ring":
                # keep only the next node as a persistent peer; gossip
                # still reaches everyone around the ring
                peers = cfg.p2p.persistent_peers.split(",")
                cfg.p2p.persistent_peers = peers[i % len(peers)]
            import dataclasses

            cfg.consensus = dataclasses.replace(
                cfg.consensus,
                timeout_commit_ns=manifest["timeout_commit_ms"] * ms,
                timeout_propose_ns=manifest["timeout_propose_ms"] * ms,
                create_empty_blocks=manifest["create_empty_blocks"],
            )
            save_toml(cfg, path)
        net.manifest = manifest
        return net

    @classmethod
    def generate_relayed(
        cls, out_dir: str, n_vals: int, starting_port: int
    ) -> "Testnet":
        """A testnet whose every inter-node p2p link runs through a
        severable :class:`LinkRelay` — the `disconnect` perturbation's
        substrate. One relay per DIRECTED pair (i dials j), so a single
        node can be partitioned without touching third-party links. PEX
        is disabled: learned direct addresses would bypass the relays.
        """
        from ..config_file import load_toml, save_toml

        net = cls.generate(out_dir, n_vals, starting_port)
        port_to_idx = {
            starting_port + 2 * j: j for j in range(n_vals)
        }
        for i, node in enumerate(net.nodes):
            path = os.path.join(node.home, "config", "config.toml")
            cfg = load_toml(path)
            cfg.base.home = node.home
            cfg.p2p.pex = False
            rewritten = []
            for entry in cfg.p2p.persistent_peers.split(","):
                if not entry:
                    continue
                pid, addr = entry.split("@", 1)
                host, port_s = addr.rsplit(":", 1)
                j = port_to_idx[int(port_s)]
                relay = net.relays.get((i, j))
                if relay is None:
                    relay = LinkRelay(host, int(port_s))
                    net.relays[(i, j)] = relay
                rewritten.append(f"{pid}@127.0.0.1:{relay.port}")
            cfg.p2p.persistent_peers = ",".join(rewritten)
            save_toml(cfg, path)
        return net

    def partition(self, idx: int) -> None:
        """Sever every link to/from node ``idx`` (perturb.go disconnect)."""
        for (i, j), relay in self.relays.items():
            if idx in (i, j):
                relay.sever()

    def heal(self, idx: int) -> None:
        """Re-enable node ``idx``'s links (the reference reconnects after
        10 s; healing is the caller's schedule here)."""
        for (i, j), relay in self.relays.items():
            if idx in (i, j):
                relay.heal()

    def start(self) -> None:
        for n in self.nodes:
            n.start()

    def stop(self) -> None:
        for n in self.nodes:
            try:
                n.stop()
            except Exception:
                pass
        for relay in self.relays.values():
            relay.close()

    def live_nodes(self) -> list[ProcessNode]:
        return [
            n
            for n in self.nodes
            if n.proc is not None and n.proc.poll() is None
        ]

    def wait_all_height(self, target: int, timeout: float = 90.0) -> bool:
        deadline = time.monotonic() + timeout
        return all(
            n.wait_height(target, max(deadline - time.monotonic(), 0.1))
            for n in self.live_nodes()
        )

    # -- invariants (test/e2e/tests/block_test.go) -------------------------

    def check_app_hash_agreement(self, up_to: int | None = None) -> None:
        """Every node reports the same app hash at every common height."""
        nodes = self.live_nodes()
        if len(nodes) < 2:
            return
        common = min(n.height() for n in nodes)
        if up_to is not None:
            common = min(common, up_to)
        for h in range(1, common + 1):
            hashes = {n.app_hash_at(h) for n in nodes}
            if len(hashes) != 1:
                raise AssertionError(
                    f"app hash divergence at height {h}: {hashes}"
                )

    def check_progress(self, blocks: int = 2, timeout: float = 60.0) -> None:
        """Chain must advance ``blocks`` beyond the current max height."""
        start = max(n.height() for n in self.live_nodes())
        if not self.wait_all_height(start + blocks, timeout):
            # diagnostics only: a node whose RPC is hung (often the very
            # reason progress stalled) must not turn the curated error
            # into a raw network traceback
            def safe_height(n):
                try:
                    return n.height()
                except Exception:
                    return -1

            nodes = self.live_nodes()
            heights = [safe_height(n) for n in nodes]
            lagger = nodes[heights.index(min(heights))]
            raise AssertionError(
                f"no progress: stuck at {heights} (wanted {start + blocks};"
                f" -1 = RPC unreachable)\n"
                f"--- slowest node log tail ({lagger.home}) ---\n"
                f"{lagger.log_tail(3000)}"
            )


# -- simnet mode (no sockets, no subprocesses) ---------------------------
#
# The process tier above runs REAL nodes and real TCP — slow,
# wall-clock, nondeterministic. `--simnet` runs the same scenario
# intents on the deterministic in-process plane (cometbft_tpu/simnet):
# seeded virtual links, scripted faults, bit-reproducible runs. A
# failing CI run prints its seed; `--seed N` replays that exact
# schedule locally. Default seed: COMETBFT_TPU_SIMNET_SEED.


def run_simnet_load(
    seed: int, n_nodes: int = 4, rate: int = 200, heights: int = 6,
    burst: int = 1,
) -> dict:
    """Scenario-less simnet load run: N validators, a virtual-rate tx
    stream, a block-walk latency report — the loadtime shape without a
    socket in sight.  ``burst`` > 1 is the sustained mempool-STORM
    mode: burst txs per tick at the same aggregate rate, so storms in
    the thousands of tx/s stay tractable on the event heap."""
    from ..simnet import SimNet
    from .load import SimLoadGenerator, sim_load_report

    net = SimNet(n_nodes, seed=seed)
    try:
        net.start()
        gen = SimLoadGenerator(
            net, rate=rate, burst=burst, run_id=f"sim{seed}"
        )
        if burst > 1:
            net.mark_storm(rate)
        gen.start()
        ok = net.run_until_height(heights, max_virtual_ms=240_000)
        gen.stop()
        net.run(max_virtual_ms=500)  # let in-flight commits land
        net.assert_no_fork()
        rep = sim_load_report(net, gen.run_id)
        return {
            "ok": ok and rep.txs > 0,
            "seed": seed,
            "node_heights": net.heights(),
            "sent": gen.sent,
            # rep.summary()'s "heights" = [first, last] height carrying
            # load txs (the loadtime report shape), NOT node heights
            **rep.summary(),
        }
    finally:
        net.stop()


def main(argv=None) -> int:
    """CLI: ``python -m cometbft_tpu.e2e.runner --simnet ...``."""
    import argparse
    import json

    ap = argparse.ArgumentParser(prog="python -m cometbft_tpu.e2e.runner")
    ap.add_argument(
        "--simnet", action="store_true",
        help="run on the deterministic in-process simnet plane",
    )
    ap.add_argument("--scenario", default="healthy")
    ap.add_argument(
        "--seed", type=int,
        default=int(os.environ.get("COMETBFT_TPU_SIMNET_SEED", "0") or "0"),
        help="schedule seed — reproduces a failing run bit-identically",
    )
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument(
        "--load", type=int, default=0, metavar="RATE",
        help="simnet load mode: tx/s of virtual time instead of a "
        "fault scenario",
    )
    ap.add_argument(
        "--burst", type=int, default=1, metavar="N",
        help="txs pushed per load tick (storm mode: thousands of tx/s "
        "at rate/burst scheduler events per virtual second)",
    )
    args = ap.parse_args(argv)
    if not args.simnet:
        ap.error(
            "the process tier is driven from pytest "
            "(tests/test_e2e_harness.py); the CLI runs --simnet only"
        )
    if args.load:
        out = run_simnet_load(
            args.seed, n_nodes=args.nodes or 4, rate=args.load,
            burst=args.burst,
        )
        print(json.dumps(out, default=str, indent=1))
        return 0 if out["ok"] else 1
    from ..simnet.scenarios import run_scenario

    kw = {}
    if args.nodes is not None:
        kw["n_nodes"] = args.nodes
    result = run_scenario(args.scenario, args.seed, **kw)
    print(json.dumps(result.summary(), default=str, indent=1))
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
