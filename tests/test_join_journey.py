"""The full 'new node joins the network' journey at process level
(reference: the e2e runner's stateSync node archetype — a node given only
a seed address discovers peers via PEX, bootstraps state via statesync
from two RPC witnesses, block-syncs the tail, and follows consensus;
node/setup.go:476 startStateSync + p2p/pex discovery + blocksync bridge).
"""

import dataclasses
import json
import os
import socket
import time
import urllib.request

import pytest

from cometbft_tpu.e2e import Testnet

_MS = 1_000_000


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _free_port_block() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        base = s.getsockname()[1]
    return base if base + 10 < 65000 else 21000


def _rpc(addr: str, method: str, **params):
    req = urllib.request.Request(
        f"http://{addr.replace('tcp://', '')}/",
        data=json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": method, "params": params}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        body = json.load(r)
    if "error" in body:
        raise RuntimeError(body["error"])
    return body["result"]


def _speed_up(path: str) -> None:
    from cometbft_tpu import config_file

    cfg = config_file.load_toml(path)
    cfg.consensus = dataclasses.replace(
        cfg.consensus,
        timeout_propose_ns=500 * _MS,
        timeout_prevote_ns=250 * _MS,
        timeout_precommit_ns=250 * _MS,
        timeout_commit_ns=200 * _MS,
        skip_timeout_commit=False,
        create_empty_blocks=True,
    )
    config_file.save_toml(cfg, path)
    return cfg


@pytest.mark.slow
def test_join_via_seed_and_statesync(tmp_path):
    from cometbft_tpu import config_file
    from cometbft_tpu.config import default_config
    from cometbft_tpu.e2e.runner import ProcessNode
    from cometbft_tpu.node import init_files
    from cometbft_tpu.p2p import NodeKey
    from cometbft_tpu.privval import FilePV

    port = _free_port_block()
    net = Testnet.generate(str(tmp_path / "net"), 2, port)
    for node in net.nodes:
        _speed_up(os.path.join(node.home, "config", "config.toml"))
        node.env = _env()
    net.start()
    joiner = None
    try:
        assert all(n.wait_rpc(60.0) for n in net.nodes)
        # grow past a snapshot height (kvstore snapshots every 5)
        assert net.wait_all_height(12, 120.0), "validators too slow"

        # subjective trust root from the running chain
        trust_h = 5
        blk = _rpc(net.nodes[0].rpc_addr, "block", height=trust_h)
        trust_hash = blk["block_id"]["hash"]

        # the joiner knows ONLY the seed (node0) — no persistent peers
        seed_nk = NodeKey.load_or_generate(
            os.path.join(net.nodes[0].home, "config", "node_key.json")
        )
        seed_addr = f"{seed_nk.node_id}@127.0.0.1:{port}"

        jhome = str(tmp_path / "joiner")
        cfg = default_config()
        cfg.base.home = jhome
        cfg.p2p.laddr = f"tcp://127.0.0.1:{port + 6}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{port + 7}"
        init_files(cfg)
        # same chain: share the testnet's genesis, drop the generated one
        with open(
            os.path.join(net.nodes[0].home, "config", "genesis.json")
        ) as f:
            genesis_doc = f.read()
        with open(os.path.join(jhome, "config", "genesis.json"), "w") as f:
            f.write(genesis_doc)
        cfg = _speed_up(os.path.join(jhome, "config", "config.toml"))
        cfg.base.home = jhome
        cfg.p2p.seeds = seed_addr
        cfg.p2p.persistent_peers = ""
        cfg.statesync = dataclasses.replace(
            cfg.statesync,
            enable=True,
            rpc_servers=[
                f"http://{n.rpc_addr.replace('tcp://', '')}"
                for n in net.nodes
            ],
            trust_height=trust_h,
            trust_hash=trust_hash,
        )
        config_file.save_toml(
            cfg, os.path.join(jhome, "config", "config.toml")
        )

        joiner = ProcessNode(
            home=jhome, rpc_addr=f"tcp://127.0.0.1:{port + 7}", env=_env()
        )
        joiner.start()
        assert joiner.wait_rpc(90.0), (
            "joiner RPC never came up\n" + joiner.log_tail(3000)
        )

        # the journey: discover via seed -> statesync -> blocksync ->
        # consensus. Done when the joiner tracks the validators' tip.
        deadline = time.monotonic() + 180
        caught_up = False
        while time.monotonic() < deadline:
            try:
                st = _rpc(joiner.rpc_addr, "status")
                jh = int(st["sync_info"]["latest_block_height"])
                vh = net.nodes[0].height()
                if jh >= max(vh - 2, 8) and not st["sync_info"][
                    "catching_up"
                ]:
                    caught_up = True
                    break
            except Exception:
                pass
            time.sleep(0.5)
        assert caught_up, (
            f"joiner never caught up\n--- joiner log ---\n"
            + joiner.log_tail(4000)
        )

        # statesync (not genesis replay) bootstrapped it: early blocks
        # were never fetched
        with pytest.raises(RuntimeError):
            _rpc(joiner.rpc_addr, "block", height=2)

        # and it agrees with the validators at a common height
        h = min(joiner.height(), net.nodes[0].height()) - 1
        assert joiner.app_hash_at(h) == net.nodes[0].app_hash_at(h)
    finally:
        if joiner is not None:
            joiner.stop()
        net.stop()
