"""Node assembly (reference: node/node.go:138 NewNode, node/setup.go).

Wiring order mirrors the reference: DBs → state → proxy app (4 conns) →
event bus → handshake (app replay) → mempool → consensus → RPC/p2p (as
those layers land). ``Node.start`` boots services in dependency order;
``stop`` unwinds them.
"""

from __future__ import annotations

import json
import os
import threading

from .. import proxy
from ..abci.kvstore import KVStoreApplication
from ..blocksync import BlocksyncReactor
from ..config import Config
from ..consensus import ConsensusState
from ..consensus.reactor import ConsensusReactor
from ..consensus.replay import Handshaker
from ..consensus.wal import WAL
from ..evidence import EvidencePool, EvidenceReactor
from ..libs import db as dbm
from ..libs.service import BaseService
from ..mempool import CListMempool
from ..mempool.reactor import MempoolReactor
from ..p2p import MultiplexTransport, NodeInfo, NodeKey, Switch
from ..p2p.conn.connection import MConnConfig
from ..privval import FilePV
from ..state import BlockExecutor, Store, make_genesis_state
from ..store import BlockStore
from ..types import GenesisDoc
from ..types.event_bus import EventBus


def init_files(config: Config) -> dict:
    """``cometbft init`` (cmd/cometbft/commands/init.go): write config dir,
    node key, validator key, and a single-validator genesis if absent."""
    home = os.path.expanduser(config.base.home)
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)

    pv_key_file = config.base.resolve(config.base.priv_validator_key_file)
    pv_state_file = config.base.resolve(config.base.priv_validator_state_file)
    pv = FilePV.load_or_generate(pv_key_file, pv_state_file)

    # durable config (config/toml.go WriteConfigFile): written once so
    # operators edit a file, not code
    from ..config_file import save_toml

    toml_path = config.base.resolve("config/config.toml")
    if not os.path.exists(toml_path):
        save_toml(config, toml_path)

    genesis_file = config.base.resolve(config.base.genesis_file)
    created_genesis = False
    if not os.path.exists(genesis_file):
        from ..types import GenesisValidator

        doc = GenesisDoc(
            chain_id=f"test-chain-{os.urandom(3).hex()}",
            validators=[
                GenesisValidator(pub_key=pv.get_pub_key(), power=10)
            ],
        )
        doc.validate_and_complete()
        with open(genesis_file, "w") as f:
            f.write(doc.to_json())
        created_genesis = True
    return {
        "pv": pv,
        "genesis_file": genesis_file,
        "created_genesis": created_genesis,
    }


def load_genesis(config: Config) -> GenesisDoc:
    with open(config.base.resolve(config.base.genesis_file)) as f:
        return GenesisDoc.from_json(f.read())


def _make_db(config: Config, name: str) -> dbm.DB:
    if config.base.db_backend == "mem":
        return dbm.MemDB()
    data_dir = config.base.resolve("data")
    path = os.path.join(data_dir, f"{name}.db")
    if config.base.db_backend == "native":
        # C++ engine (the cgo-backend tier of cometbft-db). An unusable
        # backend is FATAL, not a fallback: silently writing FileDB
        # format under a db_backend=native config would poison every
        # offline tool that later trusts the config (compacting a
        # foreign-format file erases it). Reference behavior: the node
        # refuses to start when the configured backend can't open.
        from ..libs.db_native import NativeDB

        return NativeDB(path)
    return dbm.FileDB(path)


def _app_client_creator(config: Config, app_db: dbm.DB):
    """proxy/client.go DefaultClientCreator."""
    pa = config.base.proxy_app
    if pa in ("kvstore", "persistent_kvstore"):
        return proxy.local_client_creator(KVStoreApplication(app_db)), True
    if pa == "noop":
        from ..abci.application import BaseApplication

        return proxy.local_client_creator(BaseApplication()), True
    if pa.startswith("grpc://"):
        return proxy.grpc_client_creator(pa), False
    if pa.startswith(("tcp://", "unix://")):
        return proxy.socket_client_creator(pa), False
    raise ValueError(f"unknown proxy_app {pa!r}")


class Node(BaseService):
    def __init__(self, config: Config, genesis: GenesisDoc, priv_validator):
        super().__init__("node")
        self.config = config
        self.genesis = genesis

        # 0. Observability floor: leveled structured logging + metrics
        # (reference: libs/log + per-package prometheus metrics).
        from ..libs import log as liblog
        from ..libs import metrics as libmetrics

        self.logger = liblog.Logger(
            level=liblog.parse_level(config.base.log_level)
        ).with_fields(chain=genesis.chain_id[:16])
        self.metrics = libmetrics.NodeMetrics()
        libmetrics.push_node_metrics(self.metrics)

        # 1. DBs (setup.go initDBs:107)
        self.app_db = _make_db(config, "app")
        self.block_db = _make_db(config, "blockstore")
        self.state_db = _make_db(config, "state")
        self.block_store = BlockStore(self.block_db)
        self.state_store = Store(self.state_db)

        # 2. State from DB or genesis (setup.go:537)
        state = self.state_store.load()
        if state is None:
            state = make_genesis_state(genesis)
            self.state_store.save(state)

        # 3. Proxy app — 4 connections (setup.go:123)
        creator, _in_process = _app_client_creator(config, self.app_db)
        self.proxy_app = proxy.AppConns(
            creator, on_error=self._on_app_error
        )
        self.proxy_app.start()

        # 4. EventBus (setup.go:132)
        self.event_bus = EventBus()
        self.event_bus.start()

        # 5. Handshake: sync app to store (setup.go:169 doHandshake)
        executor_for_replay = BlockExecutor(
            self.state_store, self.proxy_app.consensus,
            block_store=self.block_store,
        )
        handshaker = Handshaker(
            self.state_store, state, self.block_store, genesis,
            block_exec=executor_for_replay,
        )
        handshaker.handshake(self.proxy_app)
        state = handshaker.state

        # 6. Mempool (setup.go:223)
        self.mempool = CListMempool(
            config.mempool,
            self.proxy_app.mempool,
            height=state.last_block_height,
        )
        if config.consensus.create_empty_blocks is False:
            self.mempool.enable_txs_available()

        # 7. Evidence pool (setup.go:254)
        self.evidence_db = _make_db(config, "evidence")
        self.evidence_pool = EvidencePool(
            self.evidence_db, self.state_store, self.block_store
        )

        # 8. Block executor + consensus (setup.go:254-292)
        self.block_exec = BlockExecutor(
            self.state_store,
            self.proxy_app.consensus,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            block_store=self.block_store,
            event_bus=self.event_bus,
        )
        wal_path = config.base.resolve(config.consensus.wal_file)
        os.makedirs(os.path.dirname(wal_path), exist_ok=True)
        self.consensus = ConsensusState(
            config.consensus,
            state,
            self.block_exec,
            self.block_store,
            tx_notifier=self.mempool,
            evidence_pool=self.evidence_pool,
            event_bus=self.event_bus,
            wal=WAL(wal_path),
        )
        if priv_validator is not None:
            self.consensus.set_priv_validator(priv_validator)
        self.consensus.logger = self.logger.with_module("consensus")
        self.state = state
        self._txs_available_thread: threading.Thread | None = None
        self._last_commit_time = 0.0
        self.consensus.add_block_committed_hook(self._on_block_committed)
        # Commit-chain failures fail-stop the whole node (the reference
        # panics in finalizeCommit) — same posture as _on_app_error.
        self.consensus.on_fatal = self._on_app_error

        # 8b. Pipelined heights (consensus/pipeline.py): speculative
        # execution + ordered commit-writer behind a durability barrier.
        # Knob-gated (COMETBFT_TPU_PIPELINE / COMETBFT_TPU_SPEC_EXEC);
        # the commit-writer fsyncs through the consensus WAL, so it must
        # be wired to the SAME instance the FSM logs to.
        from ..consensus.pipeline import CommitPipeline, pipeline_mode, spec_mode

        pipe = CommitPipeline(
            self.block_exec, self.consensus.wal, on_fatal=self._on_app_error
        )
        pmode = pipeline_mode()
        pipe.enabled = pmode in ("auto", "on", "inline")
        pipe.inline = pmode == "inline"
        smode = spec_mode()
        pipe.spec_enabled = smode == "on" or (
            smode == "auto"
            and getattr(
                self.proxy_app.consensus, "supports_speculation", lambda: False
            )()
        )
        pipe.note_base(state.last_block_height)
        self.block_exec.prune_gate = pipe.durable_height
        self.consensus.pipeline = pipe

        # 9. P2P: transport + switch + reactors (setup.go:325,394)
        self.node_key = NodeKey.load_or_generate(
            config.base.resolve(config.base.node_key_file)
        )
        # Flight-ring origin: every row the consensus receive routine
        # records carries this node's id prefix, so per-node timelines
        # decode even when several nodes share one process (the same
        # prefix the netstats peer label uses on the remote side).
        from ..libs import health as libhealth

        self.consensus.health_origin = libhealth.register_origin(
            self.node_key.node_id[:10]
        )
        # the commit-writer/spec workers record ring rows for the same
        # node as the receive routine
        pipe.health_origin = self.consensus.health_origin
        # Blocksync only when it can help: enabled in config and we're not
        # the sole validator (node.go onlyValidatorIsUs check).
        only_us = (
            priv_validator is not None
            and len(state.validators) == 1
            and state.validators.has_address(
                bytes(priv_validator.get_pub_key().address())
            )
        )
        # Statesync only makes sense for an empty node (node.go:377).
        self.statesync_enabled = (
            config.statesync.enable and state.last_block_height == 0
        )
        run_blocksync = config.base.block_sync and not only_us
        self.consensus_reactor = ConsensusReactor(
            self.consensus, wait_sync=run_blocksync or self.statesync_enabled
        )
        self.blocksync_reactor = BlocksyncReactor(
            state,
            self.block_exec,
            self.block_store,
            # during statesync, blocksync stays parked until the snapshot
            # restore hands it a state (switch_to_block_sync)
            run_blocksync and not self.statesync_enabled,
            consensus_reactor=self.consensus_reactor,
            min_recv_rate=config.blocksync.min_recv_rate,
        )
        if self.statesync_enabled:
            # parked-for-statesync is NOT synced: the constructor pre-sets
            # the event for plain non-blocksync nodes only
            self.blocksync_reactor.synced.clear()
        self.mempool_reactor = MempoolReactor(config.mempool, self.mempool)
        # Advertised software version; env-overridable so the e2e upgrade
        # perturbation (restart under a bumped version — the reference's
        # docker-image swap, runner/perturb.go:16-31) is observable over
        # RPC/p2p while staying protocol-compatible.
        from ..state.state import SOFTWARE_VERSION

        from ..libs import netstats as libnetstats

        self.node_info = NodeInfo(
            node_id=self.node_key.node_id,
            listen_addr="",
            network=genesis.chain_id,
            moniker=config.base.moniker,
            version=os.environ.get(
                "COMETBFT_TPU_SOFTWARE_VERSION", SOFTWARE_VERSION
            ),
            # advertise the provenance-stamp capability: messages are
            # stamped only toward peers that advertise it back, so an
            # unstamped peer sees byte-identical wire traffic
            # (COMETBFT_TPU_NET_STAMP=0 withdraws the advertisement)
            other=(
                {libnetstats.NODEINFO_STAMP_KEY: 1}
                if libnetstats.stamping_wanted()
                else {}
            ),
        )
        self.transport = MultiplexTransport(
            self.node_key,
            self.node_info,
            handshake_timeout=config.p2p.handshake_timeout_ns / 1e9,
            dial_timeout=config.p2p.dial_timeout_ns / 1e9,
        )
        self.switch = Switch(
            self.transport,
            mconn_config=MConnConfig(
                send_rate=config.p2p.send_rate,
                recv_rate=config.p2p.recv_rate,
                flush_throttle=config.p2p.flush_throttle_timeout_ns / 1e9,
            ),
            max_inbound=config.p2p.max_num_inbound_peers,
            max_outbound=config.p2p.max_num_outbound_peers,
        )
        self.evidence_reactor = EvidenceReactor(self.evidence_pool)
        # 9c. Statesync reactor: every node serves snapshots; a syncing
        # node also runs the Syncer (setup.go:476 startStateSync)
        from ..statesync import StatesyncReactor, Syncer

        self.statesync_reactor = StatesyncReactor(self.proxy_app.snapshot)
        self.syncer = None
        if self.statesync_enabled:
            sp = self._make_state_provider()
            self.syncer = Syncer(
                self.proxy_app.snapshot,
                self.proxy_app.query,
                sp,
                self.statesync_reactor.request_chunk,
                chunk_timeout=config.statesync.chunk_request_timeout_ns / 1e9,
                discovery_time=config.statesync.discovery_time_ns / 1e9,
            )
            self.statesync_reactor.syncer = self.syncer

        self.switch.add_reactor("MEMPOOL", self.mempool_reactor)
        self.switch.add_reactor("BLOCKSYNC", self.blocksync_reactor)
        self.switch.add_reactor("EVIDENCE", self.evidence_reactor)
        self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
        self.switch.add_reactor("STATESYNC", self.statesync_reactor)

        # 9d. PEX + address book (setup.go:427,454)
        from ..p2p.pex import AddrBook, PexReactor

        self.addr_book = AddrBook(
            config.base.resolve("config/addrbook.json")
        )
        self.addr_book.add_our_address(self.node_key.node_id)
        self.pex_reactor = None
        if config.p2p.pex:
            self.pex_reactor = PexReactor(
                self.addr_book,
                seed_mode=config.p2p.seed_mode,
                max_outbound=config.p2p.max_num_outbound_peers,
            )
            self.switch.add_reactor("PEX", self.pex_reactor)
        self.node_info.channels = self.switch.channel_ids()

        # 9b. Indexers (setup.go:141 createAndStartIndexerService)
        from ..state.indexer import (
            IndexerService,
            KVBlockIndexer,
            KVTxIndexer,
        )

        if config.tx_index.indexer == "kv":
            self.indexer_db = _make_db(config, "tx_index")
            self.tx_indexer = KVTxIndexer(self.indexer_db)
            self.block_indexer = KVBlockIndexer(self.indexer_db)
        elif config.tx_index.indexer == "sqlite":
            # external-DB sink (the reference's psql-sink tier,
            # state/indexer/sink/psql/psql.go:250): relational event
            # storage, SQL-translated search
            from ..state.sink import (
                SQLiteBlockIndexer,
                SQLiteEventSink,
                SQLiteTxIndexer,
            )

            self.indexer_db = None
            self.event_sink = SQLiteEventSink(
                os.path.join(config.base.resolve("data"), "events.sqlite")
            )
            self.tx_indexer = SQLiteTxIndexer(self.event_sink)
            self.block_indexer = SQLiteBlockIndexer(self.event_sink)
        else:
            self.indexer_db = None
            self.tx_indexer = None
            self.block_indexer = None
        if self.tx_indexer is not None:
            self.indexer_service = IndexerService(
                self.tx_indexer, self.block_indexer, self.event_bus
            )
            self.indexer_service.start()
        else:
            self.indexer_service = None

        # 10. RPC environment + server (node.go:536 startRPC)
        from ..rpc import Environment, RPCServer

        self.rpc_env = Environment(
            block_store=self.block_store,
            state_store=self.state_store,
            consensus=self.consensus,
            consensus_reactor=self.consensus_reactor,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            switch=self.switch,
            proxy_app_query=self.proxy_app.query,
            event_bus=self.event_bus,
            genesis=genesis,
            node_info=self.node_info,
            tx_indexer=self.tx_indexer,
            block_indexer=self.block_indexer,
            priv_validator_pub_key=(
                priv_validator.get_pub_key()
                if priv_validator is not None
                else None
            ),
            config=config,
        )
        self.rpc_env.extra["metrics"] = self.metrics
        self.rpc_env.extra["refresh_metrics"] = self._refresh_metrics
        self.rpc_env.extra["pex_reactor"] = self.pex_reactor
        rpc_routes = None
        if getattr(config.rpc, "unsafe", False):
            from ..rpc.core.routes import ROUTES, UNSAFE_ROUTES

            rpc_routes = {**ROUTES, **UNSAFE_ROUTES}
        self.rpc_server = (
            RPCServer(
                self.rpc_env,
                config.rpc.laddr,
                logger=self.logger.with_module("rpc"),
                routes=rpc_routes,
            )
            if config.rpc.laddr
            else None
        )
        # pprof/JAX-profiler server (node/node.go:651 startPprofServer)
        self.pprof_server = None
        if getattr(config.rpc, "pprof_laddr", ""):
            from ..libs.pprof import PprofServer

            self.pprof_server = PprofServer(
                config.rpc.pprof_laddr,
                logger=self.logger.with_module("pprof"),
            )
        # Dedicated Prometheus scrape listener (the reference's
        # Instrumentation server, node/node.go:630 + config/config.go
        # prometheus_listen_addr). COMETBFT_TPU_PROM_ADDR overrides the
        # config section; starting it also enables libs/devstats so the
        # XLA compile/device-memory/transfer families carry real data.
        from ..libs import devstats as libdevstats

        prom_addr = libdevstats.prometheus_addr(config)
        self.prometheus_server = None
        if prom_addr:
            self.prometheus_server = libdevstats.PrometheusServer(
                prom_addr,
                self.metrics.registry,
                refresh=self._refresh_metrics,
                logger=self.logger.with_module("prometheus"),
            )
        # Cross-caller verify coalescer (crypto/coalesce.py): the
        # steady-state vote path's feeder for the device kernel.
        # COMETBFT_TPU_COALESCE gates it; the decision is deferred to
        # on_start because in "auto" mode it probes the jax backend —
        # constructing a Node must stay free of backend init.
        self.verify_coalescer = None
        # Cross-caller hash plane (crypto/hashplane.py): coalesced
        # SHA-256 for mempool tx keys, PartSet leaves and merkle
        # levels. COMETBFT_TPU_HASH gates it; same deferred-probe boot
        # as the verify coalescer.
        self.hash_plane = None
        # Health monitor (libs/health): started in _finish_start — the
        # always-on flight recorder + SLO watchdogs + black-box dumps.
        self.health_monitor = None
        # Peer-health suspicion scorer (p2p/suspicion): started in
        # _finish_start behind COMETBFT_TPU_SUSPICION — evicts gray
        # (slow-but-alive) peers off the netstats signals.
        self.suspicion_scorer = None
        # Light-client proof service (light/service.py): serves
        # light_verify/light_status over the RPC server, funnelling
        # thousands of clients' skipping-verification commit checks
        # through the shared verifiers (and the coalescer, when one is
        # routed). Knob-gated (COMETBFT_TPU_LIGHT); started LAST in
        # _finish_start with leak-safe unwind like the health monitor.
        self.light_service = None
        self.switch.logger = self.logger.with_module("p2p")
        self.blocksync_reactor.logger = self.logger.with_module("blocksync")
        self.statesync_reactor.logger = self.logger.with_module("statesync")

    def _on_block_committed(self, height: int) -> None:
        """Metrics + the per-commit log line (consensus/metrics.go)."""
        import time as _time

        meta = self.block_store.load_block_meta(height)
        now = _time.monotonic()
        self.metrics.height.set(height)
        if self._last_commit_time:
            self.metrics.block_interval.observe(now - self._last_commit_time)
        self._last_commit_time = now
        if meta is not None:
            self.metrics.block_txs.set(meta.num_txs)
            self.metrics.block_size.set(meta.block_size)
            self.metrics.total_txs.inc(meta.num_txs)
            self.logger.with_module("consensus").info(
                "finalized block",
                height=height,
                num_txs=meta.num_txs,
                app_hash=meta.header.app_hash,
            )
        # Absent signers of the block's own seen commit
        # (consensus/metrics.go MissingValidators{,Power}).
        try:
            commit = self.block_store.load_seen_commit()
            if commit is not None and commit.height == height:
                from ..types.block import BLOCK_ID_FLAG_ABSENT

                # the set that SIGNED height h is the per-height persisted
                # one — node.state is the boot-time snapshot and goes
                # stale immediately (review finding)
                vals = self.state_store.load_validators(height)
                if vals is None:
                    return
                missing = missing_power = 0
                for idx, cs in enumerate(commit.signatures):
                    if cs.block_id_flag == BLOCK_ID_FLAG_ABSENT:
                        missing += 1
                        val = vals.get_by_index(idx)
                        if val is not None:
                            missing_power += val.voting_power
                self.metrics.missing_validators.set(missing)
                self.metrics.missing_validators_power.set(missing_power)
        except Exception:
            pass  # metrics must never break the commit path

    def _refresh_metrics(self) -> None:
        """Pull-time gauges (collector pattern): cheap reads at scrape —
        nothing here may touch the consensus commit path or disk."""
        from ..libs import devstats as libdevstats

        # device memory + arena occupancy into THIS node's registry
        # (no-op unless devstats is on; never initializes a jax backend
        # from the scrape path)
        libdevstats.sample(self.metrics)
        # health SLIs + composite score from the flight recorder (lock-
        # free ring reads; never touches an engine mutex)
        from ..libs import health as libhealth

        libhealth.sample(self.metrics)
        # network-plane gauges: per-channel queue depth/high-watermark,
        # top-K peer rates (lock-free connection snapshot)
        from ..libs import netstats as libnetstats

        libnetstats.sample(self.metrics)
        out, inb = self.switch.num_peers()
        self.metrics.peers.set(out + inb)
        self.metrics.mempool_size.set(self.mempool.size())
        vals = self.consensus.get_round_state().validators
        if vals is not None:
            self.metrics.validators.set(len(vals))
            self.metrics.validators_power.set(vals.total_voting_power())
        if self.evidence_pool is not None:
            try:
                offenders = set()
                # walk the gossip clist directly: pending_evidence()
                # serializes every item for its byte cap — too heavy for
                # the scrape path
                for el in self.evidence_pool.evidence_list:
                    ev = el.value
                    if hasattr(ev, "vote_a"):  # DuplicateVoteEvidence
                        offenders.add(bytes(ev.vote_a.validator_address))
                    for v in getattr(ev, "byzantine_validators", []):
                        offenders.add(bytes(v.address))
                self.metrics.byzantine_validators.set(len(offenders))
            except Exception:
                pass

    def _make_state_provider(self):
        """Light-client state provider from config.state_sync
        (stateprovider.go:29: needs witnesses, so >=2 RPC servers)."""
        from ..light import TrustOptions
        from ..light.rpc_provider import RPCProvider
        from ..statesync import StateProvider

        ss = self.config.statesync
        if not ss.rpc_servers:
            raise ValueError("statesync requires state_sync.rpc_servers")
        providers = [
            RPCProvider(addr, self.genesis.chain_id)
            for addr in ss.rpc_servers
        ]
        return StateProvider(
            self.genesis.chain_id,
            self.genesis,
            providers,
            TrustOptions(
                period_ns=ss.trust_period_ns,
                height=ss.trust_height,
                hash=bytes.fromhex(ss.trust_hash),
            ),
            initial_height=self.genesis.initial_height,
        )

    def _statesync_routine(self) -> None:
        """Background restore; on success bootstrap stores and hand off to
        blocksync (node.go startStateSync + statesync completion path)."""
        slog = self.logger.with_module("statesync")
        slog.info("discovering snapshots")
        try:
            state, commit = self.syncer.sync_any(deadline=120.0)
        except Exception:
            # Any failure path (SyncError, light-client errors, RPC down)
            # must not leave the node parked forever...
            import traceback

            traceback.print_exc()
            if self.syncer.applied_any:
                # ...but once ANY chunk was applied the app is no longer at
                # genesis: block-syncing from height 1 would replay against
                # mutated app state and fork on the first app hash.
                # Fail-stop like the reference (syncer.go verifyApp panic).
                import sys

                print(
                    "statesync failed after chunks were applied; "
                    "the data dir needs a reset — stopping node",
                    file=sys.stderr,
                )
                try:
                    self.stop()
                except Exception:
                    pass
                return
            # nothing applied: safe to block-sync the chain from genesis
            slog.error("statesync failed; falling back to blocksync")
            self.blocksync_reactor.switch_to_block_sync(self.state)
            return
        self.state_store.bootstrap(state)
        self.block_store.save_seen_commit(commit)
        self.state = state
        slog.info(
            "snapshot restored", height=state.last_block_height,
            app_hash=state.app_hash,
        )
        self.blocksync_reactor.switch_to_block_sync(state)

    def _on_app_error(self, err: Exception) -> None:
        # Fail-stop: the app is the source of truth (multi_app_conn.go:129).
        if self.is_running():
            try:
                self.stop()
            except Exception:
                os._exit(1)

    # -- lifecycle (node.go:364 OnStart) -----------------------------------

    def on_start(self) -> None:
        # boot order (node.go:364): pprof → RPC → transport listen → switch
        # (starts reactors, which start consensus) → dial persistent peers
        #
        # Network-plane telemetry first (refcounted like devstats /
        # health; COMETBFT_TPU_NET=0 pins it off): it must be live
        # before the switch accepts the first connection, and the boot
        # unwind below releases it on any failure.
        from ..libs import devledger as libdevledger
        from ..libs import lockprof as liblockprof
        from ..libs import netstats as libnetstats
        from ..libs import profile as libprofile
        from ..libs import txtrace as libtxtrace

        libnetstats.acquire()
        # the device-time ledger rides the same lifecycle: per-caller
        # attribution is on exactly while a node runs (kill switch
        # COMETBFT_TPU_LEDGER=0), released on any boot failure below
        libdevledger.acquire()
        # the tx-lifecycle plane too (kill switch COMETBFT_TPU_TX=0):
        # sampled stage stamps start with the first admitted tx, and
        # this node's mempool joins the oldest-age probe the
        # tx_starved watchdog and mempool_oldest_age_seconds read
        libtxtrace.acquire()
        # lock-contention profiler (kill switch COMETBFT_TPU_LOCKPROF=0):
        # per-lock wait/hold columns record exactly while a node runs,
        # feeding lock_wait_seconds{lock}, /debug/contention and the
        # lock_contended watchdog
        liblockprof.acquire()
        # sampling profiler (kill switch COMETBFT_TPU_PROF=0): the
        # prof-sampler thread walks stacks at ~67 Hz exactly while a
        # node runs, feeding /debug/pprof/profile, the profile.json
        # bundle artifact and the cpu:<subsystem> critical-path gate
        libprofile.acquire()
        libtxtrace.register_mempool(self.mempool)
        try:
            if self.pprof_server is not None:
                self.pprof_server.start()
                self.logger.with_module("pprof").info(
                    "pprof server listening",
                    port=self.pprof_server.bound_port,
                )
            if self.rpc_server is not None:
                self.rpc_server.start()
                self.logger.with_module("rpc").info(
                    "RPC server listening", addr=self.rpc_server.bound_addr
                )
            self.transport.listen(self.config.p2p.laddr)
            self.logger.with_module("p2p").info(
                "p2p transport listening", addr=self.transport.listen_addr
            )
            self.node_info.listen_addr = self.transport.listen_addr
            # The verify coalescer starts after every other fallible boot
            # step but before the switch (which starts consensus), so the
            # very first admitted votes coalesce and an earlier boot
            # failure — pprof/RPC/listen — can't leak a routed coalescer
            # that Node.stop() (NotStartedError) would never unwind. "auto"
            # starts one only when an accelerator backend is live, so
            # host-only deployments keep their unrouted paths untouched.
            from ..crypto import coalesce as crypto_coalesce

            if crypto_coalesce.node_wants_coalescer():
                self.verify_coalescer = crypto_coalesce.VerifyCoalescer(
                    logger=self.logger.with_module("coalesce")
                )
                self.verify_coalescer.start()
                crypto_coalesce.push_active(self.verify_coalescer)
            # The hash plane rides the same boot slot and the same
            # leak-safety rules as the verify coalescer: started before
            # the switch so the first CheckTx keys / PartSet leaves
            # coalesce, unwound on ANY later boot failure. "auto"
            # starts one only on accelerator backends — host-only
            # deployments keep plain hashlib with zero round trips.
            from ..crypto import hashplane as crypto_hashplane

            try:
                if crypto_hashplane.node_wants_hashplane():
                    self.hash_plane = crypto_hashplane.HashCoalescer(
                        logger=self.logger.with_module("hashplane")
                    )
                    self.hash_plane.start()
                    crypto_hashplane.push_active(self.hash_plane)
            except BaseException:
                if self.verify_coalescer is not None:
                    crypto_coalesce.pop_active(self.verify_coalescer)
                    self.verify_coalescer.stop()
                    self.verify_coalescer = None
                raise
            try:
                self._finish_start()
            except BaseException:
                # a failed boot leaves _started unset, so Node.stop() would
                # raise NotStartedError and on_stop would never unroute the
                # coalescer — unwind it here or the orphan stays atop the
                # process-wide routing stack with its executor running
                if self.hash_plane is not None:
                    crypto_hashplane.pop_active(self.hash_plane)
                    self.hash_plane.stop()
                    self.hash_plane = None
                if self.verify_coalescer is not None:
                    crypto_coalesce.pop_active(self.verify_coalescer)
                    self.verify_coalescer.stop()
                    self.verify_coalescer = None
                raise
        except BaseException:
            # ANY boot failure: release the netstats + ledger + tx-plane
            # + lockprof + profiler acquires (on_stop never runs on a
            # half-booted node)
            libtxtrace.deregister_mempool(self.mempool)
            libprofile.release()
            liblockprof.release()
            libtxtrace.release()
            libdevledger.release()
            libnetstats.release()
            raise

    def _finish_start(self) -> None:
        """Boot steps after the verify coalescer is routed: the switch
        (which starts consensus), peer dialing, background routines and
        the Prometheus exporter. Split out so on_start can unwind the
        coalescer if ANY of them fails."""
        self.switch.start()
        persistent = [
            a.strip()
            for a in self.config.p2p.persistent_peers.split(",")
            if a.strip()
        ]
        if persistent:
            self.switch.set_persistent_peers(persistent)
            self.switch.dial_peers_async(persistent)
        # seeds prime the address book; PEX's ensure-peers loop dials them
        seeds = [
            a.strip()
            for a in self.config.p2p.seeds.split(",")
            if a.strip()
        ]
        for seed in seeds:
            self.addr_book.add_address(seed, src="seed-config")
        if self.statesync_enabled:
            threading.Thread(
                target=self._statesync_routine, name="statesync", daemon=True
            ).start()
        if self.mempool.txs_available() is not None:
            self._txs_available_thread = threading.Thread(
                target=self._forward_txs_available, daemon=True
            )
            self._txs_available_thread.start()
        # Prometheus exporter LAST: device telemetry lives exactly as
        # long as someone can scrape it (acquired here, released in
        # on_stop, refcounted across in-process nodes), and starting it
        # after every fallible boot step means a failed boot — where
        # stop() raises NotStartedError and on_stop never runs — cannot
        # leak the acquire.
        if self.prometheus_server is not None:
            from ..libs import devstats as libdevstats

            libdevstats.acquire()
            try:
                self.prometheus_server.start()
            except BaseException:
                libdevstats.release()
                raise
            self.logger.with_module("prometheus").info(
                "prometheus exporter listening",
                port=self.prometheus_server.bound_port,
            )
        # Health monitor LAST for the same leak-safety reason as the
        # exporter: its on_start acquires the flight recorder
        # (refcounted like devstats), so it must start only after every
        # fallible boot step. COMETBFT_TPU_HEALTH=0 is the kill switch;
        # the stall window scales off this node's own consensus
        # timeouts (one commit+propose cycle is the longest a healthy
        # node idles between step transitions).
        from ..libs import health as libhealth

        if libhealth.monitor_enabled():
            self.health_monitor = libhealth.HealthMonitor(
                metrics=self.metrics,
                stall_base_s=(
                    self.config.consensus.commit_timeout()
                    + self.config.consensus.propose_timeout(0)
                ),
                bundle_dir=self.config.base.resolve("data/health"),
                # legitimate silences on THIS node: still block-syncing
                # (consensus parked behind the sync reactors), or
                # intentionally waiting for transactions — a quiet
                # chain with create_empty_blocks=false is live, not
                # stalled, and must not page the operator
                idle_ok=lambda: (
                    not self.blocksync_reactor.synced.is_set()
                    or (
                        not self.config.consensus.create_empty_blocks
                        and self.mempool.size() == 0
                    )
                ),
                # slow-disk watchdog signal: this node's own WAL fsync
                # EWMA state (consensus/wal.py disk_degraded)
                disk_degraded_fn=self.consensus.wal.disk_degraded,
                logger=self.logger.with_module("health"),
            )
            try:
                self.health_monitor.start()
            except BaseException:
                # the exporter was already up: a failed boot here would
                # otherwise leak its devstats acquire (stop() raises
                # NotStartedError on a half-booted node, so on_stop
                # never runs)
                self.health_monitor = None
                self._unwind_late_services()
                raise
        # Peer-health suspicion scorer (p2p/suspicion): acts on the
        # netstats gray-failure signals by evicting suspect peers
        # through the switch. Same late-boot posture — nothing below
        # depends on it, and a failure unwinds the monitor + exporter.
        from ..p2p import suspicion as p2p_suspicion

        if p2p_suspicion.enabled():
            try:
                self.suspicion_scorer = p2p_suspicion.SuspicionScorer(
                    self.switch,
                    metrics=self.metrics,
                    logger=self.logger.with_module("suspicion"),
                )
                self.suspicion_scorer.start()
            except BaseException:
                self.suspicion_scorer = None
                self._unwind_late_services()
                raise
        # Light-client proof service LAST, same leak-safety posture:
        # everything it depends on (stores, RPC env, metrics, the
        # routed coalescer) is already up, and a failure here unwinds
        # the health monitor + exporter acquires that on_stop would
        # never release on a half-booted node.
        from ..light import service as light_service_mod

        if light_service_mod.node_wants_light_service():
            from ..light.provider import StoreBackedProvider

            try:
                self.light_service = light_service_mod.LightService(
                    provider=StoreBackedProvider(
                        self.block_store, self.state_store,
                        self.genesis.chain_id,
                    ),
                    chain_id=self.genesis.chain_id,
                    logger=self.logger.with_module("light"),
                )
                self.light_service.start()
            except BaseException:
                self.light_service = None
                self._unwind_late_services()
                raise
            self.rpc_env.extra["light_service"] = self.light_service
            self.logger.with_module("light").info(
                "light proof service serving light_verify/light_status"
            )

    def _unwind_late_services(self) -> None:
        """Stop every late-boot service started so far (reverse boot
        order) and release the exporter acquire — the ONE failure path
        of the _finish_start late-service ladder, so adding a new late
        service cannot silently miss an earlier one's teardown.  The
        caller Nones the service whose start just failed before calling
        (a half-started BaseService raises from stop())."""
        for attr in (
            "light_service", "suspicion_scorer", "health_monitor",
        ):
            svc = getattr(self, attr)
            if svc is not None:
                try:
                    if svc.is_running():
                        svc.stop()
                except Exception:
                    pass
                setattr(self, attr, None)
        self._unwind_late_boot()

    def _unwind_late_boot(self) -> None:
        """Release the Prometheus exporter's devstats acquire after a
        late _finish_start failure (a half-booted node never runs
        on_stop, so the unwind must happen at the failure site)."""
        if self.prometheus_server is not None:
            from ..libs import devstats as libdevstats

            try:
                if self.prometheus_server.is_running():
                    self.prometheus_server.stop()
            except Exception:
                pass
            libdevstats.release()

    def _forward_txs_available(self) -> None:
        ev = self.mempool.txs_available()
        while not self.quit_event().is_set():
            if ev.wait(timeout=0.2):
                ev.clear()
                self.consensus.handle_txs_available()

    def on_stop(self) -> None:
        from ..libs import metrics as libmetrics

        # pop THIS node's registry; an in-process peer node pushed later
        # keeps the top slot, an earlier one is restored (libs/metrics
        # node-stack semantics)
        libmetrics.pop_node_metrics(self.metrics)
        # Remote-signer endpoint (default_new_node attaches it): release
        # the listening socket + ping thread or a same-process restart on
        # the same laddr fails with EADDRINUSE.
        endpoint = getattr(self, "_privval_endpoint", None)
        if endpoint is not None:
            try:
                endpoint.stop()
            except Exception:
                pass
        if self.indexer_service is not None:
            try:
                self.indexer_service.stop()
            except Exception:
                pass
        if self.rpc_server is not None and self.rpc_server.is_running():
            try:
                self.rpc_server.stop()
            except Exception:
                pass
        # Light service right after the RPC listener: no new requests
        # can arrive, queued waiters are rejected, and stop() drains
        # every in-flight verification before the verifiers below it
        # (coalescer, stores) unwind.
        if getattr(self, "light_service", None) is not None:
            try:
                if self.light_service.is_running():
                    self.light_service.stop()
            except Exception:
                pass
        if self.pprof_server is not None and self.pprof_server.is_running():
            try:
                self.pprof_server.stop()
            except Exception:
                pass
        if self.prometheus_server is not None:
            from ..libs import devstats as libdevstats

            if self.prometheus_server.is_running():
                try:
                    self.prometheus_server.stop()
                except Exception:
                    pass
            libdevstats.release()
        if self.suspicion_scorer is not None:
            try:
                if self.suspicion_scorer.is_running():
                    self.suspicion_scorer.stop()
            except Exception:
                pass
        if self.health_monitor is not None:
            try:
                if self.health_monitor.is_running():
                    self.health_monitor.stop()
            except Exception:
                pass
        for svc in (self.switch, self.event_bus, self.proxy_app):
            try:
                if svc.is_running():
                    svc.stop()
            except Exception:
                pass
        # after the switch (its peers deregister their stats blocks on
        # connection stop): release this node's netstats + device-time
        # ledger + tx-plane + lock-profiler + sampling-profiler acquires
        from ..libs import devledger as libdevledger
        from ..libs import lockprof as liblockprof
        from ..libs import netstats as libnetstats
        from ..libs import profile as libprofile
        from ..libs import txtrace as libtxtrace

        libtxtrace.deregister_mempool(self.mempool)
        libprofile.release()
        liblockprof.release()
        libtxtrace.release()
        libnetstats.release()
        libdevledger.release()
        # Coalescer after consensus is down: unroute first (new callers
        # fall back to host instantly), then drain — stop() resolves
        # every pending ticket, so no verifier thread is left hanging.
        if getattr(self, "verify_coalescer", None) is not None:
            from ..crypto import coalesce as crypto_coalesce

            crypto_coalesce.pop_active(self.verify_coalescer)
            try:
                if self.verify_coalescer.is_running():
                    self.verify_coalescer.stop()
            except Exception:
                pass
        # Hash plane with the same unroute-then-drain discipline: new
        # hashers fall back to hashlib instantly, stop() resolves every
        # pending digest ticket.
        if getattr(self, "hash_plane", None) is not None:
            from ..crypto import hashplane as crypto_hashplane

            crypto_hashplane.pop_active(self.hash_plane)
            try:
                if self.hash_plane.is_running():
                    self.hash_plane.stop()
            except Exception:
                pass
        try:
            self.consensus.wal.close()
        except Exception:
            pass
        for db in (
            self.app_db, self.block_db, self.state_db, self.evidence_db,
            self.indexer_db, getattr(self, "event_sink", None),
        ):
            if db is None:
                continue
            try:
                db.close()
            except Exception:
                pass


def default_new_node(config: Config) -> Node:
    """node/setup.go:64 DefaultNewNode.

    With ``priv_validator_laddr`` set the node listens for a remote
    signer and signs through it (setup.go:595
    createAndStartPrivValidatorSocketClient); otherwise the file PV.
    """
    genesis = load_genesis(config)
    if config.base.priv_validator_laddr:
        from ..privval.signer import (
            RetrySignerClient,
            SignerClient,
            SignerListenerEndpoint,
        )

        endpoint = SignerListenerEndpoint(config.base.priv_validator_laddr)
        endpoint.start()
        try:
            pv = RetrySignerClient(SignerClient(endpoint, genesis.chain_id))
            node = Node(config, genesis, pv)
        except Exception:
            endpoint.stop()
            raise
        node._privval_endpoint = endpoint
        return node
    pv = FilePV.load_or_generate(
        config.base.resolve(config.base.priv_validator_key_file),
        config.base.resolve(config.base.priv_validator_state_file),
    )
    return Node(config, genesis, pv)
