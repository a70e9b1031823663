"""Node assembly (reference: node/node.go NewNode :706, OnStart :941).

Wires, in the reference's order: DBs → state → proxyApp → EventBus →
privval → handshake → mempool → block executor → consensus → RPC.
(p2p switch + reactors attach here as they land; a single-node validator
is fully functional without them — BASELINE config #1.)
"""

from __future__ import annotations

import os
from typing import Optional

from tmtpu.abci.example.kvstore import KVStoreApplication
from tmtpu.config.config import Config
from tmtpu.consensus.replay import Handshaker
from tmtpu.consensus.state import ConsensusState
from tmtpu.crypto import batch as crypto_batch
from tmtpu.libs.db import DB, MemDB, SQLiteDB
from tmtpu.libs.service import BaseService
from tmtpu.mempool.clist_mempool import CListMempool
from tmtpu.privval.file_pv import FilePV
from tmtpu.proxy import AppConns, default_client_creator
from tmtpu.state.execution import BlockExecutor
from tmtpu.state.state import state_from_genesis
from tmtpu.state.store import StateStore
from tmtpu.store.block_store import BlockStore
from tmtpu.types.event_bus import EventBus
from tmtpu.types.genesis import GenesisDoc


def _make_db(config: Config, name: str) -> DB:
    if config.base.db_backend == "mem":
        return MemDB()
    path = config.rooted(os.path.join(config.base.db_dir, f"{name}.sqlite"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    return SQLiteDB(path)


class Node(BaseService):
    def __init__(self, config: Config,
                 app=None,
                 genesis_doc: Optional[GenesisDoc] = None,
                 priv_validator=None):
        super().__init__("Node")
        self.config = config
        # [instr] txlat gates the per-tx lifecycle stamp ring before any
        # subsystem can stamp (the module fast paths read this flag)
        from tmtpu.libs import trace as _trace
        from tmtpu.libs import txlat as _txlat
        from tmtpu.libs import valstats as _valstats

        _txlat.set_enabled(config.instrumentation.txlat)
        # [instr] valstats gates the per-validator forensics ledger the
        # same way (off ⇒ every vote-path hook is one attribute read)
        _valstats.set_enabled(config.instrumentation.valstats)
        # [instr] trace_sample gates cross-process trace contexts the
        # same way (0 ⇒ the node neither mints nor adopts contexts);
        # node/chain identity lands below once known
        _trace.configure(sample_rate=config.instrumentation.trace_sample)
        crypto_batch.set_default_backend(config.base.crypto_backend)
        # resilience knobs: probe/batch deadlines + breaker thresholds
        # ([crypto] section) flow into the shared breaker registry BEFORE
        # the first verifier is built, so the first probe already runs
        # under the configured deadline
        crypto_batch.configure(config.crypto)
        # sidecar client wiring ([sidecar] section): always applied so a
        # node can flip to crypto_backend=sidecar via env without a
        # config rewrite; without an address the backend falls back
        # in-process on first use
        crypto_batch.configure_sidecar(
            config.sidecar, home=os.path.expanduser(config.base.home))
        # open the verify engine now, not on the consensus thread: loads
        # (and, first time, compiles) the native host-prep library, and
        # for the in-process device backends places the compile cache,
        # checks an explicit "tpu" against what JAX found, and logs the
        # platform once. A sidecar or cpu node never imports JAX.
        self.verify_device = crypto_batch.start_backend(
            config.base.crypto_backend, "node")

        # --- DBs + state (node.go initDBs / LoadStateFromDBOrGenesis) ---
        self.block_store = BlockStore(_make_db(config, "blockstore"))
        self.state_store = StateStore(
            _make_db(config, "state"),
            discard_abci_responses=config.storage.discard_abci_responses,
        )
        self.genesis_doc = genesis_doc or GenesisDoc.from_file(
            config.genesis_path)
        _trace.configure(chain_id=self.genesis_doc.chain_id)
        state = self.state_store.load()
        if state is None:
            state = state_from_genesis(self.genesis_doc)
            self.state_store.save(state)

        # --- proxy app (node.go createAndStartProxyAppConns) ---
        if app is None:
            if config.base.proxy_app == "kvstore":
                app = KVStoreApplication(
                    _make_db(config, "app"),
                    snapshot_interval=config.base.app_snapshot_interval)
            elif config.base.proxy_app == "noop":
                from tmtpu.abci.types import Application

                app = Application()
            else:
                app = config.base.proxy_app  # socket address
        # base.abci selects the remote transport; "local" only makes
        # sense for in-proc apps, where the creator ignores it
        transport = config.base.abci \
            if config.base.abci in ("socket", "grpc") else "socket"
        self.proxy_app = AppConns(
            default_client_creator(app, transport=transport))
        self.proxy_app.start()

        # --- event bus + tx indexer (node.go createAndStartEventBus /
        # IndexerService) ---
        self.event_bus = EventBus()
        from tmtpu.state.txindex import (
            IndexerService, KVTxIndexer, NullTxIndexer,
        )

        if config.tx_index.indexer == "kv":
            from tmtpu.state.txindex import KVBlockIndexer

            self.tx_indexer = KVTxIndexer(_make_db(config, "txindex"))
            self.block_indexer = KVBlockIndexer(
                _make_db(config, "blockindex"))
        elif config.tx_index.indexer == "psql":
            # SQL event sink (node.go EventSinksFromConfig "psql")
            from tmtpu.state.sink_sql import (
                SQLBlockIndexer, SQLSink, SQLTxIndexer,
                open_sink_connection,
            )

            sink = SQLSink(
                open_sink_connection(config.tx_index.psql_conn,
                                     config.rooted(config.base.db_dir)),
                self.genesis_doc.chain_id)
            self.tx_indexer = SQLTxIndexer(sink)
            self.block_indexer = SQLBlockIndexer(sink)
        else:
            self.tx_indexer = NullTxIndexer()
            self.block_indexer = None
        self.indexer_service = IndexerService(
            self.tx_indexer, self.event_bus,
            block_indexer=self.block_indexer)

        # --- privval ---
        self.signer_endpoint = None
        if priv_validator is None:
            if config.base.priv_validator_laddr:
                # remote signer (node.go:1449): listen and wait for the
                # signer process to dial in before consensus can start
                from tmtpu.privval.signer import (
                    SignerClient, SignerListenerEndpoint,
                )

                self.signer_endpoint = SignerListenerEndpoint(
                    config.base.priv_validator_laddr)
                self.signer_endpoint.accept(timeout=60.0)
                self.signer_endpoint.start_accept_loop()
                self.signer_endpoint.start_ping_loop()
                priv_validator = SignerClient(self.signer_endpoint,
                                              self.genesis_doc.chain_id)
            else:
                priv_validator = FilePV.load_or_generate(
                    config.rooted(config.base.priv_validator_key_file),
                    config.rooted(config.base.priv_validator_state_file),
                )
        self.priv_validator = priv_validator

        # --- handshake: sync app with store (node.go doHandshake) ---
        hs = Handshaker(self.state_store, state, self.block_store,
                        self.genesis_doc, self.event_bus)
        hs.handshake(self.proxy_app)
        self.state = hs.state

        # --- mempool (node.go:368; version per config, like FastSync) ---
        mp_kwargs = dict(
            max_txs=config.mempool.size,
            max_txs_bytes=config.mempool.max_txs_bytes,
            cache_size=config.mempool.cache_size,
            keep_invalid_txs_in_cache=config.mempool.keep_invalid_txs_in_cache,
            batch_check=config.mempool.batch_check,
            batch_gather_wait_s=config.mempool.batch_gather_wait_ns / 1e9,
            batch_max_txs=config.mempool.batch_max_txs,
            verify_signatures=config.mempool.verify_signatures,
        )
        if config.mempool.version == "v1":
            from tmtpu.mempool.priority_mempool import PriorityMempool

            mempool_cls = PriorityMempool
            mp_kwargs.update(
                ttl_num_blocks=config.mempool.ttl_num_blocks,
                ttl_duration_ns=config.mempool.ttl_duration_ns)
        else:
            mempool_cls = CListMempool
        self.mempool = mempool_cls(self.proxy_app.mempool, **mp_kwargs)

        # --- evidence pool ---
        from tmtpu.evidence.pool import EvidencePool

        self.evidence_pool = EvidencePool(
            _make_db(config, "evidence"), self.state_store, self.block_store)

        # --- block executor + consensus ---
        self.block_exec = BlockExecutor(
            self.state_store, self.proxy_app.consensus, self.mempool,
            self.evidence_pool, self.event_bus,
            verify_backend=None,  # BatchVerifier default (config'd above)
        )
        wal_path = config.wal_path
        os.makedirs(os.path.dirname(wal_path), exist_ok=True)
        self.consensus = ConsensusState(
            config.consensus, self.state, self.block_exec, self.block_store,
            self.mempool, self.evidence_pool, self.event_bus,
            self.priv_validator, wal_path,
        )
        if config.base.misbehaviors:
            from tmtpu.consensus.misbehavior import parse_schedule

            self.consensus.misbehaviors = parse_schedule(
                config.base.misbehaviors)

        # --- p2p stack (node.go createTransport/createSwitch) ---
        self.node_key = None
        self.switch = None
        self.node_id = ""
        self.consensus_reactor = None
        self.fast_sync = False
        self.state_sync = False
        self.link_shaper = None
        self.fuzz_config = None
        if config.p2p.laddr:
            from tmtpu.consensus.reactor import ConsensusReactor
            from tmtpu.mempool.reactor import MempoolReactor
            from tmtpu.p2p.key import NodeKey
            from tmtpu.p2p.switch import Switch
            from tmtpu.p2p.transport import NodeInfo, Transport
            from tmtpu.version import BlockProtocol, P2PProtocol, TMCoreSemVer

            self.node_key = NodeKey.load_or_gen(
                config.rooted(config.base.node_key_file))
            self.node_id = self.node_key.node_id
            _trace.configure(node_id=self.node_id)
            node_info = NodeInfo(
                node_id=self.node_key.node_id,
                listen_addr=config.p2p.laddr,
                network=self.genesis_doc.chain_id,
                version=TMCoreSemVer,
                channels=b"",  # filled from registered reactors below
                moniker=config.base.moniker,
                p2p_version=P2PProtocol,
                block_version=BlockProtocol,
                rpc_address=config.rpc.laddr,
            )
            transport = Transport(
                self.node_key, node_info,
                dial_timeout=config.p2p.dial_timeout_ns / 1e9,
                handshake_timeout=config.p2p.handshake_timeout_ns / 1e9,
            )
            transport.conn_wrapper = self._build_conn_wrapper(config)
            transport.listen(config.p2p.laddr)
            self.transport = transport
            # advertise the RESOLVED port (ephemeral ":0" binds would
            # otherwise gossip undialable addresses through PEX); an
            # explicit external_address wins (node.go:498 createTransport)
            if config.p2p.external_address:
                node_info.listen_addr = config.p2p.external_address
            elif config.p2p.laddr.endswith(":0"):
                node_info.listen_addr = \
                    config.p2p.laddr.rsplit(":", 1)[0] + \
                    f":{transport.listen_port}"
            self.switch = Switch(transport,
                                 max_inbound=config.p2p.max_num_inbound_peers,
                                 max_outbound=config.p2p.max_num_outbound_peers,
                                 send_rate=config.p2p.send_rate,
                                 recv_rate=config.p2p.recv_rate)
            # fast sync only makes sense when someone else has blocks
            # (node.go:450 createBlockchainReactor + onlyValidatorIsUs)
            self.fast_sync = (config.block_sync.enable
                              and not self._only_validator_is_us())
            # statesync: fresh node + config opt-in (node.go:649)
            self.state_sync = (config.state_sync.enable
                               and self.state.last_block_height == 0)
            self.consensus_reactor = ConsensusReactor(
                self.consensus,
                wait_sync=self.fast_sync or self.state_sync)
            self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
            self.switch.add_reactor("MEMPOOL", MempoolReactor(
                self.mempool, broadcast=config.mempool.broadcast,
                seen_cache=config.mempool.gossip_seen_cache))
            # blocksync reactor version per config (node.go:450 picks the
            # blockchain reactor by config.FastSync.Version the same way)
            if config.block_sync.version == "v2":
                from tmtpu.blocksync.v2 import BlocksyncReactorV2 \
                    as blocksync_cls
            elif config.block_sync.version == "v1":
                from tmtpu.blocksync.v1 import BlocksyncReactorV1 \
                    as blocksync_cls
            else:
                from tmtpu.blocksync.reactor import BlocksyncReactor \
                    as blocksync_cls

            # with statesync pending, blocksync starts LATER via
            # switch_to_fast_sync once the snapshot state is planted
            self.blocksync_reactor = blocksync_cls(
                self.state, self.block_exec, self.block_store,
                self.fast_sync and not self.state_sync,
                consensus_reactor=self.consensus_reactor)
            self.switch.add_reactor("BLOCKSYNC", self.blocksync_reactor)
            from tmtpu.evidence.reactor import EvidenceReactor

            self.switch.add_reactor("EVIDENCE",
                                    EvidenceReactor(self.evidence_pool))
            # PEX + addrbook (node.go:627 createPEXReactorAndAddToSwitch)
            self.addr_book = None
            if config.p2p.pex:
                from tmtpu.p2p.pex import AddrBook, PexReactor

                self.addr_book = AddrBook(
                    config.rooted("config/addrbook.json"),
                    our_id=self.node_id)
                seeds = [a.strip() for a in config.p2p.seeds.split(",")
                         if a.strip()]
                self.pex_reactor = PexReactor(
                    self.addr_book, seed_mode=config.p2p.seed_mode,
                    seeds=seeds)
                self.switch.add_reactor("PEX", self.pex_reactor)
            # statesync reactor (node.go:839) — always serves snapshots;
            # the syncing side activates when state_sync.enable on a fresh
            # node (see on_start)
            from tmtpu.statesync import StatesyncReactor, Syncer

            self.statesync_reactor = StatesyncReactor(self.proxy_app)
            if self.state_sync:
                # state_provider is attached in _statesync_routine: its
                # light client does network I/O at construction, which must
                # not block or fail Node.__init__ (node.go builds it inside
                # startStateSync for the same reason)
                self.statesync_reactor.syncer = Syncer(
                    self.proxy_app, None,
                    self.statesync_reactor.request_chunk,
                    chunk_timeout_s=config.state_sync
                    .chunk_request_timeout_ns / 1e9,
                    request_snapshots=self.statesync_reactor
                    .request_snapshots,
                    get_peers=self.statesync_reactor.statesync_peers)
            self.switch.add_reactor("STATESYNC", self.statesync_reactor)
            # advertise exactly the channels with a registered reactor:
            # claiming a channel we can't serve makes peers' sends fatal
            # (MConnection errors on packets for unknown channels)
            node_info.channels = bytes(sorted(
                d.channel_id for d in self.switch._channel_descs))
            self.switch.set_persistent_peers(
                [a.strip() for a in config.p2p.persistent_peers.split(",")
                 if a.strip()])

        # --- RPC ---
        self.rpc_server = None
        if config.rpc.laddr:
            from tmtpu.rpc.server import RPCServer

            rc = config.rpc
            self.rpc_server = RPCServer(
                rc.laddr, self,
                cors_origins=rc.cors_allowed_origins,
                cors_methods=rc.cors_allowed_methods,
                cors_headers=rc.cors_allowed_headers,
                tls_cert=config.rooted(rc.tls_cert_file)
                if rc.tls_cert_file else "",
                tls_key=config.rooted(rc.tls_key_file)
                if rc.tls_key_file else "",
                max_body_bytes=rc.max_body_bytes,
                max_open_connections=rc.max_open_connections,
                max_subscription_clients=rc.max_subscription_clients,
                max_subscriptions_per_client=
                rc.max_subscriptions_per_client)

        # --- gRPC broadcast API (node.go startRPC: served on
        # rpc.grpc_laddr when set; deprecated upstream but shipped) ---
        self.grpc_api_server = None
        if config.rpc.grpc_laddr:
            from tmtpu.rpc import core as rpc_core
            from tmtpu.rpc.grpc_api import BroadcastAPIServer

            routes = rpc_core.build_routes(rpc_core.Environment(self))
            self.grpc_api_server = BroadcastAPIServer(
                config.rpc.grpc_laddr, routes["broadcast_tx_commit"])

        # --- health engine: stall watchdog + liveness/readiness ---
        self.watchdog = None
        if config.health.enable:
            self.watchdog = self._build_watchdog(config.health)

        # --- pprof (node.go:894-900: gated on RPC.PprofListenAddress) ---
        self.pprof_server = None
        if config.rpc.pprof_laddr:
            from tmtpu.rpc.pprof import PprofServer

            self.pprof_server = PprofServer(
                config.rpc.pprof_laddr,
                health=self.watchdog.liveness if self.watchdog else None,
                ready=self._readiness if self.watchdog else None)

    def _build_watchdog(self, hc):
        """Wire the libs/watchdog checks to this node's subsystems:
        consensus progress, p2p peer floor, mempool drain,
        blocksync/statesync status, and the TPU crypto backend."""
        from tmtpu.libs import watchdog as wdg

        wd = wdg.Watchdog(
            interval_s=hc.watchdog_interval_ns / 1e9,
            slow_span_threshold_s=hc.slow_span_threshold_ns / 1e9)
        wd.register("consensus", wdg.consensus_progress_check(
            self.consensus, hc.consensus_stall_timeout_ns / 1e9,
            is_syncing=self._is_syncing))
        if self.switch is not None and hc.min_peers > 0:
            wd.register("p2p", wdg.peer_count_check(
                self.switch.num_peers, hc.min_peers))
        if self.mempool is not None:
            wd.register("mempool", wdg.mempool_drain_check(
                self.mempool, hc.mempool_stall_timeout_ns / 1e9))
        wd.register("sync", wdg.sync_status_check(
            lambda: self._is_syncing() and not self.state_sync,
            lambda: self.state_sync))
        instr = self.config.instrumentation
        if instr.latency_slo_ms > 0 and instr.txlat:
            # armed only when an SLO is configured AND the stamp ring is
            # on (without txlat the histogram never moves and the check
            # would report healthy forever while lying about coverage)
            wd.register("latency", wdg.latency_slo_check(
                instr.latency_slo_ms,
                window_s=hc.latency_slo_window_ns / 1e9,
                consecutive=hc.latency_slo_samples))
        if instr.valstats and hc.validator_flap_threshold > 0:
            # armed only when the forensics ledger is on (without it the
            # flap counts never move and the check would idle forever)
            wd.register("validator", wdg.validator_flap_check(
                window_s=hc.validator_flap_window_ns / 1e9,
                threshold=hc.validator_flap_threshold))
        if self.config.base.crypto_backend != "cpu":
            wd.register("crypto", wdg.tpu_backend_check(
                hc.fallback_storm_window_ns / 1e9,
                hc.fallback_storm_threshold,
                # under JAX_PLATFORMS=cpu emulation the gauge is
                # truthfully 0 and that is what was asked for
                expect_device=self.config.base.crypto_backend == "tpu"
                and self.verify_device["platform"] == "tpu"))
            wd.register("breaker", wdg.breaker_check())
        if self.config.base.crypto_backend == "sidecar":
            wd.register("sidecar", wdg.sidecar_check(
                hc.fallback_storm_window_ns / 1e9,
                hc.fallback_storm_threshold))
        return wd

    def _is_syncing(self) -> bool:
        """Live sync verdict. ``self.fast_sync``/``self.state_sync``
        record the LAUNCH decision and ``fast_sync`` is never cleared;
        the consensus reactor's ``wait_sync`` is the flag the handover
        actually flips (blocksync/statesync -> consensus, mirroring
        node.go's ConsensusReactor.WaitSync()). Reading the stale launch
        flag kept every multi-validator node "syncing" for its whole
        life, which permanently disarmed the consensus stall watchdog
        and /readyz."""
        if self.consensus_reactor is not None:
            return bool(self.state_sync
                        or self.consensus_reactor.wait_sync)
        return self.fast_sync or self.state_sync

    def _readiness(self):
        """/readyz verdict: live AND caught up. A syncing node is
        healthy (the watchdog gives sync a pass) but must not take
        traffic yet."""
        ok, reasons = self.watchdog.healthy()
        syncing = self._is_syncing()
        ready = ok and not syncing
        return ready, {"ready": ready, "syncing": syncing,
                       "reasons": reasons}

    def _make_state_provider(self):
        """stateprovider.go:48 — light client over the configured RPC
        servers, anchored at the configured trust height/hash."""
        from tmtpu.light.client import TrustOptions
        from tmtpu.light.provider import HTTPProvider
        from tmtpu.statesync import LightClientStateProvider

        ss = self.config.state_sync
        providers = [HTTPProvider(self.chain_id, url)
                     for url in ss.rpc_servers]
        return LightClientStateProvider(
            self.chain_id,
            TrustOptions(ss.trust_period_ns, ss.trust_height,
                         bytes.fromhex(ss.trust_hash)),
            providers,
            initial_height=self.genesis_doc.initial_height,
            consensus_params=self.genesis_doc.consensus_params,
        )

    def _statesync_routine(self) -> None:
        """node.go startStateSync: discover → sync → bootstrap stores →
        hand over to blocksync (which later hands over to consensus)."""
        import time as _time

        import sys

        syncer = self.statesync_reactor.syncer
        discovery_s = self.config.state_sync.discovery_time_ns / 1e9
        # wait for at least one peer, then ask everyone for snapshots
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline and self.is_running() and \
                self.switch.num_peers() == 0:
            _time.sleep(0.1)
        # trust anchor over the network — retried, never done in __init__
        while self.is_running():
            try:
                syncer.state_provider = self._make_state_provider()
                break
            except Exception as e:  # noqa: BLE001 — RPC flake, retry
                print(f"statesync: state provider init failed: {e}; "
                      f"retrying", file=sys.stderr)
                _time.sleep(discovery_s)
        if syncer.state_provider is None:
            return
        self.statesync_reactor.request_snapshots()
        try:
            state, commit = syncer.sync_any(discovery_time_s=discovery_s)
        except Exception as e:  # noqa: BLE001 — node stays in wait_sync
            print(f"statesync FAILED: {type(e).__name__}: {e} — node is "
                  f"waiting in sync mode; check state_sync config",
                  file=sys.stderr)
            return
        self.state_store.bootstrap(state)
        self.block_store.bootstrap(state.last_block_height)
        self.block_store.save_seen_commit(state.last_block_height, commit)
        self.state = state
        self.state_sync = False
        # blocksync fetches the tail and hands consensus the final state
        # via ConsensusReactor.switch_to_consensus
        self.blocksync_reactor.switch_to_fast_sync(state)

    def _build_conn_wrapper(self, config):
        """Compose the transport's conn_wrapper from [p2p] fuzz/shaping
        config. The LinkShaper is ALWAYS built when rpc.unsafe is on —
        even with an empty link table — so ``unsafe_net_shape`` can
        shape/partition a running node whose config started clean."""
        from tmtpu.p2p.shaping import LinkShaper, parse_links

        shaper = None
        if config.p2p.shape_links or config.rpc.unsafe:
            shaper = LinkShaper(parse_links(config.p2p.shape_links),
                                seed=config.p2p.shape_seed)
        self.link_shaper = shaper
        fuzz_cfg = None
        if config.p2p.test_fuzz:
            from tmtpu.p2p.fuzz import FuzzConnConfig

            fuzz_cfg = FuzzConnConfig(
                mode=config.p2p.test_fuzz_mode,
                max_delay_s=config.p2p.test_fuzz_max_delay_ms / 1000.0,
                prob_drop_rw=config.p2p.test_fuzz_prob_drop_rw,
                prob_drop_conn=config.p2p.test_fuzz_prob_drop_conn,
                prob_sleep=config.p2p.test_fuzz_prob_sleep,
                seed=config.p2p.test_fuzz_seed or None,
                partition_ids=[
                    p.strip() for p in
                    config.p2p.test_fuzz_partition_ids.split(",")
                    if p.strip()])
        self.fuzz_config = fuzz_cfg
        if shaper is None and fuzz_cfg is None:
            return None

        def wrap(conn, peer_id):
            # fuzz innermost so shaping (partition/latency) applies to
            # the stream the fuzzer lets through
            if fuzz_cfg is not None:
                from tmtpu.p2p.fuzz import FuzzedConnection

                conn = FuzzedConnection(conn, fuzz_cfg, peer_id=peer_id)
            if shaper is not None:
                conn = shaper.wrap(conn, peer_id)
            return conn

        return wrap

    def _only_validator_is_us(self) -> bool:
        """node.go onlyValidatorIsUs — a single-validator chain where we ARE
        the validator has no one to sync from."""
        if self.state.validators is None or self.state.validators.size() != 1:
            return False
        try:
            addr = self.priv_validator.get_pub_key().address()
        except Exception:  # noqa: BLE001
            return False
        return self.state.validators.validators[0].address == addr

    def on_start(self) -> None:
        if self.verify_device["backend"] == "tpu":
            # compile this validator set's flush shapes before any peer
            # can send a vote (crypto/batch.py warm_validator_set)
            from tmtpu.libs import log

            warmed = crypto_batch.warm_validator_set(
                self.consensus.state.validators)
            log.default_logger().with_fields(module="crypto").info(
                "verify shapes warmed", shapes=len(warmed),
                seconds=round(sum(w[3] for w in warmed), 1))
        self.indexer_service.start()
        if self.switch is not None:
            self.switch.start()
        if self.state_sync:
            import threading

            threading.Thread(target=self._statesync_routine, daemon=True,
                             name="statesync").start()
        elif not self.fast_sync:
            # with fast sync on, the blocksync reactor starts consensus via
            # SwitchToConsensus once caught up (blockchain/v0/reactor.go:303)
            self.consensus.start()
        if self.rpc_server is not None:
            self.rpc_server.start()
        if self.grpc_api_server is not None:
            self.grpc_api_server.start()
        if self.pprof_server is not None:
            self.pprof_server.start()
        if self.watchdog is not None:
            self.watchdog.start()

    def on_stop(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.pprof_server is not None:
            self.pprof_server.stop()
        if self.grpc_api_server is not None:
            self.grpc_api_server.stop()
        if self.rpc_server is not None:
            self.rpc_server.stop()
        self.consensus.stop()
        if self.switch is not None:
            self.switch.stop()
        self.indexer_service.stop()
        self.proxy_app.stop()
        if self.signer_endpoint is not None:
            self.signer_endpoint.close()

    @property
    def p2p_port(self) -> int:
        return self.transport.listen_port if self.switch else 0

    # convenience used by RPC + tests
    @property
    def chain_id(self) -> str:
        return self.genesis_doc.chain_id

    def latest_state(self):
        return self.consensus.state


def default_node(config: Config) -> Node:
    """node.go DefaultNewNode."""
    return Node(config)
