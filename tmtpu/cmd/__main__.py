"""CLI (reference: cmd/tendermint/main.go:15-56) —
``python -m tmtpu.cmd <command>``.

Commands: init, start, testnet, rollback, replay, version, show-node-id,
show-validator, gen-validator, unsafe-reset-all.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import time

from tmtpu import version as ver
from tmtpu.config.config import Config


def _load_config(home: str) -> Config:
    """config.toml (reference layout) wins; legacy config.json still
    loads; env TMTPU_<SECTION>_<FIELD> overrides either."""
    from tmtpu.config import toml as cfg_toml

    toml_path = os.path.join(os.path.expanduser(home), "config",
                             "config.toml")
    if os.path.exists(toml_path):
        cfg = cfg_toml.load_config(toml_path)
        cfg.base.home = home
        return cfg
    cfg = Config.default()
    cfg.base.home = home
    cfg_path = os.path.join(os.path.expanduser(home), "config",
                            "config.json")
    if os.path.exists(cfg_path):
        with open(cfg_path) as f:
            data = json.load(f)
        for section, vals in data.items():
            obj = getattr(cfg, section, None)
            if obj is None:
                continue
            for k, v in vals.items():
                if hasattr(obj, k):
                    setattr(obj, k, v)
    cfg_toml._apply_env_overrides(cfg)  # env wins on every config path
    return cfg


def cmd_init(args) -> int:
    """init — private validator, node key, genesis (commands/init.go)."""
    from tmtpu.privval.file_pv import FilePV
    from tmtpu.types.genesis import GenesisDoc, GenesisValidator

    cfg = _load_config(args.home)
    home = os.path.expanduser(args.home)
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    pv = FilePV.load_or_generate(
        cfg.rooted(cfg.base.priv_validator_key_file),
        cfg.rooted(cfg.base.priv_validator_state_file))
    gen_path = cfg.genesis_path
    if not os.path.exists(gen_path):
        doc = GenesisDoc(
            chain_id=args.chain_id or f"test-chain-{os.urandom(3).hex()}",
            genesis_time=time.time_ns(),
            validators=[GenesisValidator(pv.get_pub_key(), 10)],
        )
        doc.save_as(gen_path)
        print(f"Generated genesis file: {gen_path}")
    else:
        print(f"Found genesis file: {gen_path}")
    # write default config.toml if absent (config/toml.go writer)
    cfg_path = os.path.join(home, "config", "config.toml")
    if not os.path.exists(cfg_path):
        from tmtpu.config import toml as cfg_toml

        cfg_toml.write_config(cfg, cfg_path)
        print(f"Generated config file: {cfg_path}")
    print(f"Validator address: {pv.address().hex().upper()}")
    return 0


def _rpc_dumps(rpc_laddr: str, out_dir: str) -> None:
    """Fetch the standard debug RPC dumps into ``out_dir``
    (debug/util.go dumpStatus/dumpNetInfo/dumpConsensusState)."""
    import urllib.request

    base = rpc_laddr.replace("tcp://", "http://")
    for name in ("status", "consensus_state", "dump_consensus_state",
                 "net_info", "num_unconfirmed_txs"):
        try:
            with urllib.request.urlopen(f"{base}/{name}", timeout=10) as r:
                body = r.read()
            with open(os.path.join(out_dir, f"{name}.json"), "wb") as f:
                f.write(body)
        except Exception as e:  # noqa: BLE001
            print(f"  {name}: {e}", file=sys.stderr)


def _copy_home_debug(home: str, out_dir: str) -> None:
    """WAL + config copies for a debug archive (debug/kill.go
    copyWAL/copyConfig)."""
    cfg = _load_config(home)
    wal_dir = os.path.dirname(cfg.rooted(cfg.consensus.wal_file))
    if os.path.isdir(wal_dir):
        shutil.copytree(wal_dir, os.path.join(out_dir, "cs.wal"),
                        dirs_exist_ok=True)
    conf_dir = cfg.rooted("config")
    if os.path.isdir(conf_dir):
        os.makedirs(os.path.join(out_dir, "config"), exist_ok=True)
        # never exfiltrate PRIVATE KEYS into a debug archive that gets
        # shared around — resolve the configured paths, not hardcoded
        # names (priv_validator_key_file is operator-settable)
        secret_paths = {
            os.path.realpath(cfg.rooted(cfg.base.priv_validator_key_file)),
            os.path.realpath(cfg.rooted(cfg.base.node_key_file)),
        }
        for fn in os.listdir(conf_dir):
            src = os.path.join(conf_dir, fn)
            if os.path.realpath(src) in secret_paths:
                continue
            if os.path.isfile(src):
                shutil.copy2(src, os.path.join(out_dir, "config", fn))


def cmd_debug_dump(args) -> int:
    """debug dump [dir] — poll a node's state every --frequency seconds
    into timestamped archives (commands/debug/dump.go); --iterations
    bounds the loop (the reference polls forever)."""
    import tempfile
    import zipfile

    out_root = os.path.expanduser(args.output_dir)
    os.makedirs(out_root, exist_ok=True)
    it = 0
    while True:
        it += 1
        stamp = time.strftime("%Y%m%d-%H%M%S")
        with tempfile.TemporaryDirectory() as tmp:
            _rpc_dumps(args.rpc_laddr, tmp)
            # iteration suffix: sub-second --frequency must not
            # overwrite the previous archive (same-second stamp)
            archive = os.path.join(out_root, f"{stamp}-{it:04d}.zip")
            with zipfile.ZipFile(archive, "w",
                                 zipfile.ZIP_DEFLATED) as z:
                for fn in sorted(os.listdir(tmp)):
                    z.write(os.path.join(tmp, fn), fn)
        print(f"Wrote debug archive {archive}")
        if args.iterations and it >= args.iterations:
            return 0
        time.sleep(args.frequency)


def cmd_debug_kill(args) -> int:
    """debug kill <pid> <out.zip> — aggregate node state (RPC dumps +
    WAL + config), archive it, then SIGABRT the process
    (commands/debug/kill.go)."""
    import signal as _signal
    import tempfile
    import zipfile

    with tempfile.TemporaryDirectory() as tmp:
        _rpc_dumps(args.rpc_laddr, tmp)
        try:
            _copy_home_debug(args.home, tmp)
        except Exception as e:  # noqa: BLE001
            print(f"  home copy: {e}", file=sys.stderr)
        out = os.path.expanduser(args.out_file)
        with zipfile.ZipFile(out, "w", zipfile.ZIP_DEFLATED) as z:
            for root, _dirs, files in os.walk(tmp):
                for fn in files:
                    p = os.path.join(root, fn)
                    z.write(p, os.path.relpath(p, tmp))
    print(f"Wrote debug archive {out}")
    try:
        os.kill(args.pid, _signal.SIGABRT)
        print(f"Sent SIGABRT to {args.pid}")
    except ProcessLookupError:
        print(f"no such process {args.pid}", file=sys.stderr)
        return 1
    return 0


def cmd_start(args) -> int:
    """start — run the node (commands/run_node.go:100)."""
    import faulthandler

    from tmtpu.node.node import Node

    cfg = _load_config(args.home)
    # deadlock observability (the reference's deadlock build tag + debug
    # kill): SIGUSR1 dumps every thread's stack to stderr
    try:
        faulthandler.register(signal.SIGUSR1, all_threads=True)
    except (AttributeError, ValueError):
        pass
    if args.proxy_app:
        cfg.base.proxy_app = args.proxy_app
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.crypto_backend:
        cfg.base.crypto_backend = args.crypto_backend
    if getattr(args, "misbehaviors", ""):
        cfg.base.misbehaviors = args.misbehaviors
    node = Node(cfg)
    node.start()
    rpc = node.rpc_server
    print(f"Node started. chain_id={node.chain_id}"
          + (f" rpc=127.0.0.1:{rpc.port}" if rpc else ""))
    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        print("Stopping node...")
        node.stop()
    return 0


def cmd_sidecar(args) -> int:
    """sidecar — run the standalone verification daemon: one process
    owns the JAX device and serves batched verify (+ on-device tally)
    to every node on the host; nodes select it with
    ``crypto_backend=sidecar``. Address resolution: --addr flag,
    [sidecar] addr, TMTPU_SIDECAR_ADDR, then <home>/data/sidecar.sock."""
    from tmtpu.sidecar.client import default_addr
    from tmtpu.sidecar.server import SidecarServer

    cfg = _load_config(args.home)
    addr = (args.addr or cfg.sidecar.addr or
            default_addr(os.path.expanduser(args.home)))
    if args.backend:
        cfg.sidecar.backend = args.backend
    os.makedirs(os.path.join(os.path.expanduser(args.home), "data"),
                exist_ok=True)
    # the daemon's engine shares crypto/batch.py with a node process, so
    # the [crypto] resilience knobs (breaker, deadlines, sigcache) apply
    from tmtpu.crypto import batch as crypto_batch

    crypto_batch.configure(cfg.crypto)
    # the one process on the chip: place the compile cache, open JAX,
    # and refuse an explicit "tpu" that found no TPU (SystemExit)
    dev = crypto_batch.start_backend(cfg.sidecar.backend, "sidecar")
    server = SidecarServer(
        addr,
        backend=cfg.sidecar.backend,
        max_queue_lanes=cfg.sidecar.max_queue_lanes,
        max_lanes_per_dispatch=cfg.sidecar.max_lanes_per_dispatch,
        max_frame_bytes=cfg.sidecar.max_frame_bytes,
        request_deadline_s=cfg.sidecar.request_deadline_ns / 1e9,
        health_laddr=args.health_laddr or cfg.sidecar.health_laddr,
        mesh_devices=cfg.sidecar.mesh_devices,
        shard_min_lanes=cfg.sidecar.shard_min_lanes,
        profile_dir=os.path.join(os.path.expanduser(args.home), "data",
                                 "profile"))
    warm = cfg.sidecar.warm_on_start and not args.no_warm
    server.start()
    if warm:
        print("Warming verify kernels (one-time compile)...",
              flush=True)
        warm_s = server.warm()
        print(f"Warm-up done in {warm_s:.1f}s "
              f"(backend={server.backend_name()})")
    print(f"Sidecar listening on {server.addr} "
          f"backend={server.backend_name()} platform={dev['platform']} "
          f"device_kind={dev['kind']!r} devices={dev['count']} "
          f"native={dev['native']} id={server.server_id}", flush=True)
    # SIGINT stops immediately (operator ^C); SIGTERM drains first —
    # stop accepting, answer OVERLOADED (clients fall back in-process
    # penalty-free), finish in-flight joint dispatches, exit 0
    stop, term = [], []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: term.append(1))
    try:
        while not stop and not term:
            time.sleep(0.2)
        if term and not stop:
            print("SIGTERM: draining sidecar "
                  "(new requests get OVERLOADED)...", flush=True)
            clean = server.drain(
                timeout=cfg.sidecar.request_deadline_ns / 1e9 + 5.0)
            print("Drain complete" if clean
                  else "Drain timed out; stopping anyway")
    finally:
        print("Stopping sidecar...")
        server.stop()
    return 0


def cmd_lightserve(args) -> int:
    """lightserve — run the light-client commit-proof serving daemon:
    one process terminates many concurrent light-client sessions
    against a full node's RPC, answering from a trust-period-aware
    verified-fact cache and coalescing same-height cold misses into
    single joint resolves. Address resolution: --addr flag,
    [lightserve] addr, TMTPU_LIGHTSERVE_ADDR, then
    <home>/data/lightserve.sock."""
    from tmtpu.light.client import TrustOptions
    from tmtpu.light.provider import HTTPProvider
    from tmtpu.lightserve.client import default_addr
    from tmtpu.lightserve.server import LightserveServer

    cfg = _load_config(args.home)
    ls = cfg.lightserve
    addr = (args.addr or ls.addr or
            default_addr(os.path.expanduser(args.home)))
    upstream = (args.upstream or ls.upstream).rstrip("/")
    chain_id = args.chain_id or ls.chain_id
    trust_height = args.trust_height or ls.trust_height
    trust_hash = args.trust_hash or ls.trust_hash
    if not chain_id:
        print("lightserve needs a chain id (--chain-id or "
              "[lightserve] chain_id)")
        return 1
    if trust_height <= 0 or not trust_hash:
        print("lightserve needs a social-consensus trust anchor "
              "(--trust-height/--trust-hash or the [lightserve] pair)")
        return 1
    backend = args.backend or ls.backend
    os.makedirs(os.path.join(os.path.expanduser(args.home), "data"),
                exist_ok=True)
    # commit checks share crypto/batch.py, so [crypto] resilience knobs
    # apply; backend "sidecar" additionally coalesces them with every
    # other host process's lanes in the verification daemon
    from tmtpu.crypto import batch as crypto_batch

    crypto_batch.configure(cfg.crypto)
    if backend == "sidecar":
        crypto_batch.configure_sidecar(
            cfg.sidecar, home=os.path.expanduser(args.home))
    crypto_batch.start_backend(backend, "lightserve")
    server = LightserveServer(
        addr, HTTPProvider(chain_id, upstream),
        TrustOptions(period_ns=ls.trusting_period_ns,
                     height=trust_height,
                     hash=bytes.fromhex(trust_hash)),
        chain_id,
        backend=None if backend == "auto" else backend,
        max_clock_drift_ns=ls.max_clock_drift_ns,
        max_client_skew_ns=ls.max_client_skew_ns,
        reply_workers=ls.reply_workers,
        cache_max_facts=ls.cache_max_facts,
        store_max_blocks=ls.store_max_blocks,
        max_queue_sessions=ls.max_queue_sessions,
        max_frame_bytes=ls.max_frame_bytes,
        request_deadline_s=ls.request_deadline_ns / 1e9,
        backwards_limit=ls.backwards_limit,
        health_laddr=args.health_laddr or ls.health_laddr,
        hit_rate_floor=ls.hit_rate_floor,
        hit_rate_min_lookups=ls.hit_rate_min_lookups,
        backlog_ceiling=ls.backlog_ceiling)
    server.start()  # fetches + verifies the trust anchor
    print(f"Lightserve listening on {server.addr} chain={chain_id} "
          f"anchor={trust_height} upstream={upstream} "
          f"id={server.server_id}")
    # SIGINT stops immediately; SIGTERM drains (new sessions answered
    # OVERLOADED, queued joint resolves finish) then exits 0
    stop, term = [], []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: term.append(1))
    try:
        while not stop and not term:
            time.sleep(0.2)
        if term and not stop:
            print("SIGTERM: draining lightserve "
                  "(new sessions get OVERLOADED)...", flush=True)
            clean = server.drain(
                timeout=ls.request_deadline_ns / 1e9 + 5.0)
            print("Drain complete" if clean
                  else "Drain timed out; stopping anyway")
    finally:
        print("Stopping lightserve...")
        server.stop()
    return 0


def cmd_version(args) -> int:
    print(ver.TMCoreSemVer)
    return 0


def cmd_show_validator(args) -> int:
    from tmtpu.privval.file_pv import FilePV

    cfg = _load_config(args.home)
    pv = FilePV.load(cfg.rooted(cfg.base.priv_validator_key_file),
                     cfg.rooted(cfg.base.priv_validator_state_file))
    from tmtpu.libs import amino_json

    pub = pv.get_pub_key()
    # reference `tendermint show-validator` prints the amino JSON form
    print(json.dumps(amino_json.marshal_pub_key(pub)))
    return 0


def cmd_gen_validator(args) -> int:
    from tmtpu.crypto import ed25519
    from tmtpu.libs import amino_json

    priv = ed25519.gen_priv_key()
    pub = priv.pub_key()
    # amino JSON shape (cmd/tendermint/commands/gen_validator.go) so the
    # output pastes into a reference genesis/priv_validator_key file
    print(json.dumps({
        "address": pub.address().hex().upper(),
        "pub_key": amino_json.marshal_pub_key(pub),
        "priv_key": amino_json.marshal_priv_key(priv),
    }, indent=2))
    return 0


def _reset_file_pv(key_file: str, state_file: str) -> None:
    """reset.go resetFilePV: existing key keeps its identity but the
    sign state returns to genesis (a FRESH zero state file — FilePV.load
    refuses to start without one); no key means generate both."""
    import json as _json

    from tmtpu.libs import amino_json
    from tmtpu.privval.file_pv import FilePV

    if os.path.exists(key_file):
        with open(key_file) as f:
            kd = _json.load(f)
        pv = FilePV(amino_json.unmarshal_priv_key(kd["priv_key"]),
                    key_file, state_file)
        os.makedirs(os.path.dirname(state_file) or ".", exist_ok=True)
        pv.save()
        print("Reset private validator file to genesis state")
    else:
        os.makedirs(os.path.dirname(key_file) or ".", exist_ok=True)
        os.makedirs(os.path.dirname(state_file) or ".", exist_ok=True)
        FilePV.generate(key_file, state_file)
        print("Generated private validator file")


def cmd_unsafe_reset_all(args) -> int:
    """Wipe data dir + addrbook, reset validator sign state to genesis
    (commands/reset.go resetAll)."""
    cfg = _load_config(args.home)
    if not getattr(args, "keep_addr_book", False):
        ab = cfg.rooted("config/addrbook.json")  # node.py:258 path
        if os.path.exists(ab):
            os.unlink(ab)
            print(f"Removed address book {ab}")
    else:
        print("The address book remains intact")
    data = cfg.rooted(cfg.base.db_dir)
    if os.path.isdir(data):
        shutil.rmtree(data)
        os.makedirs(data)
        print(f"Removed all data in {data}")
    _reset_file_pv(cfg.rooted(cfg.base.priv_validator_key_file),
                   cfg.rooted(cfg.base.priv_validator_state_file))
    return 0


def cmd_reset_state(args) -> int:
    """Remove the chain databases + WAL, keep keys AND validator sign
    state (commands/reset.go resetState)."""
    cfg = _load_config(args.home)
    data = cfg.rooted(cfg.base.db_dir)
    for name in ("blockstore.db", "state.db", "evidence.db",
                 "tx_index.db"):
        p = os.path.join(data, name)
        if os.path.exists(p):
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)
            print(f"Removed {p}")
    # the WAL lives wherever consensus.wal_file points (config.py:27) —
    # a stale WAL after a state wipe bricks startup with "#ENDHEIGHT >=
    # current height"
    wal_path = cfg.rooted(cfg.consensus.wal_file)
    wal_dir = os.path.dirname(wal_path)
    if os.path.basename(wal_dir) == "cs.wal":
        if os.path.isdir(wal_dir):
            shutil.rmtree(wal_dir)
            print(f"Removed {wal_dir}")
    else:
        # custom location: remove the group head + rotated segments only
        base = os.path.basename(wal_path)
        for fn in sorted(os.listdir(wal_dir)) if os.path.isdir(wal_dir) \
                else []:
            if fn == base or fn.startswith(base + "."):
                os.unlink(os.path.join(wal_dir, fn))
                print(f"Removed {os.path.join(wal_dir, fn)}")
    return 0


def cmd_unsafe_reset_priv_validator(args) -> int:
    """Reset this node's validator sign state to genesis
    (commands/reset.go ResetPrivValidatorCmd)."""
    cfg = _load_config(args.home)
    _reset_file_pv(cfg.rooted(cfg.base.priv_validator_key_file),
                   cfg.rooted(cfg.base.priv_validator_state_file))
    return 0


def cmd_gen_node_key(args) -> int:
    """Generate the node key and print its ID
    (commands/gen_node_key.go — errors if one already exists)."""
    from tmtpu.p2p.key import NodeKey

    cfg = _load_config(args.home)
    path = cfg.rooted(cfg.base.node_key_file)
    if os.path.exists(path):
        print(f"node key at {path!r} already exists", file=sys.stderr)
        return 1
    nk = NodeKey.load_or_gen(path)
    print(nk.node_id)
    return 0


def cmd_probe_upnp(args) -> int:
    """Probe the LAN for a UPnP IGD and report its external IP
    (commands/probe_upnp.go)."""
    import json as _json

    from tmtpu.p2p import upnp

    gw = upnp.discover(timeout_s=args.timeout)
    if gw is None:
        print(_json.dumps({"success": False}))
        return 1
    out = {"success": True, "control_url": gw.control_url,
           "service": gw.service}
    try:
        out["external_ip"] = gw.external_ip()
    except Exception as e:  # noqa: BLE001 — gateway present, call failed
        out["external_ip_error"] = repr(e)
    print(_json.dumps(out))
    return 0


def cmd_replay_console(args) -> int:
    """replay-console — step through the consensus WAL's in-progress
    height one message at a time (commands/replay.go replay-console):
    app replay via handshake first, then each WAL message is printed and
    applied on Enter (or immediately with --no-input)."""
    import json as _json

    from tmtpu.node.node import Node

    cfg = _load_config(args.home)
    cfg.rpc.laddr = ""
    cfg.p2p.laddr = ""
    node = Node(cfg)  # handshake replays the app to the store height

    def on_msg(m):
        print("--> " + _json.dumps(_proto_to_jsonable(m)))
        if not args.no_input:
            input("press Enter to apply...")

    try:
        cs = node.consensus
        cs.do_wal_catchup = False  # we drive it ourselves
        # mirror on_start's recovery sequence (state.py:148-151), minus
        # the live round re-drive: an inspection tool must never sign or
        # append to the WAL it is examining
        cs._reconstruct_last_commit()
        cs.catchup_replay(on_msg=on_msg, live_redrive=False)
        rs = cs.rs
        print(f"Replayed console to height {rs.height}, round {rs.round}, "
              f"step {rs.step}")
    finally:
        # the node was never start()ed, so node.stop() would no-op
        # (libs/service.py guards on _started) — shut the pieces that
        # Node.__init__ opened down explicitly
        if node.consensus.wal is not None:
            node.consensus.wal.close()
        node.proxy_app.stop()
    return 0


def cmd_show_node_id(args) -> int:
    from tmtpu.p2p.key import NodeKey

    cfg = _load_config(args.home)
    nk = NodeKey.load_or_gen(cfg.rooted(cfg.base.node_key_file))
    print(nk.node_id)
    return 0


def cmd_rollback(args) -> int:
    """rollback — state back one height (commands/rollback.go)."""
    from tmtpu.state.rollback import RollbackError, rollback
    from tmtpu.state.store import StateStore
    from tmtpu.store.block_store import BlockStore
    from tmtpu.libs.db import SQLiteDB

    cfg = _load_config(args.home)
    if cfg.base.db_backend != "sqlite":
        print("rollback requires a persistent (sqlite) db_backend",
              file=sys.stderr)
        return 1
    data = cfg.rooted(cfg.base.db_dir)
    bs = BlockStore(SQLiteDB(os.path.join(data, "blockstore.sqlite")))
    ss = StateStore(SQLiteDB(os.path.join(data, "state.sqlite")))
    try:
        height, app_hash = rollback(bs, ss)
    except RollbackError as e:
        print(f"rollback failed: {e}", file=sys.stderr)
        return 1
    print(f"Rolled back state to height {height} and hash "
          f"{app_hash.hex().upper()}")
    return 0


def cmd_replay(args) -> int:
    """replay — re-sync the app from the block store via handshake
    (commands/replay.go)."""
    from tmtpu.node.node import Node

    cfg = _load_config(args.home)
    cfg.rpc.laddr = ""
    cfg.p2p.laddr = ""
    node = Node(cfg)  # the constructor's handshake IS the replay
    print(f"Replayed to height {node.state.last_block_height}, app hash "
          f"{node.state.app_hash.hex().upper()}")
    node.stop()
    return 0


def cmd_testnet(args) -> int:
    """testnet — N validator home dirs wired full-mesh
    (commands/testnet.go)."""
    from tmtpu.config import toml as cfg_toml
    from tmtpu.privval.file_pv import FilePV
    from tmtpu.p2p.key import NodeKey
    from tmtpu.types.genesis import GenesisDoc, GenesisValidator

    out = os.path.expanduser(args.output_dir)
    n = args.validators
    base_p2p, base_rpc = args.starting_port, args.starting_port + 1000
    pvs, node_ids = [], []
    homes = []
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        os.makedirs(os.path.join(home, "config"), exist_ok=True)
        os.makedirs(os.path.join(home, "data"), exist_ok=True)
        homes.append(home)
        cfg = Config.default()
        cfg.base.home = home
        pvs.append(FilePV.load_or_generate(
            cfg.rooted(cfg.base.priv_validator_key_file),
            cfg.rooted(cfg.base.priv_validator_state_file)))
        node_ids.append(NodeKey.load_or_gen(
            cfg.rooted(cfg.base.node_key_file)).node_id)
    gen = GenesisDoc(
        chain_id=args.chain_id or f"testnet-{os.urandom(3).hex()}",
        genesis_time=time.time_ns(),
        validators=[GenesisValidator(pv.get_pub_key(), 1) for pv in pvs],
    )
    peers = [f"{node_ids[i]}@127.0.0.1:{base_p2p + i}" for i in range(n)]
    for i, home in enumerate(homes):
        cfg = Config.default()
        cfg.base.home = home
        cfg.base.moniker = f"node{i}"
        cfg.p2p.laddr = f"tcp://127.0.0.1:{base_p2p + i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{base_rpc + i}"
        cfg.p2p.persistent_peers = ",".join(
            p for j, p in enumerate(peers) if j != i)
        gen.save_as(cfg.genesis_path)
        cfg_toml.write_config(
            cfg, os.path.join(home, "config", "config.toml"))
    print(f"Successfully initialized {n} node directories in {out}")
    return 0


def cmd_reindex_event(args) -> int:
    """reindex-event — rebuild tx/block-event indexes from the stores
    (commands/reindex_event.go)."""
    from tmtpu.libs.db import SQLiteDB
    from tmtpu.state.store import StateStore
    from tmtpu.state.txindex import (
        KVBlockIndexer, KVTxIndexer, reindex_events,
    )
    from tmtpu.store.block_store import BlockStore

    cfg = _load_config(args.home)

    def db(name):
        return SQLiteDB(cfg.rooted(os.path.join(cfg.base.db_dir,
                                                f"{name}.sqlite")))

    n = reindex_events(BlockStore(db("blockstore")), StateStore(db("state")),
                       KVTxIndexer(db("txindex")),
                       KVBlockIndexer(db("blockindex")),
                       first=args.start_height, last=args.end_height)
    print(f"Reindexed {n} heights")
    return 0


def cmd_compact_db(args) -> int:
    """experimental-compact-goleveldb analogue — VACUUM every sqlite DB in
    the data dir to reclaim space after pruning."""
    import sqlite3

    cfg = _load_config(args.home)
    data = cfg.rooted(cfg.base.db_dir)
    total = 0
    for fname in sorted(os.listdir(data) if os.path.isdir(data) else []):
        if not fname.endswith(".sqlite"):
            continue
        path = os.path.join(data, fname)
        before = os.path.getsize(path)
        conn = sqlite3.connect(path)
        conn.execute("VACUUM")
        conn.close()
        after = os.path.getsize(path)
        total += before - after
        print(f"{fname}: {before} -> {after} bytes")
    print(f"Reclaimed {total} bytes")
    return 0


def cmd_light(args) -> int:
    """light — run a light-client-backed RPC proxy daemon
    (commands/light.go)."""
    import threading

    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.light.client import TrustOptions, open_client
    from tmtpu.light.provider import HTTPProvider
    from tmtpu.light.proxy import LightProxy

    primary = args.primary.rstrip("/")
    witnesses = [w for w in (args.witnesses or "").split(",") if w]
    home = os.path.expanduser(args.home)
    # commit checks share crypto/batch.py: the home's [crypto] knobs and
    # crypto_backend apply, and a device backend gets its compile cache
    cfg = _load_config(home)
    crypto_batch.configure(cfg.crypto)
    crypto_batch.set_default_backend(cfg.base.crypto_backend)
    if cfg.base.crypto_backend == "sidecar":
        crypto_batch.configure_sidecar(cfg.sidecar, home=home)
    crypto_batch.start_backend(cfg.base.crypto_backend, "light")
    lc = open_client(
        home, args.chain_id,
        TrustOptions(period_ns=int(args.trusting_period * 1e9),
                     height=args.trusted_height,
                     hash=bytes.fromhex(args.trusted_hash)),
        HTTPProvider(args.chain_id, primary),
        [HTTPProvider(args.chain_id, w) for w in witnesses],
        sequential=args.sequential)
    proxy = LightProxy(lc, primary, laddr=args.laddr)
    proxy.start()
    print(f"light proxy for {args.chain_id} listening on {proxy.laddr} "
          f"(primary {primary}, {len(witnesses)} witnesses)")
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        proxy.stop()
    return 0


def _proto_to_jsonable(m):
    """Generic ProtoMessage -> JSON-able dict (bytes as hex, nested
    messages recursed, absent fields omitted) — the wal2json view."""
    from tmtpu.libs.protoio import ProtoMessage

    if isinstance(m, ProtoMessage):
        out = {}
        for _, name, _spec in m.FIELDS:
            v = getattr(m, name)
            if v is not None:
                out[name] = _proto_to_jsonable(v)
        return out
    if isinstance(m, (bytes, bytearray)):
        return bytes(m).hex()
    if isinstance(m, list):
        return [_proto_to_jsonable(x) for x in m]
    return m


def _jsonable_to_proto(cls, data):
    """Inverse of _proto_to_jsonable for a known message class."""
    kw = {}
    for _, name, spec in cls.FIELDS:
        if name not in data:
            continue
        v = data[name]
        kind = spec[0] if isinstance(spec, tuple) else spec
        if kind in ("msg", "msg!"):
            kw[name] = _jsonable_to_proto(spec[1], v)
        elif kind == "rep":
            inner = spec[1]
            if isinstance(inner, tuple):  # ("msg"/"msg!", cls)
                kw[name] = [_jsonable_to_proto(inner[1], x) for x in v]
            elif inner == "bytes":
                kw[name] = [bytes.fromhex(x) for x in v]
            else:
                kw[name] = list(v)
        elif kind == "bytes":
            kw[name] = bytes.fromhex(v)
        else:
            kw[name] = v
    return cls(**kw)


def cmd_wal2json(args) -> int:
    """wal2json — decode a consensus WAL to JSON lines (reference
    scripts/wal2json/main.go). Tolerates a torn tail unless --strict."""
    import json as _json

    from tmtpu.consensus.wal import WAL

    for msg in WAL.iter_messages(args.wal_file, strict=args.strict):
        print(_json.dumps(_proto_to_jsonable(msg)))
    return 0


def cmd_json2wal(args) -> int:
    """json2wal — rebuild a WAL file from wal2json output (reference
    scripts/json2wal/main.go; used to craft replay/corruption fixtures)."""
    import json as _json
    import struct
    import zlib

    from tmtpu.consensus.wal import WALMessagePB
    from tmtpu.libs import protoio

    with open(args.json_file) as jf, open(args.wal_file, "wb") as wf:
        for line in jf:
            line = line.strip()
            if not line:
                continue
            msg = _jsonable_to_proto(WALMessagePB, _json.loads(line))
            payload = msg.encode()
            wf.write(struct.pack(">I", zlib.crc32(payload))
                     + protoio.encode_uvarint(len(payload)) + payload)
    return 0


def cmd_signer_harness(args) -> int:
    """signer-harness — remote-signer conformance checks
    (tools/tm-signer-harness/main.go)."""
    from tmtpu.privval.harness import HarnessFailure, run_harness

    expect = bytes.fromhex(args.expect_pubkey) if args.expect_pubkey else None
    try:
        return run_harness(args.laddr, args.chain_id,
                           accept_deadline_s=args.accept_deadline,
                           expect_pubkey=expect)
    except HarnessFailure as e:
        print(f"FAIL {e}")
        return 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tmtpu",
                                description="TPU-native BFT consensus node")
    p.add_argument("--home", default=os.environ.get("TMHOME", "~/.tmtpu"))
    _sub = p.add_subparsers(dest="cmd", required=True)

    class _Sub:
        """--home is accepted before OR after the subcommand, like the
        reference's cobra persistent flag; SUPPRESS keeps the subparser
        from clobbering a pre-subcommand --home with its default."""

        @staticmethod
        def add_parser(*a, **kw):
            sp = _sub.add_parser(*a, **kw)
            sp.add_argument("--home", default=argparse.SUPPRESS)
            return sp

    sub = _Sub()

    sp = sub.add_parser("init", help="initialize home dir")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("start", help="run the node")
    sp.add_argument("--proxy-app", default="")
    sp.add_argument("--rpc-laddr", dest="rpc_laddr", default="")
    sp.add_argument("--crypto-backend", default="",
                    choices=["", "auto", "cpu", "tpu", "sidecar"])
    sp.add_argument("--misbehaviors", default="",
                    help="maverick-style schedule 'double-prevote@3,...' "
                         "(byzantine test nets only)")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("sidecar",
                        help="run the shared batch-verify daemon")
    sp.add_argument("--addr", default="",
                    help="listen address (unix:///path.sock or "
                         "tcp://host:port); default [sidecar] addr / "
                         "TMTPU_SIDECAR_ADDR / <home>/data/sidecar.sock")
    sp.add_argument("--backend", default="",
                    choices=["", "auto", "cpu", "tpu"],
                    help="daemon-side verify engine")
    sp.add_argument("--health-laddr", dest="health_laddr", default="",
                    help="HTTP host:port for /healthz + /metrics")
    sp.add_argument("--no-warm", action="store_true",
                    help="skip the startup kernel warm-up compile")
    sp.set_defaults(fn=cmd_sidecar)

    sp = sub.add_parser("lightserve",
                        help="run the light-client commit-proof "
                             "serving daemon")
    sp.add_argument("--addr", default="",
                    help="listen address (unix:///path.sock or "
                         "tcp://host:port); default [lightserve] addr / "
                         "TMTPU_LIGHTSERVE_ADDR / "
                         "<home>/data/lightserve.sock")
    sp.add_argument("--upstream", default="",
                    help="full node RPC URL feeding the verified spine")
    sp.add_argument("--chain-id", dest="chain_id", default="")
    sp.add_argument("--trust-height", dest="trust_height", type=int,
                    default=0)
    sp.add_argument("--trust-hash", dest="trust_hash", default="",
                    help="hex header hash at --trust-height")
    sp.add_argument("--backend", default="",
                    choices=["", "auto", "cpu", "tpu", "sidecar"],
                    help="commit-verify engine; 'sidecar' rides the "
                         "host's verification daemon")
    sp.add_argument("--health-laddr", dest="health_laddr", default="",
                    help="HTTP host:port for /healthz + /metrics")
    sp.set_defaults(fn=cmd_lightserve)

    sp = sub.add_parser("version")
    sp.set_defaults(fn=cmd_version)

    sp = sub.add_parser("show-validator")
    sp.set_defaults(fn=cmd_show_validator)

    sp = sub.add_parser("gen-validator")
    sp.set_defaults(fn=cmd_gen_validator)

    sp = sub.add_parser("unsafe-reset-all")
    sp.add_argument("--keep-addr-book", action="store_true",
                    help="keep the address book intact")
    sp.set_defaults(fn=cmd_unsafe_reset_all)

    sp = sub.add_parser("reset-state",
                        help="remove the chain DBs + WAL, keep keys and "
                             "validator sign state")
    sp.set_defaults(fn=cmd_reset_state)

    sp = sub.add_parser("unsafe-reset-priv-validator",
                        help="reset validator sign state to genesis")
    sp.set_defaults(fn=cmd_unsafe_reset_priv_validator)

    sp = sub.add_parser("gen-node-key",
                        help="generate config/node_key.json, print its ID")
    sp.set_defaults(fn=cmd_gen_node_key)

    sp = sub.add_parser("probe-upnp", help="probe the LAN for a UPnP IGD")
    sp.add_argument("--timeout", type=float, default=3.0)
    sp.set_defaults(fn=cmd_probe_upnp)

    sp = sub.add_parser("replay-console",
                        help="step through the consensus WAL interactively")
    sp.add_argument("--no-input", action="store_true",
                    help="apply without pausing")
    sp.set_defaults(fn=cmd_replay_console)

    sp = sub.add_parser("show-node-id")
    sp.set_defaults(fn=cmd_show_node_id)

    sp = sub.add_parser("rollback", help="roll state back one height")
    sp.set_defaults(fn=cmd_rollback)

    sp = sub.add_parser("replay", help="re-sync the app from the stores")
    sp.set_defaults(fn=cmd_replay)

    sp = sub.add_parser("debug", help="capture a running node's state")
    dbg = sp.add_subparsers(dest="debug_cmd")
    dmp = dbg.add_parser("dump", help="poll + archive node state")
    dmp.add_argument("output_dir", nargs="?", default="./debug")
    dmp.add_argument("--rpc-laddr", dest="rpc_laddr",
                     default="tcp://127.0.0.1:26657")
    dmp.add_argument("--frequency", type=float, default=30.0)
    dmp.add_argument("--iterations", type=int, default=0,
                     help="stop after N archives (0 = forever, like the "
                          "reference)")
    dmp.set_defaults(fn=cmd_debug_dump)
    kil = dbg.add_parser("kill",
                         help="archive node state, then SIGABRT the pid")
    kil.add_argument("pid", type=int)
    kil.add_argument("out_file")
    kil.add_argument("--rpc-laddr", dest="rpc_laddr",
                     default="tcp://127.0.0.1:26657")
    kil.set_defaults(fn=cmd_debug_kill)
    # bare `tmtpu debug` behaves like one dump iteration (round-3 CLI)
    sp.set_defaults(fn=cmd_debug_dump, output_dir="./debug",
                    rpc_laddr="tcp://127.0.0.1:26657", frequency=30.0,
                    iterations=1)

    sp = sub.add_parser("reindex-event",
                        help="rebuild tx/block-event indexes from stores")
    sp.add_argument("--start-height", type=int, default=0)
    sp.add_argument("--end-height", type=int, default=0)
    sp.set_defaults(fn=cmd_reindex_event)

    sp = sub.add_parser("compact-db", help="VACUUM the data dir's DBs")
    sp.set_defaults(fn=cmd_compact_db)

    sp = sub.add_parser("light", help="light-client RPC proxy daemon")
    sp.add_argument("chain_id")
    sp.add_argument("--primary", required=True,
                    help="primary full node RPC URL")
    sp.add_argument("--witnesses", default="",
                    help="comma-separated witness RPC URLs")
    sp.add_argument("--trusted-height", type=int, required=True)
    sp.add_argument("--trusted-hash", required=True)
    sp.add_argument("--trusting-period", type=float,
                    default=7 * 24 * 3600.0, help="seconds")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.add_argument("--sequential", action="store_true",
                    help="verify all headers sequentially as opposed to "
                         "using skipping verification")
    sp.set_defaults(fn=cmd_light)

    sp = sub.add_parser("wal2json", help="decode a WAL to JSON lines")
    sp.add_argument("wal_file")
    sp.add_argument("--strict", action="store_true",
                    help="fail on torn/corrupt records instead of stopping")
    sp.set_defaults(fn=cmd_wal2json)

    sp = sub.add_parser("json2wal",
                        help="rebuild a WAL from wal2json output")
    sp.add_argument("json_file")
    sp.add_argument("wal_file")
    sp.set_defaults(fn=cmd_json2wal)

    sp = sub.add_parser("signer-harness",
                        help="remote-signer conformance checks")
    sp.add_argument("chain_id")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:0",
                    help="address the external signer dials "
                         "(tcp:// or unix://)")
    sp.add_argument("--accept-deadline", type=float, default=30.0,
                    help="seconds to wait for the signer to connect")
    sp.add_argument("--expect-pubkey", default="",
                    help="hex pubkey the signer must serve")
    sp.set_defaults(fn=cmd_signer_harness)

    sp = sub.add_parser("testnet", help="generate N validator home dirs")
    sp.add_argument("--validators", type=int, default=4)
    sp.add_argument("--output-dir", dest="output_dir", default="./mytestnet")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--starting-port", dest="starting_port", type=int,
                    default=26656)
    sp.set_defaults(fn=cmd_testnet)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
