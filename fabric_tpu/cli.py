"""fabric-tpu operator CLI (the cmd/{peer,orderer,configtxgen,
cryptogen,osnadmin,discover,ledgerutil} surface in one binary).

Usage: python -m fabric_tpu.cli <command> ...

Commands:
  cryptogen     generate org crypto material onto disk
  configtxgen   genesis block from a JSON profile
  orderer       run an ordering node (JSON config)
  peer          run a peer node (JSON config)
  sidecar-serve run a standalone validation sidecar (one device
                fabric serving many peers' signature batches)
  osnadmin      orderer channel participation (join)
  invoke/query  gateway client round trips
  snapshot      request a ledger snapshot from a peer
  ledgerutil    verify / compare ledger directories offline
  discover      discovery queries against a peer

Configs are JSON (the reference's YAML surface maps 1:1; no external
YAML dependency)."""

from __future__ import annotations

import argparse
import asyncio
import json
import sys


def _cmd_cryptogen(args):
    from fabric_tpu.crypto import cryptogen as cg

    for spec in args.org:
        msp_id, _, domain = spec.partition(":")
        org = cg.generate_org(
            msp_id, domain or f"{msp_id.lower()}.example.com",
            peers=args.peers, orderers=args.orderers, users=args.users,
        )
        out = cg.write_org(org, args.output)
        print(f"wrote {msp_id} material to {out}")


def _cmd_configtxgen(args):
    from fabric_tpu.crypto import cryptogen as cg
    from fabric_tpu.tools import configtxgen as ctg

    with open(args.profile) as f:
        prof = json.load(f)
    app_orgs = [
        ctg.OrgProfile(o["msp_id"], cg.load_org_msp(o["dir"]),
                       [tuple(a) for a in o.get("anchor_peers", [])])
        for o in prof.get("application_orgs", [])
    ]
    orderer_orgs = [
        ctg.OrgProfile(o["msp_id"], cg.load_org_msp(o["dir"]), [])
        for o in prof.get("orderer_orgs", [])
    ]
    profile = ctg.Profile(
        prof["channel"], application_orgs=app_orgs,
        orderer_orgs=orderer_orgs,
        consensus_type=prof.get("consensus", "raft"),
        raft_consenters=[tuple(c) for c in prof.get("consenters", [])],
        max_message_count=prof.get("max_message_count", 500),
        batch_timeout_ms=prof.get("batch_timeout_ms", 200),
    )
    blk = ctg.genesis_block(profile)
    with open(args.output, "wb") as f:
        f.write(blk.SerializeToString())
    print(f"wrote genesis block for {prof['channel']} to {args.output}")


def _node_tls(cfg):
    """Node mTLS material from the typed ``tls`` section (cryptogen's
    nodes/<name>/tls layout)."""
    t = cfg.tls
    if t is None or not t.cert:
        return None
    from fabric_tpu.comm.rpc import TlsProfile

    return TlsProfile.load(t.cert, t.key, t.ca)


async def _run_orderer(cfg):
    from fabric_tpu.crypto import cryptogen as cg
    from fabric_tpu.nodeconfig import OrdererConfig
    from fabric_tpu.ordering.blockcutter import BatchConfig
    from fabric_tpu.ordering.node import OrdererNode
    from fabric_tpu.protos import common_pb2

    assert isinstance(cfg, OrdererConfig)
    signer = None
    if cfg.msp_dir:
        signer = cg.load_signing_identity(cfg.msp_dir, cfg.msp_id)
    node = OrdererNode(
        cfg.id, cfg.data_dir, cfg.cluster,
        host=cfg.host, port=cfg.port,
        batch_config=BatchConfig(
            max_message_count=cfg.max_message_count,
            batch_timeout_s=cfg.batch_timeout_s,
        ),
        consensus=cfg.consensus, view_timeout=cfg.view_timeout,
        signer=signer,
        tls=_node_tls(cfg),
    )
    node.broadcast_rate = cfg.broadcast_rate
    await node.start(operations_port=cfg.operations_port)
    print(f"orderer {node.id} serving on :{node.port}", flush=True)
    for ch in cfg.channels:
        name = ch if isinstance(ch, str) else ch.name
        genesis = None
        if not isinstance(ch, str) and ch.genesis:
            genesis = common_pb2.Block()
            with open(ch.genesis, "rb") as f:
                genesis.ParseFromString(f.read())
        chain = node.join_channel(name, genesis)
        chain.wal_retention = cfg.wal_retention
    await asyncio.Event().wait()


def _build_peer(cfg):
    """Construct the PeerNode from a validated PeerConfig — shared by
    the serving ``peer`` command and the offline ``replay`` catch-up
    (which never starts the server)."""
    from fabric_tpu.crypto import cryptogen as cg
    from fabric_tpu.crypto.msp import MSPManager
    from fabric_tpu.nodeconfig import PeerConfig
    from fabric_tpu.parallel.topology import MeshTopology
    from fabric_tpu.peer.ccaas import CCaaSProxy
    from fabric_tpu.peer.chaincode import ChaincodeRuntime
    from fabric_tpu.peer.node import PeerNode

    assert isinstance(cfg, PeerConfig)
    signer = cg.load_signing_identity(cfg.msp_dir, cfg.msp_id)
    mgr = MSPManager()
    for org_dir in cfg.org_msps:
        mgr.add(cg.load_org_msp(org_dir))
    runtime = ChaincodeRuntime()
    for cc in cfg.chaincodes:
        runtime.register(cc.name, CCaaSProxy(cc.name, cc.host, cc.port))
    return PeerNode(
        cfg.id, cfg.data_dir, mgr, signer, runtime,
        host=cfg.host, port=cfg.port,
        tls=_node_tls(cfg),
        max_package_size=cfg.max_package_size,
        install_require_admin=cfg.install_require_admin,
        pipeline_depth=cfg.pipeline_depth,
        verify_chunk=cfg.verify_chunk,
        mesh_devices=cfg.mesh_devices,
        mesh_topology=MeshTopology.from_config(cfg),
        coalesce_blocks=cfg.coalesce_blocks,
        host_stage_workers=cfg.host_stage_workers,
        recode_device=cfg.recode_device,
        trace_ring_blocks=cfg.trace_ring_blocks,
        trace_slow_factor=cfg.trace_slow_factor,
        slos=cfg.slos,
        vitals_interval_s=cfg.vitals_interval_s,
        vitals_retention=cfg.vitals_retention,
        blackbox_dir=cfg.blackbox_dir,
        device_ledger=cfg.device_ledger,
        autopilot=cfg.autopilot,
        autopilot_tick_s=cfg.autopilot_tick_s,
        autopilot_knobs=cfg.autopilot_knobs,
        sign_device=cfg.sign_device,
        sign_batch_max=cfg.sign_batch_max,
        sign_batch_wait_ms=cfg.sign_batch_wait_ms,
        sign_self_check=cfg.sign_self_check,
        device_fail_threshold=cfg.device_fail_threshold,
        device_retries=cfg.device_retries,
        device_recovery_s=cfg.device_recovery_s,
        verify_deadline_ms=cfg.verify_deadline_ms,
        state_resident=cfg.state_resident,
        state_resident_mb=cfg.state_resident_mb,
        state_resident_range_bits=cfg.state_resident_range_bits,
        faults=cfg.faults,
        sidecar_endpoint=cfg.sidecar_endpoint,
        sidecar_weight=cfg.sidecar_weight,
        sidecar_recovery_s=cfg.sidecar_recovery_s,
        sidecar_listen=cfg.sidecar_listen,
        sidecar_queue_blocks=cfg.sidecar_queue_blocks,
        sidecar_coalesce=cfg.sidecar_coalesce,
        async_commit=cfg.async_commit,
        apply_queue_blocks=cfg.apply_queue_blocks,
        tx_flow=cfg.tx_flow,
    )


def _join_config_channel(node, cfg, ch):
    """Join one configured channel (genesis / snapshot anchored) and
    apply the per-channel ledger knobs."""
    from fabric_tpu.protos import common_pb2

    name = ch if isinstance(ch, str) else ch.name
    genesis = None
    if not isinstance(ch, str) and ch.genesis:
        genesis = common_pb2.Block()
        with open(ch.genesis, "rb") as f:
            genesis.ParseFromString(f.read())
    chan = node.join_channel(
        name, genesis_block=genesis,
        snapshot_dir=(None if isinstance(ch, str) or not ch.snapshot_dir
                      else ch.snapshot_dir),
    )
    chan.ledger.blocks.group_commit = cfg.group_commit
    chan.transient_retention = cfg.transient_retention
    return chan


def _claim_device(who: str) -> None:
    """A device-owning daemon says what it runs on before it serves,
    and refuses a CPU backend it did not ask for."""
    from fabric_tpu.utils import xla_env

    dev = xla_env.claim_device(who)
    print(f"{who} device: {dev['platform']} {dev['kind']} "
          f"x{dev['count']}", flush=True)


async def _run_peer(cfg):
    from fabric_tpu.discovery import PeerInfo
    from fabric_tpu.utils import xla_env

    # one device-owning process per chip (utils/xla_env.py): a peer
    # with its own device lane claims the chip; a peer attached to a
    # sidecar stays on the CPU backend so its MVCC jit and local
    # fallback never contend with the sidecar for it
    xla_env.enable_compile_cache()
    if cfg.sidecar_endpoint:
        xla_env.pin_cpu_backend()
    else:
        _claim_device(f"peer {cfg.id}")
    node = _build_peer(cfg)
    await node.start(operations_port=cfg.operations_port)
    print(f"peer {node.id} serving on :{node.port}", flush=True)
    for p in cfg.peers:
        node.registry.add(PeerInfo(p.msp_id, p.host, p.port))
    for ch in cfg.channels:
        name = ch if isinstance(ch, str) else ch.name
        chan = _join_config_channel(node, cfg, ch)
        if not isinstance(ch, str) and ch.replay_from:
            # local catch-up BEFORE the deliver loop attaches: replay
            # the staged block store at full pipeline depth
            # (peer/replay.py) — a killed start resumes from the
            # committed height on the next boot
            stats = await chan.replay_local(ch.replay_from)
            print(f"channel {name} replayed {stats['blocks']} blocks "
                  f"to height {chan.height} "
                  f"({stats['blocks_per_s']} blocks/s)", flush=True)
        orderers = ([] if isinstance(ch, str)
                    else [tuple(o) for o in ch.orderers])
        if orderers:
            chan.start_deliver(
                orderers,
                censorship_check_s=cfg.deliver_censorship_check_s,
            )
        if not isinstance(ch, str) and ch.anti_entropy:
            node.gossip_service.start_anti_entropy(name)
        node.gossip_service.start_reconciler(name)
    await asyncio.Event().wait()


def _cmd_node(args, runner):
    from fabric_tpu.nodeconfig import (
        ConfigError, load_orderer_config, load_peer_config,
    )

    loader = load_peer_config if runner is _run_peer else load_orderer_config
    try:
        cfg = loader(args.config)
    except ConfigError as e:
        print(f"config error: {e}", file=sys.stderr)
        sys.exit(2)
    try:
        asyncio.run(runner(cfg))
    except KeyboardInterrupt:
        pass


async def _run_sidecar(args):
    """Standalone validation sidecar: one device fabric serving many
    peer processes (fabric_tpu/sidecar — the PAPER.md north-star
    deployment shape).  Peers attach by setting ``sidecar_endpoint``
    in their node config."""
    from fabric_tpu.sidecar.server import SidecarServer
    from fabric_tpu.sidecar.client import parse_endpoint

    from fabric_tpu.utils import xla_env

    xla_env.enable_compile_cache()
    _claim_device("validation sidecar")

    if args.slos:
        from fabric_tpu.observe import slo as slo_mod

        slo_mod.configure(args.slos)
    if args.vitals_interval_s > 0 or args.blackbox_dir:
        # flight-data recorder on the sidecar process: trailing metric
        # series at /vitals plus black-box bundles on incident edges
        # (shed decisions, SLO fast burns) — default OFF
        from fabric_tpu.observe import timeseries as ts_mod

        ts_mod.configure(interval_s=args.vitals_interval_s,
                         retention=args.vitals_retention)
    if args.device_ledger:
        # device-time launch ledger on the sidecar process: every
        # coalesced cross-tenant dispatch reports compile/queue/
        # execute/transfer at /launches (default ON, like the peer)
        from fabric_tpu.observe import ledger as ledger_mod

        ledger_mod.configure()
    ssl_ctx = None
    if args.tls_cert and args.tls_key:
        from fabric_tpu.comm.rpc import make_server_tls

        with open(args.tls_cert, "rb") as f:
            cert = f.read()
        with open(args.tls_key, "rb") as f:
            key = f.read()
        ca = None
        if args.tls_ca:
            with open(args.tls_ca, "rb") as f:
                ca = f.read()
        ssl_ctx = make_server_tls(cert, key, ca)
    host, port = parse_endpoint(args.listen)
    from fabric_tpu.parallel.topology import MeshTopology

    topo = MeshTopology(
        devices=args.mesh_devices, shape=args.mesh_shape,
        distributed=args.mesh_distributed,
        coordinator=args.mesh_coordinator,
        process_id=args.mesh_process_id,
        num_processes=args.mesh_num_processes,
    )
    srv = SidecarServer(
        host, port, mesh_devices=args.mesh_devices,
        mesh_topology=topo if topo.configured else None,
        verify_chunk=args.verify_chunk,
        recode_device=args.recode_device,
        queue_blocks=args.queue_blocks, coalesce=args.coalesce,
        ssl_ctx=ssl_ctx,
    )
    await srv.start()
    print(f"validation sidecar serving on {srv.host}:{srv.port}",
          flush=True)
    if args.vitals_interval_s > 0 or args.blackbox_dir:
        from fabric_tpu.observe import blackbox as bb_mod

        # armed after start so bundles carry the live scheduler stats
        bb_mod.configure(out_dir=args.blackbox_dir,
                         scheduler=srv.scheduler)
    if args.autopilot:
        # SERVER-SIDE knob actuation: a sidecar-serve-local autopilot
        # reads its OWN scheduler's queue-age/BUSY telemetry and the
        # global SLO engine, and actuates the sidecar's own knobs —
        # cross-tenant coalescing and the device microbatch chunk via
        # the dispatcher-drain-boundary setters, plus tenant shed/
        # weights on the live scheduler.  (The peer-side controller
        # actuates pipeline knobs; this one owns the dispatch.)
        from fabric_tpu.control import Autopilot, set_global
        from fabric_tpu.observe.slo import global_engine

        def _apply(knob, value):
            if knob == "coalesce_blocks":
                srv.set_coalesce(int(value))
            elif knob == "verify_chunk":
                srv.set_verify_chunk(int(value))
            # pipeline_depth / host_stage_workers have no sidecar
            # meaning; their signals never fire here (no block roots)

        ap = Autopilot(
            args.autopilot_knobs or None, _apply,
            set_weight=srv.scheduler.set_weight,
            set_shed=srv.scheduler.set_shed,
            slo=global_engine(), scheduler=srv.scheduler,
            tick_s=args.autopilot_tick_s,
            initial={"coalesce_blocks": args.coalesce,
                     "verify_chunk": args.verify_chunk},
        )
        srv.autopilot = ap
        set_global(ap)
        ap.start()
        print("sidecar autopilot armed", flush=True)
    if args.operations_port is not None:
        from fabric_tpu.opsserver import HealthRegistry, OperationsServer

        health = HealthRegistry()
        health.register("sidecar", srv.health_check)
        ops = await OperationsServer(
            port=args.operations_port, health=health
        ).start()
        print(f"operations on :{ops.port}", flush=True)
    await asyncio.Event().wait()


async def _run_chaincode(args):
    from fabric_tpu.peer.ccaas import ChaincodeServer
    from fabric_tpu.peer.chaincode import KVContract, MarblesContract

    server = ChaincodeServer(port=args.port)
    await server.start()
    contract = {"kv": KVContract, "marbles": MarblesContract}[args.contract]()
    server.register(args.name, contract)
    print(f"chaincode {args.name} ({args.contract}) serving on :{server.port}",
          flush=True)
    await asyncio.Event().wait()


def _cli_ssl(args):
    """Client-side TLS context from the global --tls-* flags (mutual
    when a cert/key pair is given), or None for plaintext."""
    if not getattr(args, "tls_ca", None):
        return None
    from fabric_tpu.comm.rpc import make_client_tls

    with open(args.tls_ca, "rb") as f:
        ca = f.read()
    cert = key = None
    if getattr(args, "tls_cert", None) and getattr(args, "tls_key", None):
        with open(args.tls_cert, "rb") as f:
            cert = f.read()
        with open(args.tls_key, "rb") as f:
            key = f.read()
    return make_client_tls(ca, cert, key)


def _cmd_osnadmin(args):
    from fabric_tpu.comm.rpc import RpcClient
    from fabric_tpu.protos import common_pb2

    async def go():
        cli = RpcClient(args.host, args.port, ssl_ctx=_cli_ssl(args))
        await cli.connect()
        blk = b""
        if args.genesis:
            with open(args.genesis, "rb") as f:
                blk = f.read()
        hdr = json.dumps({"channel": args.channel}).encode()
        raw = await cli.unary(
            "Join", len(hdr).to_bytes(4, "big") + hdr + blk
        )
        await cli.close()
        print(raw.decode())

    asyncio.run(go())


def _cmd_invoke(args, evaluate=False):
    from fabric_tpu.crypto import cryptogen as cg
    from fabric_tpu.peer.gateway import GatewayClient

    signer = cg.load_signing_identity(args.msp_dir, args.msp_id)

    async def go():
        gw = GatewayClient(args.host, args.port, signer, ssl_ctx=_cli_ssl(args))
        try:
            cc_args = [a.encode() for a in args.args]
            if evaluate:
                resp = await gw.evaluate(args.channel, args.chaincode, cc_args)
                print(json.dumps({
                    "status": resp.status,
                    "payload": resp.payload.decode("utf-8", "replace"),
                }))
            else:
                tx_id, status = await gw.submit_transaction(
                    args.channel, args.chaincode, cc_args
                )
                print(json.dumps({"tx_id": tx_id, **(status or {})}))
        finally:
            await gw.close()

    asyncio.run(go())


def _cmd_ccpackage(args):
    from fabric_tpu.peer import ccpackage

    raw = ccpackage.package_ccaas(args.label, args.address)
    with open(args.output, "wb") as f:
        f.write(raw)
    print(json.dumps({
        "package_id": ccpackage.package_id(args.label, raw),
        "path": args.output,
    }))


def _cmd_ccinstall(args):
    from fabric_tpu.comm.rpc import RpcClient

    with open(args.package, "rb") as f:
        raw = f.read()
    if args.sign_msp_dir:
        # the admin-signed envelope install_require_admin peers demand
        if not args.sign_msp_id:
            print("ccinstall: --sign-msp-dir requires --sign-msp-id "
                  "(an identity without its MSP id can never validate)",
                  file=sys.stderr)
            sys.exit(2)
        from fabric_tpu.crypto.cryptogen import load_signing_identity

        signer = load_signing_identity(args.sign_msp_dir, args.sign_msp_id)
        raw = json.dumps({
            "package": raw.hex(),
            "identity": signer.serialized.hex(),
            "signature": signer.sign(raw).hex(),
        }).encode()

    async def go():
        cli = RpcClient(args.host, args.port, ssl_ctx=_cli_ssl(args))
        await cli.connect()
        res = await cli.unary("InstallChaincode", raw, timeout=60.0)
        await cli.close()
        print(res.decode())

    asyncio.run(go())


def _cmd_ccqueryinstalled(args):
    from fabric_tpu.comm.rpc import RpcClient

    async def go():
        cli = RpcClient(args.host, args.port, ssl_ctx=_cli_ssl(args))
        await cli.connect()
        res = await cli.unary("QueryInstalled", b"{}")
        await cli.close()
        print(res.decode())

    asyncio.run(go())


def _cmd_ledgerutil(args):
    from fabric_tpu.tools import ledgerutil as lu

    if args.action == "verify":
        res = lu.verify_ledger(args.dirs[0])
        print(json.dumps({"height": res.height, "ok": res.ok,
                          "errors": res.errors}))
        sys.exit(0 if res.ok else 1)
    res = lu.compare_ledgers(args.dirs[0], args.dirs[1])
    print(json.dumps(res))
    sys.exit(0 if res["identical"] else 1)


def _cmd_replay(args):
    """Offline catch-up (peer/replay.py): validate a staged block
    store into one configured channel's ledger at full pipeline depth,
    print the replay stats as JSON, and exit.  Composes with a
    ``snapshot_dir`` channel config: the snapshot bootstraps state at
    H, this replays H+1.. — and a killed run resumes from the
    committed height."""
    from fabric_tpu.nodeconfig import ConfigError, load_peer_config

    try:
        cfg = load_peer_config(args.config)
    except ConfigError as e:
        print(f"config error: {e}", file=sys.stderr)
        sys.exit(2)

    async def go():
        node = _build_peer(cfg)
        ref = None
        for ch in cfg.channels:
            if (ch if isinstance(ch, str) else ch.name) == args.channel:
                ref = ch
                break
        if ref is None:
            print(f"channel {args.channel} not in config",
                  file=sys.stderr)
            sys.exit(2)
        src = args.source or (
            "" if isinstance(ref, str) else ref.replay_from
        )
        if not src:
            print("no replay source: pass --source or set the "
                  "channel's replay_from", file=sys.stderr)
            sys.exit(2)
        chan = _join_config_channel(node, cfg, ref)
        try:
            stats = await chan.replay_local(src, depth=args.depth)
            stats["height"] = chan.height
            print(json.dumps(stats))
        finally:
            chan.stop()

    asyncio.run(go())


def _cmd_snapshot(args):
    from fabric_tpu.comm.rpc import RpcClient

    async def go():
        cli = RpcClient(args.host, args.port, ssl_ctx=_cli_ssl(args))
        await cli.connect()
        raw = await cli.unary("Snapshot", json.dumps(
            {"channel": args.channel, "out_dir": args.output}
        ).encode(), timeout=600.0)
        await cli.close()
        print(raw.decode())

    asyncio.run(go())


def _cmd_discover(args):
    from fabric_tpu.comm.rpc import RpcClient

    async def go():
        cli = RpcClient(args.host, args.port, ssl_ctx=_cli_ssl(args))
        await cli.connect()
        q = {"query": args.query, "channel": args.channel}
        if args.chaincode:
            q["chaincode"] = args.chaincode
        raw = await cli.unary("Discover", json.dumps(q).encode())
        await cli.close()
        print(raw.decode())

    asyncio.run(go())


def main(argv=None):
    p = argparse.ArgumentParser(prog="fabric-tpu")
    p.add_argument("--tls-ca", help="trusted TLS CA bundle (enables TLS)")
    p.add_argument("--tls-cert", help="client TLS certificate (mTLS)")
    p.add_argument("--tls-key", help="client TLS key (mTLS)")
    sub = p.add_subparsers(dest="cmd", required=True)

    c = sub.add_parser("cryptogen", help="generate org crypto material")
    c.add_argument("--org", action="append", required=True,
                   metavar="MSPID:domain")
    c.add_argument("--peers", type=int, default=1)
    c.add_argument("--orderers", type=int, default=0)
    c.add_argument("--users", type=int, default=1)
    c.add_argument("--output", default="crypto-config")

    c = sub.add_parser("configtxgen", help="genesis block from profile")
    c.add_argument("--profile", required=True)
    c.add_argument("--output", required=True)

    c = sub.add_parser("orderer", help="run an ordering node")
    c.add_argument("--config", required=True)

    c = sub.add_parser("peer", help="run a peer node")
    c.add_argument("--config", required=True)

    c = sub.add_parser("sidecar-serve",
                       help="run a standalone validation sidecar")
    c.add_argument("--listen", default="127.0.0.1:7054",
                   help="host:port to serve the validate stream on")
    c.add_argument("--mesh-devices", type=int, default=0,
                   help="device-mesh sharding (-1 = all local devices)")
    c.add_argument("--mesh-shape", default="",
                   help="device grid, 'N' or 'NxM' (data x replica); "
                        "overrides --mesh-devices")
    c.add_argument("--mesh-distributed", action="store_true",
                   help="span the mesh across jax.distributed "
                        "processes (requires --mesh-coordinator)")
    c.add_argument("--mesh-coordinator", default="",
                   help="host:port rendezvous for the distributed mesh")
    c.add_argument("--mesh-process-id", type=int, default=0,
                   help="this process's rank in the distributed mesh")
    c.add_argument("--mesh-num-processes", type=int, default=1,
                   help="total process count in the distributed mesh")
    c.add_argument("--verify-chunk", type=int, default=0)
    c.add_argument("--recode-device", action="store_true")
    c.add_argument("--queue-blocks", type=int, default=8,
                   help="per-tenant admission queue bound (BUSY past it)")
    c.add_argument("--coalesce", type=int, default=4,
                   help="max cross-tenant batches per device dispatch")
    c.add_argument("--operations-port", type=int, default=None)
    c.add_argument("--slos", default="",
                   help="SLO spec string (observe/slo.py), e.g. "
                        "'req:latency:ms=50;busy:busy:pct=5' — served "
                        "at /slo on the operations port")
    c.add_argument("--vitals-interval-s", type=float, default=0.0,
                   help="flight-data recorder sample interval "
                        "(seconds; 0 = recorder off)")
    c.add_argument("--vitals-retention", type=int, default=240,
                   help="points retained per metric series")
    c.add_argument("--blackbox-dir", default="",
                   help="directory for black-box incident bundles "
                        "('' = in-memory index only)")
    c.add_argument("--device-ledger", type=int, default=1,
                   help="per-launch device-time ledger (1 = on, the "
                        "default): compile/queue/execute/transfer "
                        "attribution at /launches on the operations "
                        "port")
    c.add_argument("--autopilot", action="store_true",
                   help="arm a sidecar-local traffic autopilot "
                        "actuating coalesce/verify_chunk (drain-"
                        "boundary setters) + tenant shed/weights off "
                        "this scheduler's own stats")
    c.add_argument("--autopilot-tick-s", type=float, default=1.0)
    c.add_argument("--autopilot-knobs", default="",
                   help="per-knob min/max clamp spec "
                        "(control/autopilot.py parse_knob_specs)")

    c = sub.add_parser("chaincode", help="run a sample ccaas chaincode server")
    c.add_argument("--name", required=True)
    c.add_argument("--port", type=int, default=0)
    c.add_argument("--contract", default="kv", choices=["kv", "marbles"])

    c = sub.add_parser("osnadmin", help="orderer channel participation")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, required=True)
    c.add_argument("--channel", required=True)
    c.add_argument("--genesis")

    for name in ("invoke", "query"):
        c = sub.add_parser(name, help=f"gateway {name}")
        c.add_argument("--host", default="127.0.0.1")
        c.add_argument("--port", type=int, required=True)
        c.add_argument("--channel", required=True)
        c.add_argument("--chaincode", required=True)
        c.add_argument("--msp-dir", required=True)
        c.add_argument("--msp-id", required=True)
        c.add_argument("args", nargs="+")

    c = sub.add_parser("ccpackage",
                       help="build a ccaas chaincode package")
    c.add_argument("--label", required=True)
    c.add_argument("--address", required=True,
                   help="ccaas endpoint host:port (connection.json)")
    c.add_argument("--output", required=True)

    c = sub.add_parser("ccinstall",
                       help="install a chaincode package on a peer")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, required=True)
    c.add_argument("--package", required=True)
    c.add_argument("--sign-msp-dir", default=None,
                   help="admin MSP dir: sign the install request "
                        "(required when the peer enforces "
                        "install_require_admin)")
    c.add_argument("--sign-msp-id", default=None,
                   help="MSP id of the signing admin identity")

    c = sub.add_parser("ccqueryinstalled",
                       help="list packages installed on a peer")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, required=True)

    c = sub.add_parser("ledgerutil", help="offline ledger forensics")
    c.add_argument("action", choices=["verify", "compare"])
    c.add_argument("dirs", nargs="+")

    c = sub.add_parser("snapshot", help="request a ledger snapshot")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, required=True)
    c.add_argument("--channel", required=True)
    c.add_argument("--output", required=True)

    c = sub.add_parser("replay",
                       help="offline catch-up: validate a staged "
                            "block store into a channel's ledger at "
                            "full pipeline depth")
    c.add_argument("--config", required=True,
                   help="peer config (the channel's genesis/snapshot "
                        "anchors and pipeline knobs come from here)")
    c.add_argument("--channel", required=True)
    c.add_argument("--source",
                   help="block-store directory to replay from "
                        "(default: the channel's replay_from)")
    c.add_argument("--depth", type=int, default=None,
                   help="pipeline depth override for the replay "
                        "(default: the config's pipeline_depth)")

    c = sub.add_parser("discover", help="discovery queries")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, required=True)
    c.add_argument("--channel", required=True)
    c.add_argument("--query", default="peers",
                   choices=["peers", "config", "endorsers"])
    c.add_argument("--chaincode")

    c = sub.add_parser("configtxlator",
                       help="config proto<->JSON + update deltas")
    c.add_argument("action",
                   choices=["proto_decode", "proto_encode", "compute_update"])
    c.add_argument("--type", help="message type, e.g. common.Config")
    c.add_argument("--input", help="input file (proto or JSON)")
    c.add_argument("--original", help="compute_update: original config pb")
    c.add_argument("--updated", help="compute_update: updated config pb")
    c.add_argument("--channel", help="compute_update: channel id")
    c.add_argument("--output", help="output file (default stdout)")

    c = sub.add_parser("node",
                       help="offline channel ops on a STOPPED peer")
    c.add_argument("action",
                   choices=["reset", "rollback", "unjoin", "rebuild-dbs"])
    c.add_argument("--channel-dir", required=True)
    c.add_argument("--block-number", type=int,
                   help="rollback: last block to keep")

    args = p.parse_args(argv)
    if args.cmd == "cryptogen":
        _cmd_cryptogen(args)
    elif args.cmd == "configtxgen":
        _cmd_configtxgen(args)
    elif args.cmd == "orderer":
        from fabric_tpu import cli as _self  # noqa: F401

        _cmd_node(args, _run_orderer)
    elif args.cmd == "peer":
        _cmd_node(args, _run_peer)
    elif args.cmd == "sidecar-serve":
        try:
            asyncio.run(_run_sidecar(args))
        except KeyboardInterrupt:
            pass
    elif args.cmd == "chaincode":
        try:
            asyncio.run(_run_chaincode(args))
        except KeyboardInterrupt:
            pass
    elif args.cmd == "osnadmin":
        _cmd_osnadmin(args)
    elif args.cmd == "invoke":
        _cmd_invoke(args)
    elif args.cmd == "query":
        _cmd_invoke(args, evaluate=True)
    elif args.cmd == "ccpackage":
        _cmd_ccpackage(args)
    elif args.cmd == "ccinstall":
        _cmd_ccinstall(args)
    elif args.cmd == "ccqueryinstalled":
        _cmd_ccqueryinstalled(args)
    elif args.cmd == "ledgerutil":
        _cmd_ledgerutil(args)
    elif args.cmd == "snapshot":
        _cmd_snapshot(args)
    elif args.cmd == "replay":
        _cmd_replay(args)
    elif args.cmd == "discover":
        _cmd_discover(args)
    elif args.cmd == "configtxlator":
        _cmd_configtxlator(args)
    elif args.cmd == "node":
        _cmd_nodeops(args)


def _cmd_configtxlator(args):
    from fabric_tpu.tools import configtxlator as ctl

    def out(data: bytes):
        if args.output:
            with open(args.output, "wb") as f:
                f.write(data)
        else:
            sys.stdout.buffer.write(data)
            if not data.endswith(b"\n"):
                sys.stdout.buffer.write(b"\n")

    if args.action == "proto_decode":
        with open(args.input, "rb") as f:
            out(ctl.proto_decode(args.type, f.read()).encode())
    elif args.action == "proto_encode":
        with open(args.input, "rb") as f:
            out(ctl.proto_encode(args.type, f.read().decode()))
    else:  # compute_update
        with open(args.original, "rb") as f:
            original = f.read()
        with open(args.updated, "rb") as f:
            updated = f.read()
        out(ctl.compute_update(args.channel, original, updated))


def _cmd_nodeops(args):
    from fabric_tpu.tools import nodeops

    if args.action == "reset":
        res = nodeops.reset(args.channel_dir)
    elif args.action == "rebuild-dbs":
        res = nodeops.rebuild_dbs(args.channel_dir)
    elif args.action == "unjoin":
        res = nodeops.unjoin(args.channel_dir)
    else:  # rollback
        if args.block_number is None:
            print("rollback requires --block-number", file=sys.stderr)
            sys.exit(2)
        res = nodeops.rollback(args.channel_dir, args.block_number)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
