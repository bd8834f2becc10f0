"""Command-line interface (reference: cmd/tendermint/main.go:14-37 +
cmd/tendermint/commands/*).

Commands: init, node, replica, testnet, gen_validator, show_validator,
reset_all, reset_priv_validator, replay, replay_console, version.
`--home` picks the node root (config.toml + genesis + privval + data).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import time


def _load_config(home: str):
    from tendermint_tpu.config import ensure_root, load_config

    ensure_root(home)
    return load_config(home)


# -- commands -----------------------------------------------------------------


def cmd_init(args) -> int:
    """commands/init.go:19-43: privval + genesis + config.toml."""
    from tendermint_tpu.config import ensure_root
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidatorFS

    cfg = ensure_root(args.home)
    pv_file = cfg.base.priv_validator_file()
    if os.path.exists(pv_file):
        pv = PrivValidatorFS.load(pv_file)
        print(f"Found private validator: {pv_file}")
    else:
        pv = PrivValidatorFS.generate(pv_file)
        pv.save()
        print(f"Generated private validator: {pv_file}")
    gen_file = cfg.base.genesis_file()
    if os.path.exists(gen_file):
        print(f"Found genesis file: {gen_file}")
    else:
        doc = GenesisDoc(
            genesis_time_ns=time.time_ns(),
            chain_id=args.chain_id or f"test-chain-{os.urandom(3).hex()}",
            validators=[GenesisValidator(pv.get_pub_key(), 10, "")],
        )
        doc.save_as(gen_file)
        print(f"Generated genesis file: {gen_file}")
    return 0


def cmd_node(args) -> int:
    """commands/run_node.go."""
    import logging

    logging.basicConfig(
        level=getattr(logging, (args.log_level or "info").upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    cfg = _load_config(args.home)
    for attr in ("proxy_app", "moniker", "fast_sync"):
        v = getattr(args, attr, None)
        if v is not None:
            setattr(cfg.base, attr, v)
    if args.db_backend:
        cfg.base.db_backend = args.db_backend
    if args.p2p_laddr:
        cfg.p2p.laddr = args.p2p_laddr
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.grpc_laddr:
        cfg.rpc.grpc_laddr = args.grpc_laddr
    if args.rpc_unsafe:
        cfg.rpc.unsafe = True
    if args.seeds:
        cfg.p2p.seeds = args.seeds
    if args.pex:
        cfg.p2p.pex_reactor = True
    if args.addr_book_strict is not None:
        cfg.p2p.addr_book_strict = args.addr_book_strict == "true"
    for attr in ("test_link_region", "test_link_rtt_ms"):
        v = getattr(args, attr)
        if v is not None:
            setattr(cfg.p2p, attr, v)

    # TENDERMINT_RACECHECK=1 == running the reference under `go test -race`:
    # every lock the node builds joins a process-wide order graph, reported
    # at shutdown (libs/racecheck.py). Install BEFORE node construction so
    # the reactors' locks are in scope.
    race_mon = None
    if os.environ.get("TENDERMINT_RACECHECK", "") == "1":
        from tendermint_tpu.libs import racecheck

        race_mon = racecheck.install()

    from tendermint_tpu.node import default_new_node

    node = default_new_node(cfg)
    node.start()
    print(f"Started node: moniker={cfg.base.moniker} rpc_port={node.rpc_port()}")

    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.5)
    finally:
        node.stop()
        if race_mon is not None:
            print(race_mon.report())
    return 0


def cmd_replica(args) -> int:
    """Run a verified read replica (round 24, docs/serving.md § Read
    replicas): follows --upstream with a light client and serves the
    read RPC surface from a proof-carrying cache."""
    import logging

    logging.basicConfig(
        level=getattr(logging, (args.log_level or "info").upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    cfg = _load_config(args.home)
    if args.upstream:
        cfg.replica.upstream = args.upstream
    if args.rpc_laddr:
        cfg.replica.laddr = args.rpc_laddr
    if args.max_lag_heights is not None:
        cfg.replica.max_lag_heights = args.max_lag_heights

    from tendermint_tpu.replica import ReplicaDaemon

    daemon = ReplicaDaemon(cfg)
    daemon.start()
    print(
        f"Started replica: upstream={cfg.replica.upstream} "
        f"rpc_port={daemon.rpc_port}"
    )

    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.5)
    finally:
        daemon.stop()
    return 0


def cmd_testnet(args) -> int:
    """commands/testnet.go:36-70: N validator dirs + shared genesis."""
    from tendermint_tpu.config import ensure_root
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidatorFS

    n = args.n
    gen_vals = []
    pvs = []
    for i in range(n):
        home = os.path.join(args.dir, f"mach{i}")
        cfg = ensure_root(home)
        pv = PrivValidatorFS.load_or_generate(cfg.base.priv_validator_file())
        pvs.append((home, pv, cfg))
        gen_vals.append(GenesisValidator(pv.get_pub_key(), 1, f"mach{i}"))
    doc = GenesisDoc(
        genesis_time_ns=time.time_ns(),
        chain_id=args.chain_id or "chain-" + os.urandom(3).hex(),
        validators=gen_vals,
    )
    for home, _pv, cfg in pvs:
        doc.save_as(cfg.base.genesis_file())
    print(f"Successfully initialized {n} node directories in {args.dir}")
    return 0


def cmd_gen_validator(args) -> int:
    from tendermint_tpu.types import PrivValidatorFS

    pv = PrivValidatorFS.generate(None)
    print(json.dumps(pv.to_json(), indent=2))
    return 0


def cmd_show_validator(args) -> int:
    from tendermint_tpu.config import ensure_root
    from tendermint_tpu.types import PrivValidatorFS

    cfg = ensure_root(args.home)
    pv = PrivValidatorFS.load_or_generate(cfg.base.priv_validator_file())
    print(json.dumps(pv.get_pub_key().to_json()))
    return 0


def cmd_reset_priv_validator(args) -> int:
    """commands/reset_priv_validator.go: DANGEROUS — signing state reset."""
    from tendermint_tpu.config import ensure_root
    from tendermint_tpu.types import PrivValidatorFS

    cfg = ensure_root(args.home)
    pv_file = cfg.base.priv_validator_file()
    if os.path.exists(pv_file):
        pv = PrivValidatorFS.load(pv_file)
        pv.reset()
        print(f"Reset private validator signing state: {pv_file}")
    else:
        PrivValidatorFS.generate(pv_file)
        print(f"Generated private validator: {pv_file}")
    return 0


def cmd_reset_all(args) -> int:
    """commands/reset_priv_validator.go ResetAll: wipe data/ + signing state."""
    from tendermint_tpu.config import ensure_root

    cfg = ensure_root(args.home)
    data_dir = cfg.base.db_dir()
    if os.path.isdir(data_dir):
        shutil.rmtree(data_dir, ignore_errors=True)
        os.makedirs(data_dir, exist_ok=True)
        print(f"Removed all data: {data_dir}")
    return cmd_reset_priv_validator(args)


def cmd_replay(args, console: bool = False) -> int:
    """commands/replay.go -> consensus/replay_file.go."""
    from tendermint_tpu.consensus.replay_file import run_replay_file

    cfg = _load_config(args.home)
    run_replay_file(cfg, console=console)
    return 0


def cmd_version(args) -> int:
    from tendermint_tpu.version import VERSION

    print(VERSION)
    return 0


def cmd_probe_upnp(args) -> int:
    """Probe the local network for UPnP port-mapping support
    (cmd/tendermint/main.go:29, p2p/upnp/probe.go)."""
    import json as _json

    from tendermint_tpu.p2p import upnp

    try:
        caps = upnp.probe()
        print(_json.dumps({"port_mapping": caps.port_mapping, "hairpin": caps.hairpin}))
        return 0
    except Exception as exc:  # noqa: BLE001 — a probe never tracebacks
        print(_json.dumps({"error": str(exc)}))
        return 1


# -- parser -------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tendermint-tpu",
        description="TPU-native BFT state-machine replication node",
    )
    p.add_argument(
        "--home",
        default=os.environ.get("TMHOME", os.path.expanduser("~/.tendermint_tpu")),
        help="node root directory",
    )
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("init", help="initialize a node (privval + genesis)")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("node", help="run the node")
    sp.add_argument("--proxy_app", default=None, help="app address or name (kvstore, signedkv, counter, nilapp, tcp://...)")
    sp.add_argument("--moniker", default=None)
    sp.add_argument("--fast_sync", action="store_true", default=None)
    sp.add_argument("--p2p.laddr", dest="p2p_laddr", default=None)
    sp.add_argument("--rpc.laddr", dest="rpc_laddr", default=None)
    sp.add_argument("--rpc.grpc_laddr", dest="grpc_laddr", default=None)
    sp.add_argument(
        "--rpc.unsafe", dest="rpc_unsafe", action="store_true",
        help="enable unsafe RPC routes (profiler, dial_seeds, flush "
        "mempool — rpc/core/routes.go:37-46 equivalent)",
    )
    sp.add_argument("--seeds", default=None, help="comma-separated host:port")
    sp.add_argument("--pex", action="store_true")
    sp.add_argument(
        "--p2p.addr_book_strict",
        dest="addr_book_strict",
        default=None,
        choices=["true", "false"],
        help="only store globally-routable peer addresses (turn off for "
        "loopback testnets; p2p/addrbook.py routability)",
    )
    sp.add_argument(
        "--p2p.test_link_region", dest="test_link_region", default=None,
        help="test option: the region this node is in (p2p/delay_line.py)",
    )
    sp.add_argument(
        "--p2p.test_link_rtt_ms", dest="test_link_rtt_ms", default=None,
        help="test option: round-trip times between regions, "
        "'a:a=1,a:b=90,b:b=1'; every link of the node is delayed by half",
    )
    sp.add_argument("--log_level", default="info")
    sp.add_argument("--db_backend", default=None, help="sqlite | filedb | memdb")
    sp.set_defaults(fn=cmd_node)

    sp = sub.add_parser(
        "replica",
        help="run a verified read replica following an upstream node "
        "(docs/serving.md § Read replicas)",
    )
    sp.add_argument(
        "--upstream", default=None,
        help="upstream RPC address (host:port) — a node, or another replica",
    )
    sp.add_argument("--rpc.laddr", dest="rpc_laddr", default=None)
    sp.add_argument(
        "--max_lag_heights", type=int, default=None,
        help="bounded staleness: refuse latest-reads when the verified "
        "view lags upstream by more than this many heights",
    )
    sp.add_argument("--log_level", default="info")
    sp.set_defaults(fn=cmd_replica)

    sp = sub.add_parser("testnet", help="initialize files for an N-node testnet")
    sp.add_argument("--n", type=int, default=4)
    sp.add_argument("--dir", default="mytestnet")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_testnet)

    sub.add_parser("gen_validator", help="generate a new validator keypair").set_defaults(
        fn=cmd_gen_validator
    )
    sub.add_parser("show_validator", help="show this node's validator pubkey").set_defaults(
        fn=cmd_show_validator
    )
    sub.add_parser(
        "reset_priv_validator", help="reset the validator signing state (DANGEROUS)"
    ).set_defaults(fn=cmd_reset_priv_validator)
    sub.add_parser(
        "reset_all", help="wipe blockchain data and signing state (DANGEROUS)"
    ).set_defaults(fn=cmd_reset_all)
    sub.add_parser("replay", help="replay the consensus WAL against a fresh state").set_defaults(
        fn=lambda a: cmd_replay(a, console=False)
    )
    sub.add_parser("replay_console", help="interactive WAL replay").set_defaults(
        fn=lambda a: cmd_replay(a, console=True)
    )
    sub.add_parser("version", help="print the version").set_defaults(fn=cmd_version)
    sub.add_parser(
        "probe_upnp", help="probe the network for UPnP port-mapping support"
    ).set_defaults(fn=cmd_probe_upnp)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
