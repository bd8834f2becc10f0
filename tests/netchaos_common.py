"""Real-TCP chaos-net harness (round 12, docs/secure-p2p.md scenario
matrix): N full in-process Nodes — every subsystem wired exactly as
production (`node/node.py`: consensus, mempool, fast sync, statesync,
RPC, telemetry) — peered over REAL TCP listeners through per-link
`ops/netfaults.LinkProxy` relays, with the in-repo SecretConnection
(X25519 + ChaCha20-Poly1305) encrypting every byte. No loopback fabric
anywhere: what the scenario matrix breaks is an actual network.

Topology: nodes boot in index order; node i dials every earlier node j
through the fabric's directed link (i, j), as a PERSISTENT seed — so a
severed link keeps retrying through an outage and heals without test
intervention (switch reconnect cadence is env-tuned tight for tests).
Inbound/outbound dedup never races: only i dials j, never both.

Round 18 grows this into the ADVERSARIAL network tier
(docs/netchaos.md): ChaosNet gains WAN-profile / geo-cluster verbs
(seeded latency distributions over the same link proxies), a rolling
restart arm (stop -> retarget links -> statesync re-join), per-node
genesis commit_format overrides (mixed-version nets), and soak
instrumentation (RSS / disk / flight-recorder quietness); the
VoteInjector generalizes into a HostilePeer family — mempool flooder,
oversized-frame peer, slow-loris, eclipse identities, frame corruptor —
every one speaking the real encrypted protocol.

Shared by tests/test_netchaos.py (the scenario matrix) and
tests/test_gossip_dedup.py, which is why it lives in a _common module
like tests/consensus_common.py.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import socket
import time

from tendermint_tpu.config.config import test_config
from tendermint_tpu.config.toml import ensure_root
from tendermint_tpu.node.node import Node, default_new_node
from tendermint_tpu.ops.netfaults import NetFabric, geo_clusters
from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidatorFS

CHAIN_ID = "netchaos"

# tight reconnect cadence: a healed partition must re-peer in ~a second,
# not the production 3 s x 30 default (libs/envknob-parsed, so a typo'd
# override never kills a node)
os.environ.setdefault("TENDERMINT_P2P_RECONNECT_INTERVAL_S", "0.25")
os.environ.setdefault("TENDERMINT_P2P_RECONNECT_ATTEMPTS", "400")


def wait_until(cond, timeout=60.0, tick=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


class ChaosNet:
    """N-validator kvstore net over real TCP through fault proxies."""

    def __init__(self, n: int, root: str, app: str = "kvstore",
                 snapshot_interval: int = 0,
                 commit_format_of: dict[int, str] | None = None,
                 db_backend: str | None = None,
                 retain_blocks: int = 0,
                 prune_interval: int = 0,
                 snapshot_chunk_size: int | None = None,
                 snapshot_full_every: int | None = None,
                 snapshot_keep: int | None = None,
                 height_throttle_s: float | None = None,
                 gossip_dedup: bool | None = None):
        self.n = n
        self.root = root
        self.app = app
        self.snapshot_interval = snapshot_interval
        # bounded-retention lifecycle (round 19): arm [pruning] on every
        # node; db_backend="sqlite" puts the block store on real disk so
        # the retention soaks measure actual bytes (the test preset's
        # memdb keeps only WAL + snapshots on disk)
        self.db_backend = db_backend
        self.retain_blocks = retain_blocks
        self.prune_interval = prune_interval
        self.snapshot_chunk_size = snapshot_chunk_size
        self.snapshot_full_every = snapshot_full_every
        # snapshot LIFETIME engineering for the retention scenarios: at
        # the test preset's cadence a node commits 5-20 heights/s, so
        # the default keep_recent=2 rotates a snapshot out in a couple
        # of seconds — any restore loses the race and the pruner chases
        # past the height being fetched (real deployments snapshot
        # hourly; lifetime >> restore time). snapshot_keep widens the
        # window; height_throttle_s slows the commit cadence itself
        # (a real timeout_commit instead of the preset's skipped one).
        self.snapshot_keep = snapshot_keep
        self.height_throttle_s = height_throttle_s
        # round 20: None = config default (dedup on); False boots the
        # whole net with the pre-round-20 gossip, the A/B baseline the
        # duplicate-ratio assertions compare against
        self.gossip_dedup = gossip_dedup
        # mixed-version nets (round 18): per-node genesis commit_format
        # override — {idx: "aggregate"} boots node idx under the other
        # flag; NodeInfo.compatible_with refuses the peering loudly
        self.commit_format_of = commit_format_of or {}
        self.fabric = NetFabric(name=f"chaosnet-{os.path.basename(root)}")
        self.nodes: list[Node] = []
        self.pvs: list[PrivValidatorFS] = []
        os.makedirs(root, exist_ok=True)

        # one genesis, n validators (sorted by address like make_genesis)
        pvs = []
        for i in range(n):
            pv = PrivValidatorFS(
                gen_priv_key_ed25519(f"{CHAIN_ID}-val-{i}".encode()), None
            )
            pvs.append(pv)
        pvs.sort(key=lambda pv: pv.get_address())
        self.pvs = pvs
        self.genesis = GenesisDoc(
            genesis_time_ns=time.time_ns(),
            chain_id=CHAIN_ID,
            validators=[
                GenesisValidator(pv.get_pub_key(), 10, f"v{i}")
                for i, pv in enumerate(pvs)
            ],
        )

    # -- boot ---------------------------------------------------------------

    def _make_config(self, idx: int, statesync_from: list[int] | None = None,
                     statesync_enable: bool = True):
        cfg = test_config()
        home = os.path.join(self.root, f"node{idx}")
        ensure_root(home, cfg)
        cfg.base.proxy_app = self.app
        cfg.base.moniker = f"chaos-{idx}"
        cfg.base.chain_id = CHAIN_ID
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.statesync.snapshot_interval = self.snapshot_interval
        if self.db_backend is not None:
            cfg.base.db_backend = self.db_backend
        if self.retain_blocks:
            cfg.pruning.retain_blocks = self.retain_blocks
            cfg.pruning.interval_heights = max(self.prune_interval, 1)
        if self.snapshot_chunk_size is not None:
            cfg.statesync.chunk_size = self.snapshot_chunk_size
        if self.snapshot_full_every is not None:
            cfg.statesync.snapshot_full_every = self.snapshot_full_every
        if self.snapshot_keep is not None:
            cfg.statesync.snapshot_keep_recent = self.snapshot_keep
        if self.height_throttle_s is not None:
            # production semantics: wait timeout_commit after each
            # commit before the next height (the preset skips it)
            cfg.consensus.timeout_commit = self.height_throttle_s
            cfg.consensus.skip_timeout_commit = False
        if self.gossip_dedup is not None:
            cfg.consensus.gossip_dedup = self.gossip_dedup
        if statesync_from:
            # statesync_enable=False configures the light-client
            # endpoints WITHOUT arming a boot-time restore — the
            # below-horizon runtime fallback (round 19) is what arms it
            cfg.base.fast_sync = True
            cfg.statesync.enable = statesync_enable
            cfg.statesync.rpc_servers = ",".join(
                f"127.0.0.1:{self.nodes[j].rpc_port()}" for j in statesync_from
            )
        gen = self.genesis
        fmt = self.commit_format_of.get(idx)
        if fmt is not None:
            gen = dataclasses.replace(gen, commit_format=fmt)
        gen.save_as(cfg.base.genesis_file())
        return cfg

    def _listener_port(self, j: int) -> int:
        return self.nodes[j].listener.internal_address().port

    def _seed_links(self, i: int, targets: list[int]) -> str:
        seeds = []
        for j in targets:
            link = self.fabric.add_link(
                i, j, ("127.0.0.1", self._listener_port(j))
            )
            seeds.append(link.laddr)
        return ",".join(seeds)

    def start_node(self, idx: int, pv: PrivValidatorFS | None,
                   statesync_from: list[int] | None = None,
                   dial: list[int] | None = None,
                   statesync_enable: bool = True) -> Node:
        cfg = self._make_config(
            idx, statesync_from=statesync_from,
            statesync_enable=statesync_enable,
        )
        if pv is not None:
            pv.file_path = cfg.base.priv_validator_file()
            pv.save()
        node = default_new_node(cfg)
        node.start()
        # dial earlier nodes through per-link proxies AFTER start (the
        # listener port exists once started; seeds at config time would
        # race the boot order anyway)
        targets = dial if dial is not None else list(range(len(self.nodes)))
        if targets:
            node.sw.dial_seeds(self._seed_links(idx, targets).split(","))
        self.nodes.append(node)
        return node

    def start(self) -> "ChaosNet":
        for i in range(self.n):
            self.start_node(i, self.pvs[i])
        return self

    # -- chaos verbs --------------------------------------------------------

    def partition(self, group_a) -> None:
        self.fabric.partition_groups(set(group_a))

    def heal(self) -> None:
        self.fabric.heal_all()

    def delay_node(self, idx: int, one_way_s: float,
                   asymmetric: bool = True) -> None:
        """Slow every link touching `idx`: inbound-direction traffic
        toward the node delayed, return path fast (asymmetric=True) or
        both ways (False)."""
        for (i, j), link in self.fabric.links().items():
            if idx not in (i, j):
                continue
            toward_j = one_way_s if j == idx else (0 if asymmetric else one_way_s)
            toward_i = one_way_s if i == idx else (0 if asymmetric else one_way_s)
            link.set_delay(c2s_s=toward_j, s2c_s=toward_i)

    def clear_delays(self) -> None:
        for link in self.fabric.links().values():
            link.set_delay(0, 0)

    # -- WAN tier (round 18) -------------------------------------------------

    # the test preset's 10x-shortened consensus timeouts (100 ms propose)
    # can NEVER cover an intercontinental link (40-90 ms per relayed
    # chunk): proposals always miss the window and rounds churn forever
    # with 1 ms deltas. Real WAN operators provision timeouts for RTT
    # (the production schedule is 3 s propose); applying a heavy profile
    # therefore also raises the live nodes' timeout schedule to a
    # WAN-shaped floor, and clear_wan restores the test preset. The
    # schedule is read per round from the shared config object, so the
    # mutation takes effect at the next round.
    _WAN_TIMEOUT_FLOOR = {
        "timeout_propose": 1.0, "timeout_propose_delta": 0.25,
        "timeout_prevote": 0.4, "timeout_prevote_delta": 0.2,
        "timeout_precommit": 0.4, "timeout_precommit_delta": 0.2,
    }

    def _wan_timeouts(self, on: bool) -> None:
        for node in self.nodes:
            ccfg = node.config.consensus
            if on:
                if not hasattr(ccfg, "_pre_wan_timeouts"):
                    ccfg._pre_wan_timeouts = {
                        k: getattr(ccfg, k) for k in self._WAN_TIMEOUT_FLOOR
                    }
                for k, floor in self._WAN_TIMEOUT_FLOOR.items():
                    setattr(ccfg, k, max(getattr(ccfg, k), floor))
            else:
                pre = getattr(ccfg, "_pre_wan_timeouts", None)
                if pre is not None:
                    for k, v in pre.items():
                        setattr(ccfg, k, v)

    @staticmethod
    def _is_heavy(profile) -> bool:
        from tendermint_tpu.ops.netfaults import wan_profile

        return profile is not None and wan_profile(profile).name != "lan"

    def apply_wan(self, profile, seed: int = 0) -> None:
        """One named WAN profile (ops/netfaults.WAN_PROFILES) across
        every link; per-link latencies still differ (seeded sample).
        Heavy profiles also raise the consensus timeout schedule to the
        WAN floor (see _WAN_TIMEOUT_FLOOR)."""
        self.fabric.apply_wan(profile, seed=seed)
        self._wan_timeouts(self._is_heavy(profile))

    def apply_geo_clusters(self, clusters=None, k: int = 2,
                           intra="lan", inter="intercontinental",
                           seed: int = 0) -> list[list[int]]:
        """Geo-cluster topology declared as data: "k clusters x m
        nodes" — low intra-cluster latency, high inter-cluster. Returns
        the cluster lists actually applied."""
        if clusters is None:
            clusters = geo_clusters(self.n, k)
        self.fabric.apply_geo(clusters, intra=intra, inter=inter, seed=seed)
        self._wan_timeouts(self._is_heavy(inter) or self._is_heavy(intra))
        return clusters

    def clear_wan(self) -> None:
        self.fabric.clear_wan()
        self._wan_timeouts(False)

    # -- rolling restart (round 18) ------------------------------------------

    def restart_node(self, idx: int, statesync_from: list[int] | None = None,
                     wipe: bool = False) -> Node:
        """Stop node idx and boot it again — same home (a plain restart)
        or wiped + statesync (the rolling-upgrade cold-replace arm). The
        fabric's inbound links retarget to the fresh listener port so
        the other nodes' persistent reconnect loops re-peer on their
        own; the restarted node re-dials its earlier peers through the
        SAME links (WAN profiles / delays riding them stay armed)."""
        old = self.nodes[idx]
        try:
            old.stop()
        except Exception:  # noqa: BLE001 — teardown best effort
            pass
        for link in self.fabric.links_of(idx):
            link.drop_all()
        if wipe:
            shutil.rmtree(os.path.join(self.root, f"node{idx}"),
                          ignore_errors=True)
        cfg = self._make_config(idx, statesync_from=statesync_from)
        pv = self.pvs[idx] if idx < len(self.pvs) else None
        if pv is not None:
            pv.file_path = cfg.base.priv_validator_file()
            pv.save()
        node = default_new_node(cfg)
        node.start()
        self.nodes[idx] = node
        if any(
            (link.wan_profile_name() or "lan") != "lan"
            for link in self.fabric.links().values()
        ):
            # the replacement boots with the test preset's tight
            # timeouts; if the net is WAN-shaped it needs the floor too
            self._wan_timeouts(True)
        port = self._listener_port(idx)
        seeds = []
        for (i, j), link in self.fabric.links().items():
            if j == idx:
                link.retarget(("127.0.0.1", port))
            elif i == idx:
                seeds.append(link.laddr)
        if seeds:
            node.sw.dial_seeds(seeds)
        return node

    # -- soak instrumentation (round 18) -------------------------------------

    @staticmethod
    def rss_kb() -> int:
        """This process's resident set (VmRSS), in KiB."""
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        raise RuntimeError("no VmRSS in /proc/self/status")

    def disk_bytes(self) -> int:
        """Total bytes under every node home (WALs, stores, snapshots)."""
        total = 0
        for dirpath, _dirs, files in os.walk(self.root):
            for fn in files:
                try:
                    total += os.path.getsize(os.path.join(dirpath, fn))
                except OSError:
                    pass
        return total

    def flight_dump_counts(self) -> list[int]:
        """Per-node flight-recorder auto-dump episode counts — the
        healthy-soak quietness assert (round 17's recorder)."""
        return [n.flightrec.stats()["dumps"] for n in self.nodes]

    def churn_listener(self, idx: int, down_s: float = 0.5) -> None:
        """The peer-churn arm: kill node idx's listener, reset every
        connection it has (both directions via its links), then restart
        the listener on the SAME port and let persistent dials re-peer."""
        node = self.nodes[idx]
        port = node.listener.internal_address().port
        node.listener.stop()
        for link in self.fabric.links_of(idx):
            link.drop_all()
        for peer in node.sw.peers.list():
            node.sw.stop_peer_for_error(peer, "chaos: listener churn")
        time.sleep(down_s)
        from tendermint_tpu.p2p.listener import Listener

        # the dead listener's port re-binds (SO_REUSEADDR) so the
        # fabric's links keep pointing at it and healing is automatic —
        # but lingering accepted-socket teardown can hold the addr for a
        # beat, so retry the bind briefly
        lst = None
        for _ in range(100):
            try:
                lst = Listener(f"127.0.0.1:{port}")
                break
            except OSError:
                time.sleep(0.1)
        if lst is None:
            raise OSError(f"could not re-bind churned listener port {port}")
        node.listener = lst
        node.sw.start_listener(lst)

    # -- convergence assertions ---------------------------------------------

    def heights(self) -> list[int]:
        return [n.block_store.height() for n in self.nodes]

    def wait_height(self, h: int, timeout: float = 120.0,
                    nodes: list[int] | None = None) -> bool:
        idxs = nodes if nodes is not None else range(len(self.nodes))
        return wait_until(
            lambda: all(self.nodes[i].block_store.height() >= h for i in idxs),
            timeout=timeout,
            tick=0.1,
        )

    def fingerprints(self, upto: int, node_idx: int,
                     from_height: int = 1) -> list[tuple]:
        """(height, block hash, part-set root, app hash) per height —
        the byte-identity surface the soaks assert on. `from_height`
        starts above 1 on pruned/restored stores (round 19), where
        heights below base() are legitimately absent."""
        node = self.nodes[node_idx]
        out = []
        for h in range(from_height, upto + 1):
            meta = node.block_store.load_block_meta(h)
            block = node.block_store.load_block(h)
            out.append(
                (
                    h,
                    meta.block_id.hash.hex(),
                    meta.block_id.parts_header.hash.hex(),
                    block.header.app_hash.hex(),
                    block.header.evidence_hash.hex(),
                )
            )
        return out

    def assert_converged(self, upto: int, nodes: list[int] | None = None,
                         from_height: int | None = None) -> None:
        """Byte-identity across `nodes` for heights [from_height, upto].
        from_height=None compares from the HIGHEST base among the nodes
        (round 19: pruned/restored stores legitimately hold different
        prefixes; what they share must still be byte-identical)."""
        idxs = list(nodes if nodes is not None else range(len(self.nodes)))
        if from_height is None:
            from_height = max(
                max(self.nodes[i].block_store.base(), 1) for i in idxs
            )
        want = self.fingerprints(upto, idxs[0], from_height=from_height)
        for i in idxs[1:]:
            got = self.fingerprints(upto, i, from_height=from_height)
            assert got == want, (
                f"node {i} diverges from node {idxs[0]} in heights "
                f"{from_height}..{upto}:\n{set(want) ^ set(got)}"
            )

    def broadcast_tx(self, tx: bytes, via: int = 0) -> None:
        self.nodes[via].mempool.check_tx(tx)

    def stop(self) -> None:
        for node in self.nodes:
            try:
                node.stop()
            except Exception:  # noqa: BLE001 — teardown best effort
                pass
        self.fabric.stop()
        shutil.rmtree(self.root, ignore_errors=True)


# -- the hostile-but-fluent peer family (round 18 adversary catalog) ----------


class HostilePeer:
    """Protocol-fluent adversary base: dials a node over the REAL
    encrypted transport (TCP -> SecretConnection -> NodeInfo handshake
    -> MConnection) and is admitted as an ordinary peer; it never runs
    a consensus state of its own. Subclasses are the adversary catalog
    (docs/netchaos.md): vote injection, mempool flooding, oversized
    frames, eclipse identities, frame corruption.

    `corrupt_prob` wires the p2p/fuzz.py FuzzedStream UNDER the
    SecretConnection — the frame-corruption peer: a seeded fraction of
    this adversary's encrypted frames arrive tampered, which the
    target's AEAD must flag loudly (auth failure + peer dropped)."""

    moniker = "hostile"

    def __init__(self, target_host: str, target_port: int, chain_id: str,
                 corrupt_prob: float = 0.0, corrupt_seed: int = 7,
                 commit_format: str = "full", key=None):
        from tendermint_tpu.blockchain.reactor import BLOCKCHAIN_CHANNEL
        from tendermint_tpu.consensus.reactor import (
            DATA_CHANNEL,
            STATE_CHANNEL,
            VOTE_CHANNEL,
            VOTE_SET_BITS_CHANNEL,
        )
        from tendermint_tpu.mempool.reactor import MEMPOOL_CHANNEL
        from tendermint_tpu.p2p.conn import ChannelDescriptor, MConnection
        from tendermint_tpu.p2p.fuzz import FuzzedStream
        from tendermint_tpu.p2p.node_info import NodeInfo, default_version
        from tendermint_tpu.p2p.peer import exchange_node_info
        from tendermint_tpu.p2p.secret_connection import SecretConnection
        from tendermint_tpu.p2p.stream import SocketStream
        from tendermint_tpu.statesync.reactor import STATESYNC_CHANNEL
        from tendermint_tpu.version import VERSION

        self.vote_channel = VOTE_CHANNEL
        self.mempool_channel = MEMPOOL_CHANNEL
        # every channel the node's reactors gossip on: an unknown inbound
        # channel is a fatal mconn error, and the consensus/mempool
        # reactors start pushing to a fresh peer immediately
        channels = (
            STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL, VOTE_SET_BITS_CHANNEL,
            MEMPOOL_CHANNEL, BLOCKCHAIN_CHANNEL, STATESYNC_CHANNEL,
        )
        sock = socket.create_connection((target_host, target_port), timeout=10)
        self._key = key if key is not None else gen_priv_key_ed25519()
        stream = SocketStream(sock)
        self.fuzz = None
        if corrupt_prob > 0:
            # handshake CLEAN (a corrupted key exchange would just fail
            # admission), then arm corruption once the mconn runs — the
            # adversary is a fluent peer whose frames tamper in flight
            stream = FuzzedStream(stream, prob_corrupt=0.0,
                                  seed=corrupt_seed)
            self.fuzz = stream
        self.conn = SecretConnection(stream, self._key)
        info = NodeInfo(
            pub_key=self._key.pub_key(),
            moniker=self.moniker,
            network=chain_id,
            version=default_version(VERSION),
            other=[f"commit_format={commit_format}"],
        )
        info.channels = bytes(channels)
        self.remote_info = exchange_node_info(self.conn, info, timeout=10)
        self._err: list = []
        self.mconn = MConnection(
            self.conn,
            [ChannelDescriptor(id=c, priority=5) for c in channels],
            # round 19: subclasses that TALK BACK (the adversarial
            # statesync offerers) override _on_receive; the base peer
            # stays deaf like before
            on_receive=self._on_receive,
            on_error=self._err.append,
        )
        self.mconn.start()
        if self.fuzz is not None:
            self.fuzz.prob_corrupt = corrupt_prob

    def _on_receive(self, ch_id: int, msg_bytes: bytes) -> None:
        """Inbound messages from the target; base adversaries ignore
        them (runs on the mconn recv thread — overrides must not block)."""

    def send_msg(self, ch_id: int, payload: bytes) -> bool:
        return self.mconn.send(ch_id, payload)

    def errors(self) -> list:
        return list(self._err)

    def dropped(self) -> bool:
        """Did the target (or the wire) kill this adversary's link?"""
        return bool(self._err) or not self.mconn.is_running()

    def close(self) -> None:
        try:
            self.mconn.stop()
        except Exception:  # noqa: BLE001 — teardown best effort
            pass
        try:
            self.conn.close()
        except Exception:  # noqa: BLE001
            pass


class VoteInjector(HostilePeer):
    """Pushes crafted consensus votes — the double-signer of the
    byzantine scenario."""

    moniker = "byz-injector"

    def send_vote(self, vote) -> bool:
        from tendermint_tpu.consensus import messages as msgs
        from tendermint_tpu.consensus.reactor import _enc

        return self.send_msg(self.vote_channel, _enc(msgs.VoteMessage(vote)))


class MempoolFlooder(HostilePeer):
    """Floods the target's mempool over the gossip channel: garbage
    signed-shaped txs (structurally parseable, signatures junk — shed
    at the batched sig gate without ever reaching the app) and
    valid-but-duplicate txs (shed at the dedup cache). The scenario
    asserts consensus liveness stays flat while the flood is shed and
    visible in p2p_adversary_flood_txs_rejected."""

    moniker = "mempool-flooder"

    @staticmethod
    def _encode_tx(tx: bytes) -> bytes:
        # the REAL gossip envelope: the flood must exercise the sig
        # gate, not the unknown-message reject path
        from tendermint_tpu.mempool.reactor import _encode_tx

        return _encode_tx(tx)

    def flood_garbage(self, n: int, payload_size: int = 24,
                      seed: int = 1) -> int:
        """n unique garbage txs shaped like signedkv envelopes
        (32B pubkey + 64B sig + payload) whose signatures are noise;
        returns how many were handed to the wire."""
        import random as _random

        rng = _random.Random(seed)
        sent = 0
        for i in range(n):
            tx = rng.randbytes(96) + b"flood-%d-" % i + rng.randbytes(
                payload_size
            )
            if self.send_msg(self.mempool_channel, self._encode_tx(tx)):
                sent += 1
        return sent

    def flood_duplicates(self, tx: bytes, n: int) -> int:
        """The same VALID tx n times — every copy past the first is
        dedup-cache shed on the target."""
        sent = 0
        for _ in range(n):
            if self.send_msg(self.mempool_channel, self._encode_tx(tx)):
                sent += 1
        return sent


class OversizedFramePeer(HostilePeer):
    """Streams one message past a channel's recv ceiling: the target
    must error the reassembly at the right-sized bound (round-18 caps)
    and drop this peer for cause."""

    moniker = "oversized-framer"

    def send_oversized(self, total_bytes: int = 200_000) -> bool:
        # the mconn send side chops any length; the TARGET's vote
        # channel caps reassembly at 64 KiB and must kill the link
        return self.send_msg(self.vote_channel, b"\x00" * total_bytes)


class HostileOfferer(HostilePeer):
    """Adversarial statesync offerer (round 19 adversary catalog):
    answers the target's snapshot discovery with an offer and then
    attacks the restore path per `mode`:

      "forged"  — serves a manifest whose header/app hashes contradict
                  the light-verified chain (internally consistent, so it
                  passes decode; the binding check proves the lie);
      "corrupt" — offers a REAL snapshot but serves chunks whose bytes
                  are flipped (the digest batch proves it);
      "stall"   — answers discovery and the manifest, serves
                  `stall_after` chunks, then goes silent mid-transfer.

    The target must ban each kind (statesync_offerer_bans_* counters)
    and complete its restore from the honest offerers. Construction:
    attack state is set BEFORE super().__init__ because the mconn recv
    thread (which drives _on_receive) starts inside it."""

    moniker = "hostile-offerer"

    def __init__(self, target_host: str, target_port: int, chain_id: str,
                 manifest_json: dict, chunks: list[bytes] | None = None,
                 mode: str = "forged", stall_after: int = 1, **kw):
        assert mode in ("forged", "corrupt", "stall")
        self.manifest_json = manifest_json
        self.chunks = list(chunks or [])
        self.mode = mode
        self.stall_after = stall_after
        self.chunks_answered = 0
        self.requests_seen: list[str] = []
        super().__init__(target_host, target_port, chain_id, **kw)

    def _send_statesync(self, obj: dict) -> None:
        import json as _json

        from tendermint_tpu.statesync.reactor import STATESYNC_CHANNEL

        self.send_msg(
            STATESYNC_CHANNEL, _json.dumps(obj, sort_keys=True).encode()
        )

    def _lite(self) -> dict:
        m = self.manifest_json
        lite = {
            "format": m["format"], "height": m["height"],
            "chain_id": m["chain_id"], "chunks": m["chunks"],
            "total_bytes": m["total_bytes"], "root": m["root"],
            "header_hash": m["header_hash"],
            "kind": m.get("kind", "full"),
        }
        if lite["kind"] == "delta":
            lite["base_height"] = m["base_height"]
        return lite

    def _on_receive(self, ch_id: int, msg_bytes: bytes) -> None:
        import json as _json

        from tendermint_tpu.statesync.reactor import STATESYNC_CHANNEL

        if ch_id != STATESYNC_CHANNEL:
            return
        try:
            msg = _json.loads(msg_bytes.decode())
            mtype = msg.get("type")
        except (ValueError, UnicodeDecodeError):
            return
        self.requests_seen.append(str(mtype))
        if mtype == "snapshots_request":
            self._send_statesync(
                {"type": "snapshots_response", "snapshots": [self._lite()]}
            )
        elif mtype == "manifest_request":
            if msg.get("height") == self.manifest_json["height"]:
                self._send_statesync(
                    {"type": "manifest_response",
                     "manifest": self.manifest_json}
                )
        elif mtype == "chunk_request":
            if msg.get("height") != self.manifest_json["height"]:
                return
            if self.mode == "stall" and self.chunks_answered >= self.stall_after:
                return  # mid-transfer silence — the attack
            i = msg.get("index", 0)
            if not isinstance(i, int) or not 0 <= i < len(self.chunks):
                return
            payload = self.chunks[i]
            if self.mode == "corrupt" and payload:
                payload = bytes([payload[0] ^ 0xFF]) + payload[1:]
            self.chunks_answered += 1
            self._send_statesync({
                "type": "chunk_response",
                "height": self.manifest_json["height"],
                "index": i,
                "chunk": payload.hex().upper(),
            })


def forged_manifest_json(honest_manifest, height: int, seed: int = 5) -> dict:
    """A DECODE-VALID manifest at `height` that contradicts the verified
    chain: chunk digests and root are internally consistent (one garbage
    chunk), but header/app hashes are noise — the light-client binding
    check is the only gate that can catch it, by proving the server
    lied. Returns (manifest_json); pair it with HostileOfferer(mode=
    "forged")."""
    import random as _random

    from tendermint_tpu.statesync.snapshot import Manifest, chunk_digest

    rng = _random.Random(seed)
    garbage = rng.randbytes(512)
    m = Manifest(
        height=height,
        chain_id=honest_manifest.chain_id,
        chunk_size=honest_manifest.chunk_size,
        total_bytes=len(garbage),
        chunk_digests=[chunk_digest(garbage)],
        header_hash=rng.randbytes(20),
        app_hash=rng.randbytes(20),
        seen_commit=honest_manifest.seen_commit,
    )
    return m.to_json()


def hostile_offerer_matrix(target_host: str, target_port: int,
                           chain_id: str, honest_manifest,
                           chunks: list[bytes],
                           stall_after: int = 0) -> dict[str, HostileOfferer]:
    """The full three-kind adversarial offerer burst against one
    target: a FORGED manifest one height above the honest snapshot
    (the picker takes max, so it is exercised first and its light walk
    succeeds while the binding check proves the lie), a CORRUPT-chunk
    offerer and a STALLING offerer both pinned at the honest height.
    Callers also arm the TENDERMINT_STATESYNC_{WINDOW,CHUNK_TIMEOUT_S,
    STALL_BAN,DISCOVERY_S} knobs for their timing budget, and must
    close() every offerer."""
    return {
        "forged": HostileOfferer(
            target_host, target_port, chain_id,
            forged_manifest_json(honest_manifest,
                                 honest_manifest.height + 1),
        ),
        "corrupt": HostileOfferer(
            target_host, target_port, chain_id, honest_manifest.to_json(),
            chunks=chunks, mode="corrupt",
        ),
        "stall": HostileOfferer(
            target_host, target_port, chain_id, honest_manifest.to_json(),
            chunks=chunks, mode="stall", stall_after=stall_after,
        ),
    }


def slow_loris_handshake(target_host: str, target_port: int,
                         byte_interval_s: float = 0.4,
                         max_s: float = 60.0) -> float | None:
    """The slow-loris adversary: connect and dribble one random byte at
    a time into the secret-connection handshake, never completing it.
    Returns seconds until the TARGET closed the socket (its handshake
    deadline firing), or None if it tolerated the loris for max_s —
    the failure the scenario asserts against."""
    import random as _random

    rng = _random.Random(11)  # deterministic dribble
    sock = socket.create_connection((target_host, target_port), timeout=10)
    sock.settimeout(byte_interval_s)
    t0 = time.monotonic()
    try:
        while time.monotonic() - t0 < max_s:
            try:
                sock.sendall(rng.randbytes(1))
            except OSError:
                return time.monotonic() - t0
            try:
                if sock.recv(4096) == b"":
                    return time.monotonic() - t0
            except socket.timeout:
                continue
            except OSError:
                return time.monotonic() - t0
        return None
    finally:
        try:
            sock.close()
        except OSError:
            pass


def eclipse_dials(target_host: str, target_port: int, chain_id: str,
                  n: int) -> tuple[list[HostilePeer], int]:
    """The eclipse adversary: n distinct identities (fresh Ed25519 keys)
    dialed from ONE address range (loopback — exactly the shape
    IPRangeCounter dampens). Returns (admitted peers, refused count);
    the caller closes the admitted ones."""
    admitted: list[HostilePeer] = []
    refused = 0
    for i in range(n):
        try:
            admitted.append(
                HostilePeer(target_host, target_port, chain_id,
                            key=gen_priv_key_ed25519(
                                f"{chain_id}-eclipse-{i}".encode()))
            )
        except Exception:  # noqa: BLE001 — refusal shapes vary (reset,
            # EOF mid-handshake, timeout): all count as the dial shed
            refused += 1
    return admitted, refused


def make_conflicting_votes(pv, validators, height: int, round_: int,
                           chain_id: str):
    """Two signed prevotes by `pv` for the same (height, round) naming
    different blocks — the raw material of DuplicateVoteEvidence (the
    signer bypasses the privval double-sign guard exactly like
    test_byzantine.ByzantinePrivValidator: a real byzantine key holder
    is not running our guard)."""
    from tendermint_tpu.types import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import VOTE_TYPE_PREVOTE, Vote

    idx, _ = validators.get_by_address(pv.get_address())
    votes = []
    for fill in (0xAA, 0xCC):
        vote = Vote(
            validator_address=pv.get_address(),
            validator_index=idx,
            height=height,
            round_=round_,
            type_=VOTE_TYPE_PREVOTE,
            block_id=BlockID(
                bytes([fill]) * 20, PartSetHeader(1, bytes([fill ^ 0xFF]) * 20)
            ),
        )
        votes.append(
            vote.with_signature(pv.priv_key.sign(vote.sign_bytes(chain_id)))
        )
    return votes
