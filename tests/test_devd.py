"""Device-daemon tests (tendermint_tpu/devd.py): protocol, verify parity,
async pipelining, and the gateway's automatic devd routing — all against
a real daemon subprocess serving the CPU backend, so the IPC path CI
exercises is byte-for-byte the one the TPU daemon serves in production.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from tendermint_tpu import devd
from tendermint_tpu.crypto import ed25519 as ed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def daemon(tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("devd") / "devd.sock")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TENDERMINT_DEVD_SOCK": sock,
        "TENDERMINT_DEVD_ACCEPT_CPU": "1",
        "TENDERMINT_DEVD_WARM": "16",
        "TENDERMINT_DEVD_EXIT_ON_TERM": "1",
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.devd"],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
    )
    client = devd.DevdClient(sock)
    deadline = time.time() + 240  # cold .jax_cache: one f32 ladder compile
    held = False
    while time.time() < deadline:
        if proc.poll() is not None:
            break
        try:
            rep = client.ping(timeout=2.0)
            if rep.get("held"):
                held = True
                break
        except Exception:
            pass
        time.sleep(1.0)
    if not held:
        err = b""
        if proc.poll() is not None:
            err = proc.stderr.read() if proc.stderr else b""
        proc.kill()
        pytest.fail(f"daemon never reached serving state: {err[-2000:]!r}")
    yield sock, client
    try:
        client.shutdown()
    except Exception:
        pass
    client.close()
    try:
        proc.wait(timeout=15)
    except subprocess.TimeoutExpired:
        proc.kill()


def _items(n: int, tag: bytes = b"devd"):
    seed = b"\x21" * 32
    pub = ed.public_key(seed)
    return [
        (pub, tag + b"-%d" % i, ed.sign(seed, tag + b"-%d" % i))
        for i in range(n)
    ]


def test_ping_reports_serving(daemon):
    _, client = daemon
    rep = client.ping()
    assert rep["held"] and rep["status"] == "serving"
    assert rep["platform"] == "cpu"
    assert rep["warmed"] == [16]
    assert rep["pid"] > 0


def test_verify_parity_with_cpu(daemon):
    _, client = daemon
    items = _items(6)
    items[2] = (items[2][0], items[2][1], b"\x13" * 64)  # forged
    items[4] = (items[4][0], items[4][1] + b"x", items[4][2])  # tampered msg
    got = client.verify_batch(items)
    want = [ed.verify(p, m, s) for p, m, s in items]
    assert got == want == [True, True, False, True, False, True]


def test_async_pipelining_preserves_order(daemon):
    _, client = daemon
    batches = [_items(5, tag=b"pipe%d" % k) for k in range(4)]
    for k in range(4):
        p, m, _ = batches[k][k]
        batches[k][k] = (p, m, b"\x31" * 64)
    resolvers = [client.verify_batch_async(b) for b in batches]
    for k, resolve in enumerate(resolvers):
        assert resolve() == [i != k for i in range(5)], k


def test_gateway_default_routes_through_daemon(daemon, monkeypatch):
    """With a daemon serving, a default-constructed Verifier picks the
    devd backend automatically: this process does no device (or kernel)
    work at all, and the daemon's counters move."""
    sock, client = daemon
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", sock)
    monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
    import tendermint_tpu.ops.devd_backend as backend
    from tendermint_tpu.ops import gateway

    monkeypatch.setattr(backend, "_client", None)
    devd.bust_avail_cache()  # bust the TTL cache for the new path
    assert gateway.kernel_name() == "devd"

    before = client.stats().get("tpu_sigs", 0) + client.stats().get("cpu_sigs", 0)
    v = gateway.Verifier(min_tpu_batch=1)
    items = _items(8, tag=b"gw")
    items[3] = (items[3][0], items[3][1], b"\x55" * 64)
    assert v.verify_batch(items) == [i != 3 for i in range(8)]
    assert v.stats()["tpu_sigs"] == 8  # routed, not CPU-fallback
    after = client.stats().get("tpu_sigs", 0) + client.stats().get("cpu_sigs", 0)
    assert after - before == 8


class _DeadClient:
    def verify_batch(self, items):
        raise ConnectionError("daemon transport died")

    def verify_batch_async(self, items):
        raise ConnectionError("daemon transport died")


def test_transport_failure_with_live_daemon_opens_breaker(daemon, monkeypatch):
    """Requests failing while the daemon still serves: after the breaker
    threshold (3 consecutive failures) the shared breaker OPENS and
    batches ride the CPU fallback — never an in-process dial at the chip
    the live daemon exclusively holds, and (round 8) never the old
    permanent CPU latch: once the transport heals, a half-open probe
    re-closes the breaker and devd routing resumes."""
    sock, _ = daemon
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", sock)
    monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
    monkeypatch.setenv("TENDERMINT_TPU_BREAKER_BACKOFF_S", "0.05")
    monkeypatch.setenv("TENDERMINT_TPU_BREAKER_BACKOFF_CAP_S", "0.2")
    devd.bust_avail_cache()
    import tendermint_tpu.ops.devd_backend as backend
    from tendermint_tpu.ops import gateway

    gateway.reset_devd_breaker()
    try:
        v = gateway.Verifier(min_tpu_batch=1)
        assert v._kernel == "devd"
        monkeypatch.setattr(backend, "_client", _DeadClient())
        items = _items(4, tag=b"demote")
        items[1] = (items[1][0], items[1][1], b"\x99" * 64)
        # correct results throughout (retries then the CPU fallback)
        assert v.verify_batch(items) == [True, False, True, True]
        assert v._kernel == "devd"  # never stole the daemon's device
        assert v._tpu_ok  # NOT latched: the breaker owns the fallback
        br = gateway.devd_breaker()
        assert br.state == br.OPEN
        resolve = v.verify_batch_async(items)
        assert resolve() == [True, False, True, True]

        # transport heals (the daemon was serving all along): the next
        # due probe re-closes the breaker and devd routing resumes
        backend._client = None  # next _get_client dials the real daemon
        deadline = time.time() + 5.0
        while br.state != br.CLOSED and time.time() < deadline:
            time.sleep(0.05)
            assert v.verify_batch(items) == [True, False, True, True]
        assert br.state == br.CLOSED
        before = v.stats()["tpu_sigs"]
        assert v.verify_batch(items) == [True, False, True, True]
        assert v.stats()["tpu_sigs"] == before + 4  # devd-routed again
    finally:
        gateway.reset_devd_breaker()


def test_daemon_death_opens_breaker_and_recovery_restores_devd(
        daemon, monkeypatch):
    """The daemon actually gone: the breaker opens (probes fail), every
    batch verifies correctly on the CPU fallback, and when the daemon
    returns a probe re-closes the breaker — devd routing restored with
    no process restart. (Round 8 replaces the old one-way devd ->
    direct-kernel demotion: re-dialing the chip in-process raced the
    daemon's own re-claim, the exact one-owner violation devd exists to
    prevent.)"""
    sock, _ = daemon
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", sock)
    monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
    monkeypatch.setenv("TENDERMINT_TPU_BREAKER_BACKOFF_S", "0.05")
    monkeypatch.setenv("TENDERMINT_TPU_BREAKER_BACKOFF_CAP_S", "0.2")
    devd.bust_avail_cache()
    import tendermint_tpu.ops.devd_backend as backend
    from tendermint_tpu.ops import gateway

    gateway.reset_devd_breaker()
    real_available = devd.available
    try:
        v = gateway.Verifier(min_tpu_batch=1)
        assert v._kernel == "devd"
        # simulate death: transport raises AND the fresh re-ping (the
        # breaker's probe) finds nothing
        monkeypatch.setattr(backend, "_client", _DeadClient())
        monkeypatch.setattr(devd, "available", lambda *a, **k: None)
        items = _items(4, tag=b"demote2")
        items[2] = (items[2][0], items[2][1], b"\x77" * 64)
        assert v.verify_batch(items) == [True, True, False, True]
        assert v._kernel == "devd", v._kernel  # no direct-kernel steal
        assert v._tpu_ok
        br = gateway.devd_breaker()
        assert br.state == br.OPEN
        # while dead, probes keep failing and the fallback keeps serving
        time.sleep(0.1)
        assert v.verify_batch(items) == [True, True, False, True]
        assert br.state == br.OPEN
        assert br.stats()["breaker_probe_failures"] >= 1

        # daemon comes back: probe succeeds, breaker closes, devd routes
        monkeypatch.setattr(devd, "available", real_available)
        backend._client = None
        devd.bust_avail_cache()
        deadline = time.time() + 5.0
        while br.state != br.CLOSED and time.time() < deadline:
            time.sleep(0.05)
            assert v.verify_batch(items) == [True, True, False, True]
        assert br.state == br.CLOSED
        before = v.stats()["tpu_sigs"]
        assert v.verify_batch(items) == [True, True, False, True]
        assert v.stats()["tpu_sigs"] == before + 4
        st = v.stats()
        assert st["breaker_opens"] >= 1 and st["breaker_closes"] >= 1
        assert st["breaker_fallback_s"] > 0
    finally:
        gateway.reset_devd_breaker()


def test_fast_sync_rides_the_daemon(daemon, monkeypatch):
    """End to end, the production topology in miniature: a fast-syncing
    node's commit-signature batches — including concurrent speculative
    dispatches — route over IPC to the device daemon; the synced chain is
    byte-identical and the node process did no kernel work itself."""
    sock, client = daemon
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", sock)
    monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
    devd.bust_avail_cache()
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.consensus.reactor import ConsensusReactor
    from tendermint_tpu.ops import gateway
    from tendermint_tpu.p2p import Switch, connect2_switches
    from tendermint_tpu.p2p.node_info import NodeInfo, default_version
    from tests.test_reactors import (
        TEST_CHAIN_ID,
        make_genesis,
        make_node,
        stop_net,
        wait_until,
    )

    verifier = gateway.Verifier(min_tpu_batch=1)
    assert verifier._kernel == "devd"
    daemon_sigs_before = client.stats().get("tpu_sigs", 0) + client.stats().get(
        "cpu_sigs", 0
    )

    doc, pvs = make_genesis(1)
    node_a = make_node(doc, pvs[0])
    node_b = make_node(doc, None)

    def init(i, sw):
        node = (node_a, node_b)[i]
        fast_sync = i == 1
        con_r = ConsensusReactor(node.cs, fast_sync=fast_sync)
        con_r.set_event_switch(node.evsw)
        sw.add_reactor("CONSENSUS", con_r)
        sw.add_reactor("BLOCKCHAIN", BlockchainReactor(
            node.state.copy(),
            node.cs.proxy_app_conn,
            node.store,
            fast_sync=fast_sync,
            batch_verifier=verifier.commit_batch_verifier() if fast_sync else None,
            async_batch_verifier=verifier.verify_batch_async if fast_sync else None,
            status_update_interval=0.5,
        ))
        sw.set_node_info(NodeInfo(
            pub_key=sw.node_priv_key.pub_key(),
            moniker=f"devd-node{i}",
            network=TEST_CHAIN_ID,
            version=default_version("test"),
        ))
        return sw

    switches = [init(i, Switch()) for i in range(2)]
    for sw in switches:
        sw.start()
    try:
        assert wait_until(lambda: node_a.store.height() >= 3, timeout=120)
        node_a.cs.stop()
        target = node_a.store.height()
        connect2_switches(switches, 0, 1)
        assert wait_until(
            lambda: node_b.store.height() >= target, timeout=120
        ), f"B at {node_b.store.height()}, A at {target}"
        for h in range(1, target + 1):
            assert node_b.store.load_block(h).hash() == node_a.store.load_block(h).hash()
        # the signature work landed in the DAEMON, and the node-side
        # verifier recorded those batches as accelerated (devd)
        vstats = verifier.stats()
        assert vstats["tpu_sigs"] > 0 and vstats["tpu_batches"] > 0, vstats
        daemon_sigs_after = client.stats().get("tpu_sigs", 0) + client.stats().get(
            "cpu_sigs", 0
        )
        assert daemon_sigs_after - daemon_sigs_before >= vstats["tpu_sigs"]
    finally:
        stop_net([node_a, node_b], switches)


def test_second_daemon_refuses_live_socket(daemon):
    sock, _ = daemon
    env = {
        **os.environ,
        "TENDERMINT_DEVD_SOCK": sock,
        "TENDERMINT_DEVD_ACCEPT_CPU": "1",
        "TENDERMINT_DEVD_EXIT_ON_TERM": "1",
    }
    proc = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.devd"],
        env=env, cwd=REPO, capture_output=True, timeout=60,
    )
    assert proc.returncode != 0
    assert b"already serving" in proc.stderr


def test_available_requires_held_device(daemon, monkeypatch, tmp_path):
    sock, _ = daemon
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", sock)
    devd.bust_avail_cache()
    rep = devd.available()
    assert rep is not None and rep["held"]
    # no socket -> unavailable (and the gateway default falls back)
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", str(tmp_path / "absent.sock"))
    devd.bust_avail_cache()
    assert devd.available() is None


@pytest.mark.parametrize("final,want", [("serving", "tpu"), ("failed", None)])
def test_resolve_platform_waits_out_claiming_daemon(
        monkeypatch, tmp_path, final, want):
    """A devd socket whose daemon is mid-claim/warm means the chip is
    (about to be) owned: resolve_platform must WAIT for it to serve —
    never touch the device itself, never settle on the host path minutes
    before the daemon comes up. A daemon that reports `failed` (final:
    it is about to exit) ends the wait at once with no accelerator."""
    import pickle
    import socket as socketlib
    import struct
    import threading

    from tendermint_tpu.ops import gateway

    path = str(tmp_path / "fake-devd.sock")
    state = {"pings": 0}

    def handle(c):
        try:
            while True:
                (n,) = struct.unpack(">I", c.recv(4))
                pickle.loads(c.recv(n))
                state["pings"] += 1
                if state["pings"] < 3:
                    rep = {"ok": True, "held": False, "status": "warming",
                           "platform": None}
                elif final == "failed":
                    rep = {"ok": True, "held": False, "status": "failed",
                           "platform": None, "error": "ClaimError: boom"}
                else:
                    rep = {"ok": True, "held": True, "status": "serving",
                           "platform": "tpu"}
                payload = pickle.dumps(rep)
                c.sendall(struct.pack(">I", len(payload)) + payload)
        except Exception:  # noqa: BLE001 — client closed
            pass

    def serve():
        srv = socketlib.socket(socketlib.AF_UNIX, socketlib.SOCK_STREAM)
        srv.bind(path)
        srv.listen(8)
        while True:
            c, _ = srv.accept()
            threading.Thread(target=handle, args=(c,), daemon=True).start()

    threading.Thread(target=serve, daemon=True).start()
    time.sleep(0.2)
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", path)
    monkeypatch.setenv("TENDERMINT_DEVD_RESOLVE_WAIT_S", "30")
    monkeypatch.delenv("TENDERMINT_TPU_PLATFORM", raising=False)
    monkeypatch.setitem(gateway._platform_cache, "v", None)
    gateway._platform_cache.pop("v")
    devd.bust_avail_cache()
    t0 = time.time()
    assert gateway.resolve_platform() == want
    assert state["pings"] >= 3  # it actually polled through "warming"
    assert time.time() - t0 < 25  # and "failed" did not wait out the bound
    gateway._platform_cache.pop("v", None)
    # what a daemon said is remembered process-wide ("daemon_said"): left
    # in, every default Verifier of a LATER test file on this xdist worker
    # routes to a daemon that is gone (tests/test_types.py read
    # tpu_sigs 0 for 8 when the schedule put it behind this file)
    gateway._platform_cache.pop("daemon_said", None)
    devd.bust_avail_cache()


# -- the rules of the device plane (PR 22) ------------------------------------
#
# libtpu gives a chip to ONE process. Its owner — the daemon — never
# serves the host verifier under the device's name, a claim that fails is
# final, and everybody else is TOLD the platform: nobody dials to find out.


def test_ping_carries_device_kind_and_count(daemon):
    """`kind` and `count` of chip_smoke.py's last line come from the
    daemon: the one process that may ask JAX."""
    _, client = daemon
    rep = client.ping()
    assert rep["device_kind"] == "cpu"
    assert rep["device_count"] >= 1
    assert len(rep["device_ids"]) == rep["device_count"]
    assert rep["error"] is None
    claim = client.status()["claim"]
    assert claim["served"] == "f32"
    assert claim["cache_dir"]
    assert claim["kernels"]["f32"]["warm_s"].keys() == {"16"}


# A daemon whose f32 kernel raises — on marked batches ("marked": the
# warm-up passes, a later client batch hits it) or on every batch
# ("always": the warm-up itself hits it). The steering lives HERE, in the
# test's launcher; the program grows no option for it.
_BOOM_DAEMON = """
import sys
from tendermint_tpu.ops import ed25519_f32 as k
mode = sys.argv[1]
real = k.verify_batch_async
def boom(items):
    if mode == "always" or any(m.startswith(b"BOOM") for _, m, _ in items):
        raise RuntimeError("kernel refused (test)")
    return real(items)
k.verify_batch_async = boom
k.verify_batch = lambda items: boom(items)()
from tendermint_tpu import devd
devd.main()
"""


def _start_boom_daemon(tmp_path, mode: str):
    sock = str(tmp_path / "boom.sock")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TENDERMINT_DEVD_SOCK": sock,
        "TENDERMINT_DEVD_ACCEPT_CPU": "1",
        "TENDERMINT_DEVD_WARM": "16",
        "TENDERMINT_DEVD_EXIT_ON_TERM": "1",
        "PYTHONPATH": REPO,
    }
    proc = subprocess.Popen(
        [sys.executable, "-c", _BOOM_DAEMON, mode], env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
    )
    return sock, proc


def _wait_status(sock, proc, wanted: set, timeout: float = 240.0) -> dict:
    client = devd.DevdClient(sock)
    deadline = time.time() + timeout
    try:
        while time.time() < deadline:
            try:
                rep = client.ping(timeout=2.0)
                if rep.get("status") in wanted:
                    return rep
            except Exception:  # noqa: BLE001 — not listening yet
                pass
            if proc.poll() is not None:
                break
            time.sleep(0.2)
    finally:
        client.close()
    err = proc.stderr.read() if proc.poll() is not None else b""
    proc.kill()
    pytest.fail(f"daemon never reached {wanted}: {err[-2000:]!r}")


def test_kernel_failure_in_daemon_is_an_error_not_a_host_answer(
        tmp_path, monkeypatch):
    """The owner of the chip never answers an Ed25519 lane from the
    host: a kernel that raises inside the daemon is an error to the
    client (whose breaker then opens and whose OWN host verifier
    answers), the daemon's cpu_sigs stays 0, and the daemon keeps
    serving the batches its kernel accepts."""
    sock, proc = _start_boom_daemon(tmp_path, "marked")
    try:
        _wait_status(sock, proc, {"serving"})
        client = devd.DevdClient(sock)
        bad = _items(4, tag=b"BOOM")
        with pytest.raises(devd.DevdError, match="kernel refused"):
            client.verify_batch(bad)
        with pytest.raises(Exception, match="kernel refused"):
            client.verify_stream(bad, chunk=2)
        good = _items(4, tag=b"fine")
        assert client.verify_batch(good) == [True] * 4
        assert client.stats()["cpu_sigs"] == 0

        # through the gateway: the client's breaker does its job
        monkeypatch.setenv("TENDERMINT_DEVD_SOCK", sock)
        monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
        monkeypatch.setenv("TENDERMINT_TPU_BREAKER_BACKOFF_S", "30")
        devd.bust_avail_cache()
        import tendermint_tpu.ops.devd_backend as backend
        from tendermint_tpu.ops import gateway

        monkeypatch.setattr(backend, "_client", None)
        gateway.reset_devd_breaker()
        try:
            v = gateway.Verifier(min_tpu_batch=1)
            assert v._kernel == "devd"
            assert v.verify_batch(bad) == [True] * 4  # the CLIENT's host path
            br = gateway.devd_breaker()
            assert br.state == br.OPEN
            assert v.stats()["cpu_sigs"] == 4
        finally:
            gateway.reset_devd_breaker()
        stats = client.stats()
        assert stats["cpu_sigs"] == 0, stats
        client.shutdown()
        client.close()
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


def test_failed_claim_is_final_status_failed_exit_nonzero(tmp_path):
    """A warm-up that hits a refused kernel: status `failed` with the
    error text in ping, and the process exits non-zero — it does not
    retry for ever and does not serve the host under the device's name."""
    sock, proc = _start_boom_daemon(tmp_path, "always")
    try:
        rep = _wait_status(sock, proc, {"failed"})
        assert not rep["held"]
        assert "kernel refused" in rep["error"]
        assert proc.wait(timeout=30) != 0
        assert b"claim failed" in proc.stderr.read()
        assert not os.path.exists(sock)
    finally:
        if proc.poll() is None:
            proc.kill()


def test_production_daemon_without_accelerator_fails_its_claim(tmp_path):
    """No ACCEPT_CPU and no chip: initialising lands on the cpu backend,
    which a production daemon refuses to serve. Bounded, no retry loop."""
    sock = str(tmp_path / "prod.sock")
    env = {
        **{k: v for k, v in os.environ.items()
           if k != "TENDERMINT_DEVD_ACCEPT_CPU"},
        "JAX_PLATFORMS": "cpu",
        "TENDERMINT_DEVD_SOCK": sock,
        "TENDERMINT_DEVD_EXIT_ON_TERM": "1",
    }
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.devd"],
        env=env, cwd=REPO, capture_output=True, timeout=120,
    )
    assert proc.returncode != 0
    assert b"no accelerator" in proc.stderr
    assert time.time() - t0 < 60


@pytest.mark.parametrize("placed", [True, False])
def test_jitcache_enable_honours_an_outside_cache_dir(tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache
    directory in code (JAX reads the variable itself); without it the
    cache is at a fixed path under the checkout."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from tendermint_tpu.jitcache import enable\n"
         "before = jax.config.jax_compilation_cache_dir\n"
         "print(before); print(enable())"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    before, after = out.stdout.split()[-2:]
    if placed:
        assert before == after == str(tmp_path / "cc")
    else:
        assert before == "None"
        assert os.path.dirname(after) == os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("told,want", [
    ("nothing", None), ("daemon", "tpu"), ("disable", "cpu"),
    ("platform", "tpu"),
])
def test_non_daemon_resolves_platform_without_dialing(
        monkeypatch, tmp_path, told, want):
    """A process that is not the daemon is TOLD its platform (env, or
    the daemon's ping): it starts no child and never asks jax.devices()."""
    import jax

    from tendermint_tpu.ops import gateway

    def forbidden(*a, **k):
        raise AssertionError("platform resolution dialed the device")

    monkeypatch.setattr(subprocess, "Popen", forbidden)
    monkeypatch.setattr(jax, "devices", forbidden)
    monkeypatch.setattr(jax, "default_backend", forbidden)
    monkeypatch.delenv("TENDERMINT_TPU_PLATFORM", raising=False)
    monkeypatch.delenv("TENDERMINT_TPU_DISABLE", raising=False)
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", str(tmp_path / "absent.sock"))
    if told == "daemon":
        monkeypatch.setattr(
            devd, "available",
            lambda *a, **k: {"held": True, "platform": "tpu"},
        )
    elif told == "disable":
        monkeypatch.setenv("TENDERMINT_TPU_DISABLE", "1")
    elif told == "platform":
        monkeypatch.setenv("TENDERMINT_TPU_PLATFORM", "tpu")
    saved = dict(gateway._platform_cache)
    gateway._platform_cache.clear()
    devd.bust_avail_cache()
    try:
        assert gateway.resolve_platform() == want
        assert gateway.on_tpu() == (want == "tpu")
    finally:
        gateway._platform_cache.clear()
        gateway._platform_cache.update(saved)
        devd.bust_avail_cache()
