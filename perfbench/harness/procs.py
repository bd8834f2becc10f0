"""Processes of a run: the device daemon (through the benchmark's
launcher), CLI nodes, the load generator. The harness process itself
never imports JAX (`no_jax_here`), because libtpu gives the chip to one
process and that process is the daemon.

Launch code copied from `chip_smoke.py` (daemon_env, wait_held) and
`ops/localnet.py` (home generation, node command line), with two
changes the issue asks for: nodes dial each other directly over
loopback (no relay thread anywhere), and the consensus timeouts come
from the configuration file, not from `LocalnetSpec.consensus_timeouts()`.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
RUN_ROOT = os.path.join(ROOT, ".perfbench_run")

_children: list[subprocess.Popen] = []


class HarnessError(Exception):
    """The run cannot produce a result line (no chip, a process died,
    a bound passed). Exit code != 0, nothing printed on stdout."""


def no_jax_here() -> None:
    if "jax" in sys.modules:
        raise HarnessError("the harness process imported jax")


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def base_env() -> dict:
    """The environment every child starts from: the caller's, minus
    whatever would tell a process its platform or kernel from outside."""
    env = dict(os.environ)
    for k in ("TENDERMINT_DEVD_SIM_RATE", "TENDERMINT_TPU_DISABLE",
              "TENDERMINT_TPU_PLATFORM", "TENDERMINT_TPU_KERNEL",
              "TENDERMINT_DEVD_SOCKS", "TENDERMINT_DEVD_SOCK",
              "TENDERMINT_TPU_MIN_BATCH", "BENCH_RUN"):
        env.pop(k, None)
    env["PYTHONPATH"] = ROOT
    return env


def build_native() -> float:
    """`make -C native` when the library is missing or older than its
    sources: the nodes would otherwise each try to build it at once."""
    t0 = time.time()
    native = os.path.join(ROOT, "native")
    lib = os.path.join(native, "libtendermint_native.so")
    src = os.path.join(native, "src")
    try:
        lib_m = os.path.getmtime(lib)
        stale = any(os.path.getmtime(os.path.join(src, f)) > lib_m
                    for f in os.listdir(src))
    except OSError:
        stale = True
    if stale:
        r = subprocess.run(["make", "-C", native], capture_output=True,
                           text=True, timeout=300)
        if r.returncode != 0:
            raise HarnessError(f"make -C native failed: {r.stderr[-1500:]}")
    return time.time() - t0


def run_dir(name: str) -> str:
    """A fresh directory for this run's homes, logs and control files,
    at a fixed place inside the checkout."""
    import shutil

    d = os.path.join(RUN_ROOT, name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def sock_path(run: str) -> str:
    """Unix socket paths are capped near 107 bytes; a deep checkout falls
    back to the temporary directory the caller gave this process."""
    p = os.path.join(run, "devd.sock")
    if len(p) < 100:
        return p
    return os.path.join(tempfile.mkdtemp(prefix="perfbench-"), "devd.sock")


def free_ports(n: int) -> list[int]:
    """n free loopback ports (bound once, released; a tiny race that a
    sealed machine with one benchmark on it does not lose)."""
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


# -- the daemon ---------------------------------------------------------------


class Daemon:
    def __init__(self, run: str, daemon_cfg: dict, control: str = "",
                 accept_cpu: bool = False):
        self.run = run
        self.sock = sock_path(run)
        self.ctl = os.path.join(run, "ctl")
        self.log = os.path.join(run, "devd.log")
        self._req = 0
        env = base_env()
        env["TENDERMINT_DEVD_SOCK"] = self.sock
        env["TENDERMINT_DEVD_EXIT_ON_TERM"] = "1"
        # the compile cache: where the machine says, else a fixed path in
        # the checkout (the program's own default, stated here so that
        # nothing depends on it)
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(ROOT, ".jax_cache", "perfbench"))
        for k, v in daemon_cfg.get("env", {}).items():
            env[k] = str(v)
        if accept_cpu:
            env["TENDERMINT_DEVD_ACCEPT_CPU"] = "1"
            env["JAX_PLATFORMS"] = "cpu"
        else:
            env.pop("TENDERMINT_DEVD_ACCEPT_CPU", None)
            env.pop("JAX_PLATFORMS", None)
        cmd = [sys.executable, os.path.join(HERE, "devd_launcher.py"),
               "--root", ROOT, "--ctl", self.ctl]
        if control:
            cmd += ["--control", control]
        os.makedirs(self.ctl, exist_ok=True)
        self.proc = subprocess.Popen(
            cmd, env=env, cwd=ROOT, stdout=open(self.log, "ab"),
            stderr=subprocess.STDOUT, start_new_session=True)
        _children.append(self.proc)

    def client(self, **kw):
        from tendermint_tpu import devd

        return devd.DevdClient(self.sock, **kw)

    def status(self, timeout: float = 30.0) -> dict:
        c = self.client(connect_timeout=2.0, io_timeout=timeout)
        try:
            return c.status(timeout=timeout)
        finally:
            c.close()

    def wait_held(self, deadline: float) -> dict:
        while time.time() < deadline:
            rep = None
            if os.path.exists(self.sock):
                try:
                    rep = self.status()
                except Exception:  # noqa: BLE001 — not listening yet
                    rep = None
            if rep is not None and rep.get("status") == "failed":
                raise HarnessError("the daemon reports a failed claim: "
                                   f"{rep.get('error')}\n{tail(self.log)}")
            if self.proc.poll() is not None:
                raise HarnessError(
                    f"the daemon exited with code {self.proc.returncode} "
                    f"before it held a device\n{tail(self.log)}")
            if rep is not None and rep.get("held"):
                return rep
            time.sleep(0.25)
        raise HarnessError("the daemon did not hold a device within the "
                           f"bound\n{tail(self.log)}")

    def post(self, op: str, **kw) -> str:
        """Hand the launcher's control loop one request (a file); returns
        the name its answer will have."""
        self._req += 1
        name = f"{self._req:04d}.json"
        tmp = os.path.join(self.ctl, ".req-" + name)
        with open(tmp, "w") as f:
            json.dump({"op": op, **kw}, f)
        os.replace(tmp, os.path.join(self.ctl, "req-" + name))
        return name

    def wait_ack(self, name: str, op: str, timeout: float = 60.0) -> dict:
        ack = os.path.join(self.ctl, "ack-" + name)
        deadline = time.time() + timeout
        while time.time() < deadline:
            if os.path.exists(ack):
                with open(ack) as f:
                    out = json.load(f)
                if not out.get("ok"):
                    raise HarnessError(f"launcher {op}: {out.get('error')}")
                return out
            if self.proc.poll() is not None:
                raise HarnessError(f"the daemon died during {op}\n"
                                   + tail(self.log))
            time.sleep(0.01)
        raise HarnessError(f"launcher {op}: no answer in {timeout}s")

    def request(self, op: str, timeout: float = 60.0, **kw) -> dict:
        """One request to the launcher's control loop (a file each way)."""
        return self.wait_ack(self.post(op, **kw), op, timeout)

    def shutdown(self) -> int:
        try:
            c = self.client()
            c.shutdown()
            c.close()
        except Exception:  # noqa: BLE001 — stop_all() still reaps it
            pass
        try:
            return self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            return -1


# -- node homes ---------------------------------------------------------------


def write_home(home: str, genesis, priv_validator, cfg_sets: dict) -> None:
    """One node home as the CLI would load it: config.toml through the
    program's TOML round trip, genesis.json, priv_validator.json.
    cfg_sets = {"base": {...}, "consensus": {...}, "p2p": {...}, ...}."""
    from tendermint_tpu.config import load_config
    from tendermint_tpu.config.toml import config_to_toml, ensure_root

    ensure_root(home)
    cfg = load_config(home)
    for section, kv in cfg_sets.items():
        target = getattr(cfg, section)
        for k, v in kv.items():
            if not hasattr(target, k):
                raise HarnessError(f"config has no {section}.{k}")
            setattr(target, k, v)
    with open(os.path.join(home, "config.toml"), "w") as f:
        f.write(config_to_toml(cfg))
    priv_validator.file_path = cfg.base.priv_validator_file()
    priv_validator.save()
    genesis.save_as(cfg.base.genesis_file())


class Node:
    def __init__(self, home: str, index: int, p2p_port: int, rpc_port: int):
        self.home = home
        self.index = index
        self.p2p_port = p2p_port
        self.rpc_port = rpc_port
        self.proc: subprocess.Popen | None = None
        self.log = os.path.join(home, "node.log")

    @property
    def rpc_addr(self) -> tuple[str, int]:
        return ("127.0.0.1", self.rpc_port)

    def start(self, seeds: list[str], env_extra: dict,
              fast_sync: bool = False, log_level: str = "error") -> None:
        env = base_env()
        # the nodes are CLIENTS of the daemon: a node that loaded libtpu
        # would fight it for the chip
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("TENDERMINT_P2P_RECONNECT_INTERVAL_S", "0.5")
        env.update({k: str(v) for k, v in env_extra.items()})
        cmd = [sys.executable, "-m", "tendermint_tpu.cli",
               "--home", self.home, "node",
               "--p2p.laddr", f"tcp://127.0.0.1:{self.p2p_port}",
               "--rpc.laddr", f"tcp://127.0.0.1:{self.rpc_port}",
               "--p2p.addr_book_strict", "false",
               "--log_level", log_level]
        if fast_sync:
            cmd.append("--fast_sync")
        if seeds:
            cmd += ["--seeds", ",".join(seeds)]
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=open(self.log, "ab"),
            stderr=subprocess.STDOUT, start_new_session=True)
        _children.append(self.proc)

    def check_alive(self) -> None:
        if self.proc is not None and self.proc.poll() is not None:
            raise HarnessError(f"node{self.index} exited with code "
                               f"{self.proc.returncode}\n{tail(self.log)}")


def stop_all(grace: float = 10.0) -> None:
    """SIGTERM every child, wait, SIGKILL what is left: nothing
    outlives a run."""
    for proc in _children:
        if proc.poll() is None:
            try:
                proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
    deadline = time.time() + grace
    for proc in _children:
        try:
            proc.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                proc.kill()
            proc.wait()
    _children.clear()
