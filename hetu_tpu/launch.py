"""Cluster configuration + multi-host launcher (the ``heturun`` capability).

Reference: ``bin/heturun`` → python/runner.py:150 parses a cluster yaml
(DistConfig, python/hetu/context.py:2204), spawns PS roles locally/via SSH and
workers under mpirun.  TPU-native: there is no PS process tree or mpirun —
each host runs ONE process per chip-set, `jax.distributed.initialize` forms
the world over the coordinator, and XLA's collectives ride ICI/DCN.  The
launcher therefore reduces to: parse the cluster spec, compose per-process
environments, exec the training script on every host (ssh for remote ones),
and wire coordinator discovery.

CPU simulation: ``simulate_workers`` launches N local processes with a
virtual device count so multi-process logic is testable on one machine
(the reference gets the same effect by mpirun on localhost).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shlex
import subprocess
import sys
from typing import Optional, Sequence

from hetu_tpu.obs import registry as _obs
from hetu_tpu.obs.fleet import ENV_OBS_SNAPSHOT

__all__ = ["DistConfig", "HostSpec", "initialize", "launch", "simulate_workers",
           "worker_env", "embed_server_addresses", "main",
           "MultiWorkerTPUError"]

ENV_COORD = "HETU_TPU_COORD"
ENV_NPROC = "HETU_TPU_NPROC"
ENV_PROC_ID = "HETU_TPU_PROC_ID"
ENV_EMBED_SERVERS = "HETU_TPU_EMBED_SERVERS"
ENV_GANG_DIR = "HETU_TPU_GANG_DIR"
ENV_PARTIAL_DEADLINE = "HETU_TPU_PARTIAL_DEADLINE"


@dataclasses.dataclass
class HostSpec:
    host: str
    workers: int = 1          # processes to start on this host
    chief: bool = False
    servers: int = 0          # embedding-server processes on this host


@dataclasses.dataclass
class DistConfig:
    """Cluster spec.  YAML schema (reference context.py:2204-2247 analogue)::

        nodes:
          - host: localhost     # or DNS/IP
            workers: 1          # processes on this host
            chief: true         # coordinator host (default: first)
            servers: 0          # embedding-server (PS) processes on host
        port: 23456             # coordinator port
        server_port: 9123       # first embedding-server port (consecutive)
    """

    hosts: list
    port: int = 23456
    server_port: int = 9123

    @classmethod
    def from_yaml(cls, path: str) -> "DistConfig":
        import yaml
        with open(path) as f:
            raw = yaml.safe_load(f) or {}
        if not isinstance(raw, dict):
            raise ValueError(f"cluster config {path} must be a yaml mapping")
        nodes = raw.get("nodes") or raw.get("hosts") or []
        if not nodes:
            raise ValueError(f"cluster config {path} lists no nodes")
        hosts = []
        for item in nodes:
            if isinstance(item, str):
                hosts.append(HostSpec(host=item))
            else:
                hosts.append(HostSpec(host=item.get("host", "localhost"),
                                      workers=int(item.get("workers", 1)),
                                      chief=bool(item.get("chief", False)),
                                      servers=int(item.get("servers", 0))))
        if hosts and not any(h.chief for h in hosts):
            hosts[0].chief = True
        return cls(hosts=hosts, port=int(raw.get("port", 23456)),
                   server_port=int(raw.get("server_port", 9123)))

    @property
    def chief(self) -> HostSpec:
        return next(h for h in self.hosts if h.chief)

    @property
    def num_processes(self) -> int:
        return sum(h.workers for h in self.hosts)

    @property
    def coordinator_address(self) -> str:
        return f"{self.chief.host}:{self.port}"

    def process_table(self) -> list:
        """[(host, local_rank, global_process_id)] in launch order."""
        table, pid = [], 0
        for h in self.hosts:
            for lr in range(h.workers):
                table.append((h.host, lr, pid))
                pid += 1
        return table

    def server_table(self) -> list:
        """[(host, port)] for every embedding-server role (consecutive
        ports per host starting at ``server_port``)."""
        table = []
        for h in self.hosts:
            for s in range(h.servers):
                table.append((h.host, self.server_port + s))
        return table

    @property
    def server_addresses(self) -> list:
        return [f"{host}:{port}" for host, port in self.server_table()]


def worker_env(cfg: DistConfig, process_id: int,
               base_env: Optional[dict] = None) -> dict:
    """Compose the environment for one worker process."""
    env = dict(base_env if base_env is not None else os.environ)
    env[ENV_COORD] = cfg.coordinator_address
    env[ENV_NPROC] = str(cfg.num_processes)
    env[ENV_PROC_ID] = str(process_id)
    if cfg.server_addresses:
        env[ENV_EMBED_SERVERS] = ",".join(cfg.server_addresses)
    return env


def embed_server_addresses() -> list:
    """Embedding-server addresses the launcher exported for this worker
    (for ``embed.net.RemoteHostEmbedding(servers=...)``)."""
    raw = os.environ.get(ENV_EMBED_SERVERS, "")
    return [a for a in raw.split(",") if a]


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the distributed world.  Arguments default from the environment
    set by the launcher; on TPU pods with no env set, jax's own automatic
    discovery applies (jax.distributed.initialize with no args)."""
    import jax
    coordinator_address = coordinator_address or os.environ.get(ENV_COORD)
    if num_processes is None and ENV_NPROC in os.environ:
        num_processes = int(os.environ[ENV_NPROC])
    if process_id is None and ENV_PROC_ID in os.environ:
        process_id = int(os.environ[ENV_PROC_ID])
    if coordinator_address is None:
        jax.distributed.initialize()  # TPU pod metadata discovery
    else:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)


class MultiWorkerTPUError(RuntimeError):
    """Several local workers were asked for on a host with TPU chips."""


def _local_tpu_chips() -> int:
    """TPU chips attached to this host, counted from the device nodes
    libtpu opens.  Reads /dev only: the launcher must stay off JAX, or it
    would hold the chip its children need."""
    return len(glob.glob("/dev/accel[0-9]*")
               + glob.glob("/dev/vfio/[0-9]*"))


def _is_local(host: str) -> bool:
    return host in ("localhost", "127.0.0.1", os.uname().nodename)


def _remote_cmd(host: str, env: dict, argv: Sequence[str],
                env_keys: Sequence[str]) -> list:
    """ssh command carrying the launcher env vars (runner.py:57-70 uses
    paramiko; plain ssh keeps the dependency surface zero)."""
    exports = " ".join(f"{k}={shlex.quote(env[k])}" for k in env_keys if k in env)
    remote = f"cd {shlex.quote(os.getcwd())} && {exports} {' '.join(map(shlex.quote, argv))}"
    return ["ssh", "-o", "StrictHostKeyChecking=no", host, remote]


def launch(cfg: DistConfig, argv: Sequence[str],
           extra_env: Optional[dict] = None, dry_run: bool = False):
    """Start every role in the cluster; local processes directly, remote
    ones over ssh.  Embedding-server (PS) roles start first so workers can
    connect immediately (runner.py spawns scheduler/servers before mpirun).
    Returns the list of (role_id, Popen|command); server roles are tagged
    ``"server:<addr>"``."""
    platforms = {**os.environ, **(extra_env or {})}.get("JAX_PLATFORMS")
    local_workers = sum(h.workers for h in cfg.hosts if _is_local(h.host))
    if not dry_run and local_workers > 1 and platforms != "cpu" \
            and (chips := _local_tpu_chips()):
        # a chip belongs to one process: every child would claim all of
        # this host's chips, and all but the first would fail or hang
        raise MultiWorkerTPUError(
            f"{local_workers} local workers on a host with "
            f"{chips} TPU chip(s): nothing here gives each "
            f"child its own chip. Run ONE process per host and let it "
            f"drive every chip (make_mesh over jax.devices()), or set "
            f"JAX_PLATFORMS=cpu for CPU-only workers.")
    procs = []
    carry = [ENV_COORD, ENV_NPROC, ENV_PROC_ID, ENV_EMBED_SERVERS,
             ENV_GANG_DIR, ENV_PARTIAL_DEADLINE, ENV_OBS_SNAPSHOT,
             "JAX_PLATFORMS", "XLA_FLAGS",
             "PYTHONPATH"] + sorted(extra_env or ())
    for host, port in cfg.server_table():
        srv_argv = [sys.executable, "-m", "hetu_tpu.embed.net",
                    "--port", str(port)]
        local = _is_local(host)
        cmd = srv_argv if local else _remote_cmd(host, dict(os.environ),
                                                 srv_argv, carry)
        tag = f"server:{host}:{port}"
        if dry_run:
            procs.append((tag, cmd))
        else:
            procs.append((tag, subprocess.Popen(cmd)))
    for host, _local_rank, pid in cfg.process_table():
        env = worker_env(cfg, pid)
        if extra_env:
            env.update(extra_env)
        local = _is_local(host)
        if local:
            cmd = list(argv)
        else:
            cmd = _remote_cmd(host, env, argv, carry)
        if dry_run:
            procs.append((pid, cmd))
        else:
            procs.append((pid, subprocess.Popen(
                cmd, env=env if local else os.environ.copy())))
    return procs


def simulate_workers(n: int, script: str, *, cpu_devices_per_proc: int = 1,
                     timeout: float = 120.0, port: int = 0, faults=None,
                     restart_once: bool = False, gang_dir: Optional[str] = None,
                     allow_failures: bool = False,
                     partial_deadline: Optional[float] = None,
                     obs_snapshot: Optional[float] = None) -> list:
    """Run ``script`` in ``n`` local CPU processes joined into one jax
    distributed world.  Returns each process's stdout.  The CPU analogue of
    the reference's mpirun-on-localhost test pattern (tests/test_comm.py).

    ``timeout`` is ONE shared deadline for the whole gang (it used to be
    applied per process sequentially, making the worst case ``n×timeout``).

    ``faults``: an ``exec.faults.FaultPlan`` whose ``worker_kill`` events
    are honored here — each event ``(worker_index, Fault("worker_kill",
    arg=delay_seconds, sig=...))`` signals that worker mid-run (SIGKILL by
    default), the chaos harness's process-crash injection.

    ``restart_once``: a worker that exits non-zero (including killed ones)
    is relaunched ONCE with the same command and environment — the
    preemption-restart shape; its returned output is both runs
    concatenated.  Only the restarted worker's deadline is re-armed; the
    rest of the gang keeps the original one.

    ``gang_dir``: exported to every worker as ``HETU_TPU_GANG_DIR`` so
    scripts can join the elastic-gang protocol
    (``exec.gang.GangMembership.from_env()`` + ``GangCheckpointer``).

    ``partial_deadline``: exported as ``HETU_TPU_PARTIAL_DEADLINE`` —
    the wall-clock arrival deadline (seconds) a worker script's
    ``exec.partial.PartialReduceConfig.from_env()`` picks up for
    straggler-tolerant partial gradient reduction over the shared
    ``gang_dir`` (``exec.partial.GradientBoard``).

    ``obs_snapshot``: exported as ``HETU_TPU_OBS_SNAPSHOT`` (requires
    ``gang_dir``) — the fleet-telemetry publish interval in seconds.
    Worker scripts that start a ``GangMembership`` then publish atomic
    per-rank telemetry snapshots into ``<gang_dir>/obs/`` on the
    heartbeat cadence, which ``obs.fleet.FleetAggregator`` (rank 0 or an
    external observer) merges and serves on ``/fleet/*``.

    ``allow_failures``: a worker that still exits non-zero (after any
    ``restart_once`` retry) is recorded — its output gains a trailing
    ``[worker i exited rc=N]`` line — instead of failing the gang; the
    elastic-membership shape, where survivors are expected to carry on
    past a dead peer.  ``worker_stall`` fault events SIGSTOP the target
    worker for the event's ``duration`` seconds then SIGCONT it (the
    straggler/GC-pause shape the heartbeat lease must ride out or
    evict).

    With telemetry enabled, a monitor thread publishes per-worker
    heartbeat ages (``hetu_worker_heartbeat_age_seconds{worker=...}`` —
    a heartbeat is "the process was observed alive", so a live worker's
    age hovers near the poll interval and a dead one's grows) and the
    straggler gauge ``hetu_worker_straggler_seconds`` — how far the
    still-running tail lags behind the first finisher (the quantity
    partial reduce exists to bound, SIGMOD'21).  The gauge keeps its
    last value after the gang drains, so post-run scrapes see the
    final spread."""
    import socket
    import threading
    import time
    if port == 0:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
    cfg = DistConfig(hosts=[HostSpec("127.0.0.1", workers=n, chief=True)],
                     port=port)

    def spawn(env):
        return subprocess.Popen([sys.executable, "-c", script], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    envs, procs = [], []
    for _host, _lr, pid in cfg.process_table():
        env = worker_env(cfg, pid)
        if gang_dir is not None:
            env[ENV_GANG_DIR] = gang_dir
        if partial_deadline is not None:
            env[ENV_PARTIAL_DEADLINE] = str(float(partial_deadline))
        if obs_snapshot is not None:
            if gang_dir is None:
                raise ValueError(
                    "obs_snapshot needs gang_dir: fleet-telemetry "
                    "snapshots are published into <gang_dir>/obs/")
            env[ENV_OBS_SNAPSHOT] = str(float(obs_snapshot))
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={cpu_devices_per_proc}").strip()
        envs.append(env)
        procs.append(spawn(env))
    def kill_worker(proc, sig):
        # bound to the ORIGINAL incarnation at arm time: a kill whose
        # delay outlives that run is a no-op (inherent to wall-clock
        # chaos) — it must not hit a restart_once replacement and burn
        # the gang's only retry
        if proc.poll() is None:
            proc.send_signal(sig)

    timers = []

    def stall_worker(proc, duration):
        # SIGSTOP/SIGCONT pair bound to the original incarnation, like
        # kill_worker: a stall must not freeze a restarted replacement
        import signal as _sig
        if proc.poll() is None:
            proc.send_signal(_sig.SIGSTOP)
            t2 = threading.Timer(
                duration, lambda: proc.poll() is None
                and proc.send_signal(_sig.SIGCONT))
            t2.daemon = True
            t2.start()
            timers.append(t2)

    if faults is not None:
        for widx, delay, sig in faults.worker_kills(len(procs)):
            t = threading.Timer(delay, kill_worker, (procs[widx], sig))
            t.daemon = True
            t.start()
            timers.append(t)
        for widx, delay, duration in faults.worker_stalls(len(procs)):
            t = threading.Timer(delay, stall_worker,
                                (procs[widx], duration))
            t.daemon = True
            t.start()
            timers.append(t)
    mon_stop = threading.Event()
    if _obs.enabled():
        reg = _obs.get_registry()
        hb_gauge = reg.gauge(
            "hetu_worker_heartbeat_age_seconds",
            "seconds since each simulated worker was last observed alive "
            "(live workers hover near the poll interval; a grown age is "
            "a dead or reaped worker)", ("worker",))
        strag_gauge = reg.gauge(
            "hetu_worker_straggler_seconds",
            "lag of the still-running tail behind the gang's first "
            "finisher (holds its last value once the gang drains)")
        last_alive = [time.monotonic()] * len(procs)

        def monitor():
            poll_s = 0.05
            while not mon_stop.wait(poll_s):
                now = time.monotonic()
                exited = []
                for w in range(len(procs)):
                    if procs[w].poll() is None:  # sees restart_once swaps
                        last_alive[w] = now
                    else:
                        exited.append(last_alive[w])
                    hb_gauge.labels(worker=str(w)).set(now - last_alive[w])
                if exited and len(exited) < len(procs):
                    strag_gauge.set(now - min(exited))

        threading.Thread(target=monitor, daemon=True,
                         name="hetu-worker-heartbeats").start()
    outs = [""] * len(procs)
    # one shared deadline; a restarted worker gets a fresh PERSONAL budget
    # (others keep the gang deadline — re-arming it for everyone would
    # quietly reintroduce the n×timeout worst case)
    deadlines = [time.monotonic() + timeout] * len(procs)
    restarted = set()
    try:
        i = 0
        while i < len(procs):
            p = procs[i]
            out, _ = p.communicate(
                timeout=max(deadlines[i] - time.monotonic(), 0.001))
            outs[i] += out
            if p.returncode != 0:
                if restart_once and i not in restarted:
                    restarted.add(i)
                    deadlines[i] = time.monotonic() + timeout
                    procs[i] = spawn(envs[i])
                    continue  # collect the restarted run's output
                if allow_failures:
                    # elastic gangs expect dead peers; record, don't raise
                    outs[i] += f"\n[worker {i} exited rc={p.returncode}]"
                    i += 1
                    continue
                raise RuntimeError(
                    f"worker {i} failed (rc={p.returncode}):\n{outs[i]}")
            i += 1
    finally:
        mon_stop.set()
        for t in timers:
            t.cancel()
        # a failed/timed-out peer leaves the others blocked in distributed
        # init — reap everything before surfacing the error
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return outs


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI: ``heturun -c cluster.yml [--dry-run] python train.py ...``."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="heturun", description="hetu-tpu multi-host launcher")
    parser.add_argument("-c", "--config", required=True, help="cluster yaml")
    parser.add_argument("--dry-run", action="store_true",
                        help="print the per-host commands instead of running")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    cfg = DistConfig.from_yaml(args.config)
    if not args.command:
        parser.error("no command given")
    procs = launch(cfg, args.command, dry_run=args.dry_run)
    if args.dry_run:
        for pid, cmd in procs:
            print(f"[{pid}] {shlex.join(cmd) if isinstance(cmd, list) else cmd}")
        return 0
    # wait on every worker (server roles run until the workers finish, then
    # are terminated — runner.py kills PS roles the same way), report the
    # first worker failure
    workers = [(pid, p) for pid, p in procs if not str(pid).startswith("server:")]
    servers = [(pid, p) for pid, p in procs if str(pid).startswith("server:")]
    rcs = [p.wait() for _pid, p in workers]
    for _tag, p in servers:
        p.terminate()
        p.wait()
    return next((r for r in rcs if r), 0)


if __name__ == "__main__":
    sys.exit(main())
