"""Launcher (reference: python/paddle/distributed/launch/main.py CLI +
controllers/collective.py:22-150 CollectiveController/Pod + watcher.py log
watcher + fleet/elastic/manager.py restart semantics).

One controller process per host (TPU model: the process drives all local
chips through PJRT; jax.distributed handles multi-host rendezvous). The
controller spawns the worker pod, a watcher thread tails worker logs for
fatal patterns and monitors liveness, and on worker failure the pod is torn
down and — when --max_restart allows — respawned with PADDLE_RESTART_COUNT
incremented (elastic level 1: in-place pod restart; the reference's etcd
scale-in/out is the same loop keyed on a store watch).

A chip belongs to one process at a time, so the supported TPU mode is ONE
worker per host driving all local chips, started by a launcher that never
initializes a JAX backend itself (it would hold the chips its worker
needs). Both are checked before anything is spawned: a launcher process
with a live backend refuses to spawn, and --nproc_per_node > 1 on a host
whose workers would use the TPU is an error — the workers inherit one
environment with no chip partition, so the first would take every chip and
the rest would hang. Several workers per host remain the CPU test mode
(JAX_PLATFORMS=cpu)."""

from __future__ import annotations

import argparse
import glob
import os
import re
import signal
import subprocess
import sys
import threading
import time

__all__ = ["launch", "Pod", "LogWatcher"]

_FATAL_PATTERNS = re.compile(
    r"(FatalError|Check failed|core dumped|Segmentation fault|NumericError)")


class LogWatcher(threading.Thread):
    """Tails worker log files, surfacing fatal patterns (reference:
    launch/controllers/watcher.py)."""

    def __init__(self, paths, on_fatal=None, interval=0.5):
        super().__init__(daemon=True)
        self.paths = list(paths)
        self.on_fatal = on_fatal
        self.interval = interval
        self.fatal_lines: list[str] = []
        # start at the current size: logs open in append mode, and a stale
        # fatal line from a previous launcher run must not kill a fresh pod
        self._offsets = {}
        for p in self.paths:
            try:
                self._offsets[p] = os.path.getsize(p)
            except OSError:
                self._offsets[p] = 0
        self._stop_evt = threading.Event()  # NB: Thread reserves _stop

    def stop(self):
        self._stop_evt.set()

    def scan_once(self):
        for p in self.paths:
            try:
                with open(p, "rb") as f:
                    f.seek(self._offsets[p])
                    chunk = f.read()
            except OSError:
                continue
            # only consume complete lines — a fatal pattern split across a
            # read boundary must still match on the next scan
            cut = chunk.rfind(b"\n")
            if cut < 0:
                continue
            self._offsets[p] += cut + 1
            for line in chunk[:cut].decode(errors="replace").splitlines():
                if _FATAL_PATTERNS.search(line):
                    self.fatal_lines.append(f"{p}: {line}")
                    if self.on_fatal is not None:
                        self.on_fatal(p, line)

    def run(self):
        while not self._stop_evt.is_set():
            self.scan_once()
            time.sleep(self.interval)
        self.scan_once()


def _workers_would_use_tpu(env) -> bool:
    """True when a worker started with `env` would initialize the TPU
    backend: JAX_PLATFORMS does not keep it off, and this host has TPU
    device nodes. Decided without touching JAX."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _check_one_process_per_chip(nproc_per_node, env):
    if nproc_per_node > 1 and _workers_would_use_tpu(env):
        raise RuntimeError(
            f"--nproc_per_node={nproc_per_node} on a TPU host: a chip "
            "belongs to one process, and the workers would inherit one "
            "environment with no chip partition — the first takes every "
            "chip and the others hang. Run one worker per host (it drives "
            "all local chips), or set JAX_PLATFORMS=cpu for a CPU pod.")
    # sys.modules, not an import: the launcher itself must not pull jax in
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and xb.backends_are_initialized():
        raise RuntimeError(
            "the launcher process has already initialized a JAX backend and "
            "would hold the chips its worker needs; start workers from a "
            "process that has not touched JAX")


class Pod:
    """The set of worker processes on this host (reference Pod in
    launch/controllers/collective.py)."""

    def __init__(self, args, restart_count=0):
        self.args = args
        self.restart_count = restart_count
        self.procs: list[subprocess.Popen] = []
        self.log_paths: list[str] = []
        self.wd_report_paths: list[str] = []
        self.flight_paths: list[str] = []

    def spawn(self):
        args = self.args
        nnodes = int(str(args.nnodes).split(":")[0])
        _check_one_process_per_chip(args.nproc_per_node, os.environ)
        os.makedirs(args.log_dir, exist_ok=True)
        for local in range(args.nproc_per_node):
            env = dict(os.environ)
            env["PADDLE_TRAINER_ID"] = str(
                args.rank * args.nproc_per_node + local)
            env["PADDLE_TRAINERS_NUM"] = str(nnodes * args.nproc_per_node)
            env["PADDLE_LOCAL_RANK"] = str(local)
            env["PADDLE_JOB_ID"] = args.job_id
            env["PADDLE_RESTART_COUNT"] = str(self.restart_count)
            if args.master:
                env["PADDLE_MASTER"] = args.master
                env["JAX_COORDINATOR_ADDRESS"] = args.master
            log_path = os.path.join(
                args.log_dir, f"workerlog.{local}.r{self.restart_count}")
            self.log_paths.append(log_path)
            # comm-watchdog post-mortem channel: the worker's spill thread
            # appends timeout reports here (comm_watchdog.enable), and the
            # launcher folds the file into the worker log on death so
            # hang-induced restarts are diagnosable after the fact
            wd_path = log_path + ".wd"
            try:
                # stale report from a previous launcher run in the same
                # log_dir must not be pinned on this pod's death (the
                # LogWatcher guards the .log channel the same way)
                os.unlink(wd_path)
            except OSError:
                pass
            env["PADDLE_WD_REPORT_FILE"] = wd_path
            self.wd_report_paths.append(wd_path)
            # flight-recorder post-mortem channel: ResilientTrainer (and the
            # SIGTERM/excepthook handlers it installs) dump the last-N-steps
            # telemetry ring here; folded into the worker log on death like
            # the watchdog spill
            fl_path = log_path + ".flight"
            try:
                os.unlink(fl_path)
            except OSError:
                pass
            env["PADDLE_FLIGHT_FILE"] = fl_path
            self.flight_paths.append(fl_path)
            if args.max_restart > 0:
                # restartable pods escalate hangs: the spill thread's
                # FatalError line trips the LogWatcher → teardown → respawn
                env["PADDLE_WD_FATAL"] = "1"
            logf = open(log_path, "ab")
            proc = subprocess.Popen(
                [sys.executable, args.training_script,
                 *args.training_script_args],
                env=env, stdout=logf, stderr=subprocess.STDOUT,
            )
            proc._logf = logf  # closed in terminate()/watch()
            self.procs.append(proc)
            print(f"[launch] worker {local} (restart {self.restart_count}) "
                  f"logging to {log_path}", file=sys.stderr)

    def terminate(self, grace=3.0):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + grace
        for p in self.procs:
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.1)
            if p.poll() is None:
                p.kill()
            try:  # reap: the restart loop keeps this process alive, so an
                p.wait(timeout=5)  # unreaped child would linger as a zombie
            except Exception:
                pass
        self._close_logs()

    def _close_logs(self):
        for p in self.procs:
            f = getattr(p, "_logf", None)
            if f is not None and not f.closed:
                f.close()

    def dump_watchdog_reports(self):
        """Post-mortem: drain each worker's comm-watchdog spill file AND its
        flight-recorder dump into its log (and the launcher's stderr) before
        respawning, so the stuck-step report and the last-N-steps telemetry
        ring survive the restart that destroys the worker process."""
        channels = [
            ("comm-watchdog", self.wd_report_paths),
            ("flight-recorder", self.flight_paths),
        ]
        for kind, paths in channels:
            for local, (log_path, src_path) in enumerate(
                    zip(self.log_paths, paths)):
                try:
                    with open(src_path) as f:
                        report = f.read().strip()
                except OSError:
                    continue
                if not report:
                    continue
                banner = (f"\n[launch] {kind} post-mortem for worker "
                          f"{local} (restart {self.restart_count}):"
                          f"\n{report}\n")
                try:
                    with open(log_path, "a") as f:
                        f.write(banner)
                except OSError:
                    pass
                print(banner, file=sys.stderr)

    def watch(self, fatal_evt=None):
        """Block until the pod finishes, a worker fails, or the log watcher
        flags a fatal line (covers workers that log the error but HANG in a
        collective instead of exiting — the failure mode the reference
        watcher exists for); returns the pod exit code (first nonzero
        worker code, 1 on fatal-log teardown, 0 when all succeed)."""
        procs = list(self.procs)
        while procs:
            if fatal_evt is not None and fatal_evt.is_set():
                self.terminate()
                return 1
            for p in list(procs):
                ret = p.poll()
                if ret is None:
                    continue
                procs.remove(p)
                if ret != 0:
                    self.terminate()
                    return ret
            time.sleep(0.3)
        self._close_logs()
        return 0


def _parse():
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--master", default=None, help="coordinator ip:port (rank-0 host)")
    p.add_argument("--nnodes", default="1",
                   help="number of hosts, N or N:M (elastic range)")
    p.add_argument("--rank", type=int, default=int(os.environ.get("PADDLE_TRAINER_ID", 0)))
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes per host (TPU: 1, enforced)")
    p.add_argument("--log_dir", default="log")
    p.add_argument("--run_mode", default="collective")
    p.add_argument("--job_id", default="default")
    p.add_argument("--max_restart", type=int,
                   default=int(os.environ.get("PADDLE_MAX_RESTART", "0")),
                   help="elastic: respawn the pod up to N times on failure")
    p.add_argument("--elastic_level", type=int, default=None,
                   help="-1/0 off, 1 in-place pod restart (implies "
                        "max_restart>=1 when set)")
    p.add_argument("--devices", default=None, help="accepted for parity; TPU devices are auto-discovered")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args()
    # N:M elastic range implies restartability (reference --nnodes=2:4)
    if ":" in str(args.nnodes) and args.max_restart == 0:
        args.max_restart = 3
    return args


def launch():
    args = _parse()
    if args.elastic_level and args.elastic_level > 0 and args.max_restart == 0:
        args.max_restart = 3  # reference elastic default

    restart = 0
    current: list[Pod] = []

    def _terminate(signum, frame):
        for pod in current:
            pod.terminate()
        sys.exit(1)

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)

    while True:
        pod = Pod(args, restart_count=restart)
        current[:] = [pod]
        pod.spawn()
        fatal_evt = threading.Event()
        watcher = LogWatcher(pod.log_paths,
                             on_fatal=lambda p, line: fatal_evt.set())
        watcher.start()
        code = pod.watch(fatal_evt)
        watcher.stop()
        watcher.join(timeout=5)
        for line in watcher.fatal_lines:
            print(f"[launch] fatal log: {line}", file=sys.stderr)
        if code != 0:
            pod.dump_watchdog_reports()
        if code == 0:
            sys.exit(0)
        if restart >= args.max_restart:
            print(f"[launch] pod failed (exit {code}), restarts exhausted "
                  f"({restart}/{args.max_restart})", file=sys.stderr)
            sys.exit(code)
        restart += 1
        print(f"[launch] pod failed (exit {code}); restart "
              f"{restart}/{args.max_restart}", file=sys.stderr)
        time.sleep(1.0)
