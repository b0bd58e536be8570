"""Multi-process launcher: ``python -m paddle_tpu.distributed.launch``.

Reference contract: ``python/paddle/distributed/launch.py`` — spawn one
training process per device, export the trainer-identity env
(PADDLE_TRAINER_ID / PADDLE_CURRENT_ENDPOINT / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS), supervise the pack and kill everyone when one
child dies, teeing per-rank logs.

Preemption contract (fluid/preemption.py): every child leads its own
process GROUP (``start_new_session=True``), so terminating a trainer
terminates the DataLoader/dataset worker processes it forked too.  A
SIGTERM to the launcher (the scheduler's preemption notice) forwards
SIGTERM to every child group — trainers with ``preemption.install()``
drain and checkpoint — and escalates to SIGKILL for whatever is still
alive after ``--grace_period`` seconds.  No orphans, ever.

Liveness contract (``--heartbeat_timeout S``, fluid/watchdog.py): each
child's in-process watchdog mtime-touches a per-rank heartbeat file the
launcher exports via ``PADDLE_HEARTBEAT_FILE``.  A rank whose
interpreter is too wedged even for its own watchdog thread to run (a C
extension parked holding the GIL) stops touching — after ``S`` seconds
of staleness the launcher SIGKILLs that rank's process group and
routes the death through the normal failure machinery (plain packs
respawn the rank; ``--coordinator`` packs tear down and relaunch under
``--max_restarts``/``--elastic_min_nproc``).  Ranks that self-abort
exit with the watchdog's dedicated code (117), so teardown post-mortems
log which ranks HUNG (heartbeat-stale or watchdog-abort) vs CRASHED
(other nonzero exits) vs drained — distinguishing the root-cause rank
from gloo abort-cascade victims.

Restart contract (``--max_restarts N``, fluid/elastic.py): a child that
exits nonzero is relaunched up to N times across the job, each restart
logged to the launcher's stderr.  Plain packs relaunch just the dead
rank (fresh session-leader process group; its old group is reaped
first).  ``--coordinator`` packs are one jax.distributed world — a
single member cannot rejoin — so the whole pack is torn down (the
existing terminate_pack/escalation machinery) and relaunched at a fresh
coordinator port; with ``--elastic_min_nproc M`` the relaunch shrinks
the world by ONE, floored at M (exit codes cannot tell an organic
failure from a collective-abort cascade, so a multi-host loss converges
over successive restarts) — the
restart-with-new-world edge of elastic training: the fresh processes
reshard-restore the last checkpoint and continue
(docs/distributed.md "Elastic training").  Relaunched children see
``PADDLE_ELASTIC_ATTEMPT`` (pack relaunches so far) and
``PADDLE_ELASTIC_PREV_NPROC`` (the previous attempt's world size).
"""

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

# paddle_tpu.fluid.watchdog.EXIT_HANG, mirrored: the supervisor must
# stay importable without jax (tests pin the two constants equal)
HANG_EXIT_CODE = 117


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="paddle_tpu multi-process launcher")
    p.add_argument("--cluster_node_ips", default="127.0.0.1")
    p.add_argument("--node_ip", default="127.0.0.1")
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--nproc_per_node", type=int, default=None,
                   help="processes per node (default: 1).  On a TPU host "
                        "the supported shape is ONE process driving all "
                        "local chips: more than one plain-mode process "
                        "is refused unless JAX_PLATFORMS pins the "
                        "children off the TPU")
    p.add_argument("--selected_devices", default=None,
                   help="comma list overriding nproc_per_node")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--grace_period", type=float, default=30.0,
                   help="seconds between forwarding SIGTERM to the child "
                        "process groups and escalating to SIGKILL")
    p.add_argument("--heartbeat_timeout", type=float, default=0.0,
                   help="launcher-side liveness (fluid/watchdog.py): "
                        "children's armed watchdogs mtime-touch a "
                        "per-rank heartbeat file (PADDLE_HEARTBEAT_FILE "
                        "is exported); a rank whose file goes stale by "
                        "this many seconds is SIGKILLed and handled "
                        "like a crash (restart budget, elastic "
                        "relaunch).  Catches interpreters too wedged "
                        "to self-abort.  0 (default) = off.  Size it "
                        "well above FLAGS_watchdog_timeout_s plus the "
                        "watchdog poll interval (~1s)")
    p.add_argument("--coordinator", nargs="?", const="auto", default=None,
                   help="multi-host SPMD mode (fluid.distributed.init over "
                        "jax.distributed): spawn --nproc_per_node "
                        "SINGLE-DEVICE CPU processes with distinct process "
                        "ids, rendezvousing at this ip:port ('auto' = a "
                        "port past the endpoint range on this node).  "
                        "Collectives run gloo-backed across the processes "
                        "— the entrypoint CI uses for genuine 2-process "
                        "SPMD parity tests (docs/distributed.md)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="relaunch children that exit nonzero, up to this "
                        "many times across the job (plain mode: just the "
                        "dead rank; --coordinator mode: the whole pack at "
                        "a fresh coordinator port).  Default 0 = fail "
                        "fast, the historical behavior")
    p.add_argument("--elastic_min_nproc", type=int, default=None,
                   help="with --coordinator and --max_restarts: relaunch "
                        "a crashed pack one process SMALLER (a lost "
                        "multi-host converges over successive restarts), "
                        "never below this floor — "
                        "restart-with-new-world for elastic training "
                        "(children reshard-restore the last checkpoint; "
                        "fluid/elastic.py)")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.elastic_min_nproc is not None and not args.coordinator:
        p.error("--elastic_min_nproc needs --coordinator: only a "
                "jax.distributed pack can change its world size on "
                "relaunch")
    if args.elastic_min_nproc is not None and args.elastic_min_nproc < 1:
        p.error("--elastic_min_nproc must be >= 1: a floor of 0 would "
                "let successive relaunches shrink the job to zero "
                "processes and report success")
    if args.elastic_min_nproc is not None and args.max_restarts < 1:
        p.error("--elastic_min_nproc needs --max_restarts >= 1: without "
                "a restart budget a crash fails fast and no "
                "restart-with-new-world ever happens")
    n_nodes = len([ip for ip in args.cluster_node_ips.split(",")
                   if ip.strip()])
    if args.elastic_min_nproc is not None and n_nodes > 1:
        p.error("--elastic_min_nproc is single-node only: the shrink "
                "operates on this node's process count, and a "
                "multi-node pack would shrink by the node count per "
                "relaunch — run one elastic pack per node is not a "
                "supported topology yet")
    if args.coordinator and args.max_restarts > 0 and n_nodes > 1:
        p.error("--coordinator with --max_restarts is single-node "
                "only: each node's launcher decides relaunch (and the "
                "attempt-shifted coordinator port) locally, so a "
                "multi-node pack would desync after a crash instead of "
                "failing fast")
    if args.heartbeat_timeout < 0:
        p.error("--heartbeat_timeout must be >= 0 (seconds of "
                "heartbeat-file staleness before a rank is declared "
                "hung; 0 disables launcher-side liveness)")
    return args


class _LauncherStop(Exception):
    """Raised out of the supervision loop when the launcher itself is
    told to stop (scheduler preemption)."""


def _signal_pack(procs, sig):
    """Deliver ``sig`` to every child's whole process group.  Children
    are session leaders (start_new_session), so pgid == the child's pid
    — signal that directly: resolving via os.getpgid would fail for a
    child that already exited, leaving its forked workers orphaned (the
    group can outlive its leader)."""
    for proc, _log, _rank in procs:
        try:
            os.killpg(proc.pid, sig)
        except (OSError, ProcessLookupError):
            try:
                proc.send_signal(sig)
            except (OSError, ProcessLookupError):
                pass


def terminate_pack(procs, grace_period, hung=None):
    """Graceful pack teardown: SIGTERM every child process group, give
    trainers ``grace_period`` seconds to drain (preemption hooks save a
    final checkpoint and exit 0), then SIGKILL the groups of whatever
    survived.  Waits everything and closes logs.

    ``hung`` (optional): {rank: heartbeat staleness seconds} observed
    by the launcher's liveness monitor.  When given, a post-mortem line
    classifying every rank — HUNG (heartbeat-stale, or the watchdog's
    dedicated self-abort exit code) vs CRASHED (other nonzero exits) vs
    drained/killed-in-teardown — lands in the launcher log, so the
    root-cause rank is readable instead of guessed from a gloo
    abort-cascade where every sibling also dies nonzero."""
    _signal_pack(procs, signal.SIGTERM)
    deadline = time.monotonic() + grace_period
    pending = list(procs)
    while pending and time.monotonic() < deadline:
        pending = [t for t in pending if t[0].poll() is None]
        if pending:
            time.sleep(0.05)
    if pending:
        _signal_pack(pending, signal.SIGKILL)
    for proc, log, _rank in procs:
        proc.wait()
        if log:
            log.close()
    if hung is not None and (hung or any(
            t[0].returncode not in (0, -signal.SIGTERM, -signal.SIGKILL)
            for t in procs)):
        parts = []
        for proc, _log, rank in sorted(procs, key=lambda t: t[2]):
            ret = proc.returncode
            if rank in hung:
                parts.append("rank %d HUNG (heartbeat stale %.1fs, "
                             "killed)" % (rank, hung[rank]))
            elif ret == HANG_EXIT_CODE:
                parts.append("rank %d HUNG (watchdog self-abort, "
                             "exit %d)" % (rank, ret))
            elif ret not in (0, -signal.SIGTERM, -signal.SIGKILL):
                parts.append("rank %d crashed (exit %d)" % (rank, ret))
            else:
                parts.append("rank %d ok/teardown (exit %s)"
                             % (rank, ret))
        _restart_log("post-mortem: " + "; ".join(parts))


def get_cluster_endpoints(args, nproc):
    ips = [ip.strip() for ip in args.cluster_node_ips.split(",") if ip]
    eps = []
    for ip in ips:
        for i in range(nproc):
            eps.append("%s:%d" % (ip, args.started_port + i))
    return ips, eps


def _restart_log(msg):
    """Restart events land in the launcher log (its own stderr — the
    per-rank files hold the children's output)."""
    sys.stderr.write("[launch] %s\n" % msg)
    sys.stderr.flush()


def _supervise_pack(args, nproc, devices, attempt, prev_nproc,
                    restarts, stop_seen):
    """Spawn + supervise ONE pack incarnation.  Returns None when the
    pack finished (clean exit, or a terminal failure handled via
    sys.exit), or ``(fail_rank, code, failed_ranks)`` when a
    coordinator-mode pack crashed with restart budget remaining — the
    caller relaunches.  Plain-mode children are relaunched in place
    (rank-local restart) without tearing the pack down.

    ``restarts`` is the job-wide mutable budget ``{"used": int}``;
    ``attempt`` counts coordinator-pack relaunches (stamped into the
    children's PADDLE_ELASTIC_ATTEMPT); ``stop_seen`` is the launcher's
    stop-signal flag list, polled at safe points (never mid-spawn, so a
    just-forked child is always in ``procs`` before a stop can
    interrupt — no orphan window)."""
    ips, cluster_eps = get_cluster_endpoints(args, nproc)
    node_rank = ips.index(args.node_ip)
    # jax.distributed rendezvous address: a dedicated port past the
    # endpoint range on the first node (read by distributed.env).  Each
    # pack relaunch moves one port up — the old coordinator socket may
    # still be in TIME_WAIT, and a straggler from the previous attempt
    # must never rendezvous into the new world.
    coordinator = "%s:%d" % (ips[0], args.started_port + 1017)
    if args.coordinator and args.coordinator != "auto":
        coordinator = args.coordinator
    if attempt:
        host, port = coordinator.rsplit(":", 1)
        coordinator = "%s:%d" % (host, int(port) + attempt)
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    # launcher-side liveness (--heartbeat_timeout): one heartbeat file
    # per rank, mtime-touched by the child's armed watchdog thread.
    # The dir persists across pack relaunches (stale files are removed
    # before each respawn, so a fresh child never inherits a dead
    # child's staleness)
    hb_dir = None
    if args.heartbeat_timeout > 0:
        hb_dir = getattr(args, "_hb_dir", None)
        if hb_dir is None:
            hb_dir = args.log_dir or tempfile.mkdtemp(prefix="paddle_hb_")
            os.makedirs(hb_dir, exist_ok=True)
            args._hb_dir = hb_dir

    def _hb_path(rank):
        return os.path.join(hb_dir, "heartbeat.%d" % rank)

    def spawn(local_rank):
        rank = node_rank * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_CURRENT_ENDPOINT": cluster_eps[rank],
            "PADDLE_TRAINERS_NUM": str(len(cluster_eps)),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(cluster_eps),
            "PADDLE_DIST_COORDINATOR": coordinator,
            "FLAGS_selected_tpus": devices[local_rank],
            "PADDLE_ELASTIC_ATTEMPT": str(attempt),
        })
        if prev_nproc is not None:
            env["PADDLE_ELASTIC_PREV_NPROC"] = str(prev_nproc)
        if hb_dir is not None:
            # a fresh child must start with a clean liveness clock —
            # its watchdog recreates the file when it arms (a child
            # that never arms is simply not liveness-monitored)
            try:
                os.unlink(_hb_path(rank))
            except OSError:
                pass
            env["PADDLE_HEARTBEAT_FILE"] = _hb_path(rank)
        if args.coordinator:
            # --coordinator multi-host mode: each child is ONE
            # single-device CPU process of the jax.distributed world
            # (fluid.distributed.init reads PADDLE_MULTIHOST_CPU and
            # switches CPU collectives to gloo before backend init) —
            # genuine multi-process SPMD on one machine, the CI
            # substrate for pod-scale parity tests.  The operator's own
            # XLA_FLAGS are preserved; only a conflicting virtual
            # device count is replaced with the mode's single-device
            # pin.  PADDLE_COORDINATOR_DEVICES_PER_PROC=N (opt-in)
            # gives each process N virtual CPU devices instead — the
            # simulated multi-granule topology hierarchical-collective
            # tests need (2 procs x 2 devices = a ("dcn","ici") mesh
            # whose member axes are both >1); the env must be explicit
            # because the pack inherits the parent's XLA_FLAGS and the
            # test conftest's own 8-device pin must never leak in.
            xla = [f for f in env.get("XLA_FLAGS", "").split()
                   if "xla_force_host_platform_device_count" not in f]
            dcount = os.environ.get(
                "PADDLE_COORDINATOR_DEVICES_PER_PROC", "") or "1"
            xla.append("--xla_force_host_platform_device_count=%d"
                       % max(1, int(dcount)))
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": " ".join(xla),
                "PADDLE_MULTIHOST_CPU": "1",
            })
        cmd = [sys.executable, "-u", args.training_script] + \
            args.training_script_args
        log = None
        if args.log_dir:
            log = open(os.path.join(args.log_dir,
                                    "workerlog.%d" % rank), "a" if attempt
                       or restarts["used"] else "w")
        # start_new_session: the child leads its own process group, so
        # pack termination reaches DataLoader worker processes it forks
        return (subprocess.Popen(cmd, env=env, stdout=log,
                                 stderr=subprocess.STDOUT if log
                                 else None,
                                 start_new_session=True), log, rank)

    # supervise: if any child dies non-zero, kill the pack (launch.py
    # process-supervision contract) — unless the restart budget covers
    # it (plain mode: respawn the rank in place; coordinator mode:
    # report the crash up for a whole-pack relaunch).  Spawning happens
    # INSIDE the supervised window: a stop signal landing mid-spawn
    # must tear down the children already forked, not leak them
    fail_rank, code = None, 0
    failed_ranks = set()
    hung_ranks = {}   # rank -> heartbeat staleness (s) when killed
    procs = []
    drained = []   # children that exited during supervision
    try:
        for local_rank in range(nproc):
            if stop_seen:
                raise _LauncherStop(str(stop_seen[0]))
            procs.append(spawn(local_rank))
        while procs:
            if stop_seen:
                raise _LauncherStop(str(stop_seen[0]))
            if hb_dir is not None:
                # liveness sweep: a rank whose heartbeat file exists
                # but went stale is too wedged even for its own
                # watchdog thread — SIGKILL its group; the poll below
                # then routes the death through the normal failure
                # machinery (respawn / pack relaunch)
                now = time.time()
                for proc, _log, rank in procs:
                    if rank in hung_ranks:
                        continue
                    try:
                        age = now - os.path.getmtime(_hb_path(rank))
                    except OSError:
                        continue   # never armed (or already cleaned)
                    if age > args.heartbeat_timeout:
                        hung_ranks[rank] = age
                        _restart_log(
                            "rank %d heartbeat stale (%.1fs > %.1fs): "
                            "declaring it hung, killing its process "
                            "group" % (rank, age,
                                       args.heartbeat_timeout))
                        try:
                            os.killpg(proc.pid, signal.SIGKILL)
                        except (OSError, ProcessLookupError):
                            try:
                                proc.kill()
                            except (OSError, ProcessLookupError):
                                pass
            for tup in list(procs):
                proc, log, rank = tup
                ret = proc.poll()
                if ret is None:
                    continue
                procs.remove(tup)
                if ret != 0 and not args.coordinator and \
                        restarts["used"] < args.max_restarts:
                    # rank-local restart: reap whatever the dead
                    # child's process group still holds (a group
                    # outlives its leader), then respawn the rank as a
                    # fresh session leader
                    restarts["used"] += 1
                    if rank in hung_ranks:
                        why = "hung (heartbeat stale %.1fs)" \
                            % hung_ranks.pop(rank)
                    elif ret == HANG_EXIT_CODE:
                        why = "hung (watchdog abort, exit %d)" % ret
                    else:
                        why = "exited %d" % ret
                    _restart_log(
                        "rank %d %s; restarting it (restart "
                        "%d/%d)" % (rank, why, restarts["used"],
                                    args.max_restarts))
                    try:
                        os.killpg(proc.pid, signal.SIGKILL)
                    except (OSError, ProcessLookupError):
                        pass
                    if log:
                        log.close()
                    procs.append(spawn(rank - node_rank * nproc))
                    continue
                drained.append(tup)
                if log:
                    log.close()
                if ret != 0:
                    fail_rank, code = rank, ret
                    failed_ranks.add(rank)
                    raise ChildProcessError()
            time.sleep(0.2)
    except (ChildProcessError, KeyboardInterrupt, _LauncherStop) as e:
        # ranks ALREADY dead nonzero before the teardown begins failed
        # on their own and shrink the survivor world, not just the
        # first crash the poll loop noticed (two lost devices in one
        # poll tick).  Ranks that exit nonzero AFTER the teardown's
        # SIGTERM are collective-abort cascade victims of the same
        # crash — healthy hosts, not failures: counting them would
        # collapse the world to the --elastic_min_nproc floor on one
        # lost host
        for p2, _l2, r2 in procs + drained:
            if p2.poll() is not None and p2.returncode not in (
                    0, -signal.SIGTERM, -signal.SIGKILL):
                failed_ranks.add(r2)
        # launcher-declared hung ranks count as failures too — they
        # died by OUR SIGKILL (excluded above by exit code), but each
        # is a root-cause loss the elastic shrink policy must see
        failed_ranks.update(hung_ranks)
        # include already-exited children: their process GROUPS may
        # still hold forked workers (a group outlives its leader).
        # The stop handler only sets the flag (never raises), so this
        # teardown — grace wait, SIGKILL escalation, reaping — always
        # runs to completion, a mid-teardown SIGTERM included
        terminate_pack(procs + drained, args.grace_period,
                       hung=hung_ranks)
        stopped = isinstance(e, _LauncherStop) or bool(stop_seen)
        if fail_rank is not None:
            if not stopped and args.coordinator and \
                    restarts["used"] < args.max_restarts:
                restarts["used"] += 1
                return fail_rank, code, failed_ranks
            sys.stderr.write(
                "rank %d failed with exit code %d; pack terminated\n"
                % (fail_rank, code))
            sys.exit(code or 1)
        if stopped:
            # preemption path: children that drained cleanly (exit 0
            # after their final checkpoint) make the whole job clean
            bad = [(r, p.returncode) for p, _l, r in procs + drained
                   if p.returncode not in (0, -signal.SIGTERM)]
            if bad:
                sys.stderr.write(
                    "preempted; rank(s) %s exited non-zero\n"
                    % (sorted(r for r, _ in bad),))
                sys.exit(1)
    except BaseException:
        # spawn/supervision failure (Popen OSError, workerlog open on a
        # full disk, ...): children already forked must not outlive the
        # launcher — tear the pack down, then propagate the real error
        terminate_pack(procs + drained, args.grace_period)
        raise
    return None


def _children_may_claim_tpu():
    """True unless JAX_PLATFORMS pins the children off the TPU.  Read
    from the environment, never from JAX: a launcher that initialised a
    backend would hold the chips its children need."""
    platforms = os.environ.get("JAX_PLATFORMS", "").replace(" ", "")
    return not platforms or "tpu" in platforms.split(",")


def launch(args):
    if args.selected_devices:
        devices = [d for d in args.selected_devices.split(",") if d]
        nproc = len(devices)
    else:
        nproc = args.nproc_per_node or 1
        devices = [str(i) for i in range(nproc)]
    if nproc > 1 and not args.coordinator and _children_may_claim_tpu():
        # nothing restricts a child's chip visibility (FLAGS_selected_tpus
        # is advisory), and a chip belongs to one process at a time: N
        # children would each try to own every local chip
        sys.stderr.write(
            "plain mode with %d processes per host is refused: on a TPU "
            "host ONE process drives all local chips (CompiledProgram."
            "with_data_parallel or GradAllReduce(nranks=N) inside it).  "
            "For a CPU pack set JAX_PLATFORMS=cpu, or use --coordinator "
            "(single-device CPU processes).\n" % nproc)
        return 2
    if args.elastic_min_nproc is not None and \
            args.elastic_min_nproc > nproc:
        # a floor above the launched world would GROW the pack on
        # relaunch — fail fast instead of silently inverting the
        # shrink-only semantics on the first crash
        sys.stderr.write(
            "--elastic_min_nproc %d exceeds the launched world size %d\n"
            % (args.elastic_min_nproc, nproc))
        return 2

    # the scheduler preempts the LAUNCHER: forward the stop to the
    # pack at the supervision loop's next safe point
    stop_seen = []

    def _on_stop_signal(signum, frame):
        # flag only, NEVER raise: an async raise could land between a
        # child's Popen() and its bookkeeping (orphaning the child) or
        # mid-teardown (skipping the SIGKILL escalation).  The
        # supervision loop polls the flag at safe points
        if not stop_seen:
            stop_seen.append(signal.Signals(signum).name)

    prev_term = prev_int = None
    try:
        prev_term = signal.signal(signal.SIGTERM, _on_stop_signal)
        # Ctrl-C too: an async KeyboardInterrupt could land between a
        # child's Popen() and its bookkeeping, orphaning it — the flag
        # gives SIGINT the same safe-point drain as SIGTERM
        prev_int = signal.signal(signal.SIGINT, _on_stop_signal)
    except ValueError:
        pass   # non-main thread (tests driving launch() directly)

    restarts = {"used": 0}
    attempt = 0
    prev_nproc = None
    pending_code = None   # exit code of a crashed pack awaiting relaunch
    try:
        while True:
            if stop_seen:
                # stop landed between packs: nothing is running —
                # _supervise_pack tears its pack down before returning.
                # A crash awaiting relaunch must still report as a
                # FAILURE (its ranks died without draining), exactly
                # like the in-pack crash+stop path — not as a clean
                # preemption drain
                if pending_code is not None:
                    sys.stderr.write(
                        "rank failed with exit code %d; stop requested "
                        "— not relaunching\n" % pending_code)
                    return pending_code or 1
                return 0
            crash = _supervise_pack(args, nproc, devices, attempt,
                                    prev_nproc, restarts, stop_seen)
            if crash is None:
                return 0
            # coordinator-pack relaunch (restart-with-new-world when
            # --elastic_min_nproc): fresh attempt id → fresh
            # coordinator port, survivor count when shrinking
            fail_rank, code, failed_ranks = crash
            pending_code = code
            attempt += 1
            new_nproc = nproc
            if args.elastic_min_nproc is not None:
                # shrink by exactly ONE per relaunch: exit codes
                # cannot tell an organic failure from a gloo
                # collective-abort cascade (every sibling of a crashed
                # rank can die nonzero before the teardown reaches
                # it), so counting nonzero exits would collapse the
                # world to the floor on one lost host.  A multi-host
                # loss converges over successive restarts, one budget
                # unit each; the nonzero rank set is logged for the
                # operator
                new_nproc = max(int(args.elastic_min_nproc),
                                nproc - 1)
            _restart_log(
                "rank %d exited %d (nonzero ranks %s); relaunching "
                "pack (restart %d/%d, attempt %d, world %d -> %d)"
                % (fail_rank, code, sorted(failed_ranks),
                   restarts["used"], args.max_restarts, attempt,
                   nproc, new_nproc))
            prev_nproc, nproc = nproc, new_nproc
            # nproc only ever shrinks (floor validated <= the launched
            # world), so truncation suffices
            devices = devices[:nproc]
    finally:
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
        if prev_int is not None:
            signal.signal(signal.SIGINT, prev_int)


def main():
    sys.exit(launch(parse_args()) or 0)


if __name__ == "__main__":
    main()
