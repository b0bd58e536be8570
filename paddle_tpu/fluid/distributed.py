"""Pod-scale multi-process SPMD runtime bring-up.

Reference contract: the reference's NCCL bootstrap gives every trainer
an identity (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM) and a rendezvous
(``c_gen_nccl_id`` RPC).  The TPU-native equivalent is
``jax.distributed.initialize``: one coordinator, every process
connects, and ``jax.devices()`` becomes the GLOBAL device list — a
single GSPMD mesh (and the executor's shard_map) then spans hosts, and
XLA routes collectives over ICI/DCN instead of NCCL rings ("Scale
MLPerf-0.6 models on Google TPU-v3 Pods", PAPERS.md).

This module is the ONE place the multi-process world is initialized and
queried:

- :func:`init` — wrap ``jax.distributed.initialize`` with env-var
  autodetection (the ``distributed/launch.py`` contract: PADDLE_TRAINER_ID
  / PADDLE_TRAINERS_NUM / PADDLE_DIST_COORDINATOR /
  PADDLE_LOCAL_DEVICE_IDS), idempotent, no-op for a world of one.  On a
  CPU backend it first switches XLA's cross-process collectives to the
  gloo transport (:func:`ensure_cpu_collectives`) — without it a CPU
  pod raises "Multiprocess computations aren't implemented on the CPU
  backend", which is exactly how CI runs genuine 2-process SPMD parity
  tests on one machine (``launch.py --coordinator``).
- :func:`process_index` / :func:`process_count` / :func:`is_chief` —
  identity queries every runtime layer shares (telemetry labels,
  checkpoint chief election, device selection).
- :func:`barrier` — ``multihost_utils.sync_global_devices``: all
  processes reach the same named point before any continues (the
  multi-host checkpoint commit protocol's fence, checkpoint.py).
- :func:`any_process` — global OR of one host-side bool (one tiny
  ``process_allgather``): the preemption-stop consensus, so a SIGTERM
  delivered to ONE process drains EVERY process at the same window
  boundary instead of deadlocking the survivors inside a collective.
- :func:`shutdown` — tear the world down so a later :func:`init` can
  connect with a DIFFERENT topology: the in-process edge of elastic
  training (fluid/elastic.py); the production resize path is a process
  restart through ``distributed/launch.py``.

See docs/distributed.md "Multi-host (pod-scale) runtime".
"""

import os
import warnings

import numpy as np

from . import telemetry

# NOTE: jax is imported lazily inside functions where possible so that
# ensure_cpu_collectives() can run before the backend initializes.

# every host-side collective entry (barrier fences, consensus
# allgathers) counts here, by kind.  This is the introspection pin the
# async checkpoint protocol is verified against: its commit is
# collective-FREE, so the counter's delta across an async save must be
# exactly zero (tests pin this; docs/checkpointing.md "Async pod
# checkpoints").
_m_collectives = telemetry.counter(
    "distributed_collective_calls_total",
    "host-side collective entries (barrier/consensus), by kind")

_state = {
    "initialized": False,       # init() ran (even as a world-of-one no-op)
    "connected": False,         # jax.distributed.initialize actually ran
    "process_id": 0,
    "num_processes": 1,
}


def parallel_env_from_env():
    """(coordinator, num_processes, process_id, local_device_ids) from
    the PADDLE_* env the launcher exports (distributed/launch.py)."""
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    coord = os.environ.get("PADDLE_DIST_COORDINATOR")
    if coord is None:
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        if eps:
            # derive a dedicated rendezvous port just past the endpoint
            # range so it cannot collide with PS/RPC listeners
            ip, port = eps.split(",")[0].rsplit(":", 1)
            coord = "%s:%d" % (ip, int(port) + 1017)
    raw = os.environ.get("PADDLE_LOCAL_DEVICE_IDS", "")
    local_ids = [int(d) for d in raw.replace(",", " ").split()] \
        if raw.strip() else None
    return coord, nproc, rank, local_ids


def ensure_cpu_collectives(implementation="gloo"):
    """Route CPU cross-process collectives through ``implementation``
    (gloo by default).  Must run before the CPU backend initializes;
    idempotent.  Non-CPU backends are unaffected — the knob only matters
    when the computation actually lands on the CPU platform."""
    import jax
    jax.config.update("jax_cpu_collectives_implementation", implementation)


def init(coordinator_address=None, num_processes=None, process_id=None,
         local_device_ids=None):
    """Connect this process to the global SPMD world.

    Every argument autodetects from the launcher env
    (:func:`parallel_env_from_env`), so training scripts call
    ``fluid.distributed.init()`` unconditionally: a world of one is a
    no-op, a launched pack rendezvouses at the coordinator.  Idempotent
    — repeated calls (or an ``init_parallel_env()`` after ``init()``)
    return the existing identity instead of re-initializing.

    Returns ``(process_id, num_processes)``.
    """
    env_coord, env_nproc, env_rank, env_local = parallel_env_from_env()
    coordinator_address = coordinator_address or env_coord
    num_processes = env_nproc if num_processes is None else int(num_processes)
    process_id = env_rank if process_id is None else int(process_id)
    if local_device_ids is None:
        local_device_ids = env_local

    if _state["connected"]:
        if (num_processes != _state["num_processes"] or
                process_id != _state["process_id"]):
            raise RuntimeError(
                "fluid.distributed.init called twice with a different "
                "identity: already process %d/%d, asked for %d/%d — "
                "re-initializing the jax.distributed world needs a fresh "
                "process" % (_state["process_id"],
                             _state["num_processes"],
                             process_id, num_processes))
        return _state["process_id"], _state["num_processes"]

    if num_processes <= 1:
        # a world of one is a no-op and does NOT latch: a later call
        # with a real multi-process identity may still connect
        _state["initialized"] = True
        return 0, 1
    if not coordinator_address:
        raise ValueError(
            "fluid.distributed.init: num_processes=%d but no coordinator "
            "address — pass coordinator_address= or launch via "
            "paddle_tpu.distributed.launch (it exports "
            "PADDLE_DIST_COORDINATOR)" % num_processes)

    import jax

    # CPU pods (CI, laptops, manual two-terminal runs) need the gloo
    # transport picked BEFORE the backend spins up; TPU backends ignore
    # the knob, so ALWAYS set it (probing the backend here would
    # initialize it, which is exactly what must not happen before
    # jax.distributed.initialize)
    ensure_cpu_collectives()

    kwargs = {}
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id, **kwargs)
    _state["initialized"] = True
    _state["connected"] = True
    _state["process_id"] = int(jax.process_index())
    _state["num_processes"] = int(jax.process_count())

    # every metric / step-event / JSONL line from this process now
    # carries its process index (docs/observability.md)
    from . import telemetry
    telemetry.set_process_index(_state["process_id"],
                                _state["num_processes"])
    return _state["process_id"], _state["num_processes"]


def shutdown():
    """Tear down the multi-process world so a later :func:`init` can
    connect with a DIFFERENT topology — the in-process edge of elastic
    training (fluid/elastic.py): after a preemption drain + durable
    save, the survivors re-rendezvous at the new world size and
    reshard-restore.

    Disconnects from the coordinator (``jax.distributed.shutdown``),
    resets this module's identity state, and clears the telemetry
    process label.  A world of one (never connected) just resets local
    state.  Idempotent.

    A process that WAS connected keeps the old world's device list
    cached, so joining a new multi-process world in the same process is
    unsupported: the resize path is a process restart —
    ``distributed/launch.py`` relaunches the pack at the survivor count
    (``--max_restarts`` / ``--elastic_min_nproc``) and the fresh
    processes init cleanly.  In-process re-init is for worlds of one
    changing sharding degree and for tests."""
    # fence: join any in-flight async checkpoint upload BEFORE the
    # world goes away.  The async commit protocol is storage-only (no
    # collective), so waiting here cannot deadlock against peers that
    # already left; a background save failure surfaces as a warning —
    # teardown must not raise.
    try:
        from . import checkpoint
        checkpoint.wait_all()
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException as e:   # noqa: BLE001 — teardown must not raise
        warnings.warn(
            "in-flight checkpoint save failed during shutdown (%s: %s) "
            "— the last committed checkpoint remains the latest"
            % (type(e).__name__, e), stacklevel=2)
    was_connected = _state["connected"]
    _state.update(initialized=False, connected=False,
                  process_id=0, num_processes=1)
    from . import telemetry
    telemetry.set_process_index(None)
    if not was_connected:
        return
    import jax
    try:
        jax.distributed.shutdown()
    except Exception as e:   # noqa: BLE001 — teardown must not raise
        warnings.warn(
            "jax.distributed.shutdown failed (%s: %s) — continuing; a "
            "fresh process is the reliable way to rejoin a new world"
            % (type(e).__name__, e), stacklevel=2)


def process_index():
    """This process's index in the global world (0 for single-process;
    authoritative from jax once a backend exists)."""
    if _state["connected"]:
        return _state["process_id"]
    try:
        import jax
        return int(jax.process_index())
    except Exception:
        return 0


def process_count():
    """Number of processes in the global world (1 for single-process)."""
    if _state["connected"]:
        return _state["num_processes"]
    try:
        import jax
        return int(jax.process_count())
    except Exception:
        return 1


def is_chief():
    """True on process 0 — the single writer of multi-host checkpoint
    commits (checkpoint.py) and the one rank that logs/saves in
    reference scripts."""
    return process_index() == 0


def barrier(name="fluid-barrier"):
    """Block until every process reaches this named point.  No-op for a
    world of one.  The fence of the multi-host checkpoint protocol:
    shard uploads all land before the chief commits the marker."""
    # hang-detection stamp BEFORE entering the fence (span.__enter__
    # stamps the phase first): a barrier whose peer died parks forever —
    # the watchdog then names this phase (fluid/watchdog.py; no-op stamp
    # when disarmed).  With FLAGS_trace_spans on, the span's wall_ns
    # entry stamp is the per-rank barrier-entry time tools/pod_trace.py
    # computes skew from — the rank entering LAST is the straggler.
    _m_collectives.inc(kind="barrier")
    with telemetry.span("barrier", phase="barrier:%s" % name, name=name):
        if process_count() <= 1:
            return
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def any_process(value):
    """Global OR of one host-side bool across processes (one tiny
    allgather; no-op world of one).  The preemption-stop consensus:
    ``train_from_dataset`` asks it at its consensus boundaries so a
    stop signal delivered to ONE process stops EVERY process at the
    SAME boundary — the survivors never park inside a collective whose
    peer already drained."""
    return consensus_flags(value)[0]


def consensus_flags(*values):
    """Element-wise global OR of several host-side bools in ONE
    allgather (no-op world of one) — the training loop's stop +
    rollback consensus share a single collective per consensus
    boundary.  Every process must call this at the same points with
    the same arity (a deterministic schedule), like any collective."""
    # collective-consensus boundary stamp (stamped in a world of one
    # too: the boundary exists either way, and tests/faultinject.py's
    # hang_at("consensus") parks single-process workers right here —
    # the span's entry wall stamp lands AFTER the hook, so a parked
    # rank shows up late exactly like a genuine straggler)
    _m_collectives.inc(kind="consensus")
    with telemetry.span("consensus", phase="consensus"):
        if process_count() <= 1:
            return tuple(bool(v) for v in values)
        from jax.experimental import multihost_utils
        gathered = multihost_utils.process_allgather(
            np.asarray([bool(v) for v in values]))
        return tuple(bool(b) for b in np.any(np.atleast_2d(gathered),
                                             axis=0))


def all_processes_equal(value, name="value"):
    """Assert a host scalar is identical on every process (config
    drift check for world-visible settings); returns the value."""
    if process_count() <= 1:
        return value
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(np.asarray(value))
    if not bool(np.all(gathered == gathered[0])):
        raise RuntimeError(
            "%s differs across processes: %r" % (name, gathered))
    return value
