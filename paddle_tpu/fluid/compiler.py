"""CompiledProgram: multi-device data-parallel execution.

Reference contract: ``python/paddle/fluid/compiler.py:48`` CompiledProgram
``.with_data_parallel`` → C++ ParallelExecutor building a per-device SSA
graph with inserted NCCL allreduce handles (parallel_executor.cc:327,
multi_devices_graph_pass.cc).

TPU-native mechanism: there is no threaded SSA scheduler — the whole step is
ONE XLA computation partitioned by GSPMD over a ``jax.sharding.Mesh``.  The
feed batch is sharded on dim 0 across the 'dp' mesh axis, parameters/state
are replicated, and XLA inserts the gradient all-reduces over ICI during
SPMD partitioning — the compile-time equivalent of the reference's
AllReduceOpHandle graph rewrite (SURVEY.md §7 step 5).
"""

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import framework
from . import flags
from . import telemetry
from .executor import _CompiledProgramProxy, _DispatchPlan, global_scope

# shared with Executor._lookup_compiled: ONE executable-cache metric so
# hit rates aggregate across the single- and multi-device paths
_m_exec_cache = telemetry.counter(
    "executor_executable_cache_total",
    "compiled-executable cache lookups, by result")


class ReduceStrategy:
    AllReduce = 0
    Reduce = 1


class BuildStrategy:
    """User-visible knobs (details/build_strategy.h:36).  Fusion/memory knobs
    are accepted for parity; XLA performs the corresponding optimizations
    (op fusion, buffer sharing) during compilation, so most are no-ops.

    ``sync_batch_norm``: under GSPMD data parallelism the feed batch is ONE
    logical array, so plain batch_norm already normalises over the global
    batch (XLA inserts the cross-device reductions) — the knob is
    inherently on.  The explicit-collective transpiler path instead uses
    ``GradAllReduce(sync_batch_norm=True)`` → the sync_batch_norm op
    (ir.py sync_batch_norm_pass, reference ir/sync_batch_norm_pass.cc).
    ``fuse_all_reduce_ops``: GSPMD chooses collective layout itself; for
    the transpiler path see ``GradAllReduce(fuse_grad_size_mb=...)``."""

    ReduceStrategy = ReduceStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.gradient_scale_strategy = 0
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = True
        self.fuse_all_optimizer_ops = True
        self.memory_optimize = True
        self.enable_inplace = True
        self.num_trainers = 1
        self.trainer_id = 0
        self.nccl_comm_num = 1
        self.use_hierarchical_allreduce = False
        self.sync_batch_norm = False
        # ZeRO-1-style storage: keep params + optimizer accumulators
        # SHARDED on dim 0 over the dp axis between steps (1/N per-device
        # state bytes); GSPMD inserts the gathers around compute.  TPU
        # extension — no reference analogue.
        self.zero_shard_optimizer_state = False


class ExecutionStrategy:
    """details/execution_strategy.h — scheduling knobs; under whole-graph XLA
    compilation only num_iteration_per_drop_scope has a meaning (scope reuse
    is automatic), the rest are accepted for parity."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = False


class CompiledProgram(_CompiledProgramProxy):
    def __init__(self, program_or_graph):
        self._program = program_or_graph
        self._is_data_parallel = False
        self._places = None
        self._build_strategy = None
        self._exec_strategy = None
        self._loss_name = None
        self._cache = {}
        self._plans = {}

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        self._loss_name = loss_name
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._places = places
        return self

    @staticmethod
    def _zero_sharded_state(program, scope, ndev):
        """Names stored SHARDED over dp for ZeRO-1: parameters plus their
        same-shaped optimizer accumulators, when dim 0 divides across the
        mesh (the pipeline's stage-sharding heuristic, pipeline.py)."""
        if ndev < 2:
            return set()
        from .executor import param_names
        params = param_names(program)
        shapes = {}
        for v in program.list_vars():
            if getattr(v, "persistable", False):
                val = scope.find_var(v.name)   # shape only — no host copy
                if val is not None and hasattr(val, "shape"):
                    shapes[v.name] = tuple(val.shape)
        # state resolves to its param via the shared rule (structural
        # _opt_state_of link first, <param>_<suffix> names as fallback),
        # plus a shape match
        from .executor import resolve_state_param
        out = set()
        for n, sh in shapes.items():
            if not sh or sh[0] < ndev or sh[0] % ndev:
                continue
            if n in params:
                out.add(n)
                continue
            base = resolve_state_param(n, params, program)
            if base is not None and shapes.get(base) == sh:
                out.add(n)
        return out

    def _zero_sharded(self):
        return bool(getattr(self._build_strategy,
                            "zero_shard_optimizer_state", False))

    # -- execution (called from Executor.run) ------------------------------
    def _mesh(self, exe):
        if self._places:
            devices = self._places
        else:
            platform = exe._device.platform
            # deliberately GLOBAL (audited): the GSPMD mesh spans every
            # process's devices under jax.distributed — placement of
            # concrete arrays goes through local_devices elsewhere
            devices = [d for d in jax.devices() if d.platform == platform]
        from .mesh_utils import build_mesh
        from .executor import _model_parallel_axes
        extra = _model_parallel_axes(self._program)
        if extra:
            # model-parallel programs run over a (dp, mp/sp/ep...) mesh:
            # batch over dp, annotated weights over mp/ep, sequence over
            # sp; model axes TRAIL so they land on ICI-adjacent chips
            model = int(np.prod([d for _, d in extra]))
            if len(devices) % model:
                raise RuntimeError(
                    "model-parallel degrees %s do not divide %d devices"
                    % (dict(extra), len(devices)))
            return build_mesh(("dp",) + tuple(n for n, _ in extra),
                              (-1,) + tuple(d for _, d in extra),
                              devices=devices)
        return build_mesh(("dp",), devices=devices)

    def _run(self, exe, feed, fetch_list, scope, return_numpy):
        if not self._is_data_parallel:
            return exe._run(self._program, feed, fetch_list, scope,
                            return_numpy)
        program = self._program
        scope = scope or global_scope()
        if not feed and getattr(program, "_loader", None) is not None:
            # program-bound DataLoader under GSPMD dp: the shared
            # loader flow (executor._loader_fed_run) pulls, dispatches,
            # and hands the plan's feed shardings back so the producer
            # lands SUBSEQUENT batches already sharded across the mesh.
            # Dispatch through _run_resolved, never back through _run
            # (an empty pulled feed would re-enter this branch)
            return exe._loader_fed_run(
                program._loader, scope,
                lambda f: self._run_resolved(exe, f, fetch_list, scope,
                                             return_numpy),
                lambda f, k: self._run_window(exe, f, fetch_list, scope,
                                              k, False))
        return self._run_resolved(exe, feed, fetch_list, scope,
                                  return_numpy)

    def _run_resolved(self, exe, feed, fetch_list, scope, return_numpy):
        """Dispatch tail of ``_run`` once any loader pull has happened
        (mirrors Executor._run_resolved)."""
        program = self._program
        feed = feed or {}
        zero = self._zero_sharded()
        if flags.get_flag("dispatch_plan"):
            # same dispatch-plan hot path as Executor.run (executor.py):
            # steady state is one dict lookup + the jitted call
            pkey = exe._plan_key(program, feed, fetch_list)
            if pkey is not None:
                plan = exe._plan_get_or_build(
                    self._plans, pkey + (zero,), program,
                    lambda: self._lookup_compiled(exe, feed, fetch_list,
                                                  scope, zero)[0])
                return exe._run_plan(plan, scope, feed, return_numpy)
        exe._last_plan_hit = None   # legacy per-step-key path
        compiled, feed_vals = self._lookup_compiled(exe, feed, fetch_list,
                                                    scope, zero)
        feed_vals = compiled.globalize_feeds(feed_vals)
        return exe._dispatch(compiled, scope, feed_vals, return_numpy)

    def _run_window(self, exe, feed, fetch_list, scope, steps_per_run,
                    return_numpy):
        """Multi-step fused window over the data-parallel GSPMD step
        (Executor.run_window contract): feeds stacked [K, B, ...], batch
        dim sharded over 'dp' per inner step, the whole window ONE
        dispatch — the collective layout inside the scan body is exactly
        the K=1 step's (GSPMD partitions the body once)."""
        if not self._is_data_parallel:
            return exe._run_window(self._program, feed, fetch_list, scope,
                                   int(steps_per_run), return_numpy)
        program = self._program
        scope = scope or global_scope()
        feed = feed or {}
        K = int(steps_per_run)
        zero = self._zero_sharded()
        if flags.get_flag("dispatch_plan"):
            pkey = exe._plan_key(program, feed, fetch_list)
            if pkey is not None:
                plan = exe._plan_get_or_build(
                    self._plans, pkey + (zero, "__window__", K), program,
                    lambda: self._lookup_compiled(exe, feed, fetch_list,
                                                  scope, zero,
                                                  steps_per_run=K)[0])
                return exe._run_plan(plan, scope, feed, return_numpy)
        exe._last_plan_hit = None   # legacy per-step-key path
        compiled, feed_vals = self._lookup_compiled(exe, feed, fetch_list,
                                                    scope, zero,
                                                    steps_per_run=K)
        feed_vals = compiled.globalize_feeds(feed_vals)
        return exe._dispatch(compiled, scope, feed_vals, return_numpy)

    def _lookup_executable(self, exe, feed, fetch_list, scope,
                           steps_per_run=None):
        """(compiled block, coerced feeds) that ``_run`` / ``_run_window``
        dispatch — what ``Executor.compiled_hlo`` / ``compiled_cost`` /
        ``compiled_memory`` read when handed this CompiledProgram."""
        if not self._is_data_parallel:
            return exe._resolve_compiled(self._program, feed, fetch_list,
                                         scope, steps_per_run)
        zero = self._zero_sharded()
        return self._lookup_compiled(exe, feed, fetch_list, scope, zero,
                                     steps_per_run=steps_per_run)

    def _lookup_compiled(self, exe, feed, fetch_list, scope, zero,
                         steps_per_run=None):
        """Resolve (program, feed signature, fetches, zero) to the cached
        data-parallel executable (plus the coerced feed values, so the
        legacy path does not re-coerce), compiling on miss."""
        program = self._program
        feed = dict(feed or {})
        fetch_names = [v.name if isinstance(v, framework.Variable) else v
                       for v in (fetch_list or [])]
        feed_names = sorted(feed)
        block = program.global_block()
        from .executor import coerce_feed_value, _executable_key
        feed_vals = [coerce_feed_value(block, n, feed[n])
                     for n in feed_names]
        extra = (zero,) + (() if steps_per_run is None
                           else ("window", int(steps_per_run)))
        key = _executable_key(program, feed_names, feed_vals, fetch_names,
                              extra=extra)
        compiled = self._cache.get(key)
        if compiled is not None:
            _m_exec_cache.inc(result="hit")
        if compiled is None:
            _m_exec_cache.inc(result="miss")
            mesh = self._mesh(exe)
            repl = NamedSharding(mesh, P())
            shard0 = NamedSharding(mesh, P("dp"))
            sharded_state = frozenset(
                self._zero_sharded_state(program, scope, len(mesh.devices))
                if zero else ())
            compiled = exe._compile(program, feed_names,
                                    [v.shape for v in feed_vals], fetch_names,
                                    in_shardings=(
                                        "state-sharded", repl, shard0,
                                        sharded_state),
                                    steps_per_run=steps_per_run)
            self._cache[key] = compiled
        return compiled, feed_vals
