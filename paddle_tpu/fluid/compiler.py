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

from .executor import (_Target, _model_parallel_axes, param_names,
                       resolve_state_param)
from .mesh_utils import build_mesh


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


class CompiledProgram:
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

    # -- what Executor compiles for this object ----------------------------
    def _compile_spec(self):
        """All this class tells the executor (``Executor._target``): the
        Program to compile and, under ``with_data_parallel``, the key
        part, caches and shardings of its GSPMD step.  A plain
        CompiledProgram is its Program."""
        if not self._is_data_parallel:
            return _Target(self._program, (), None, None, None)
        return _Target(self._program, (self._zero_sharded(),), self._cache,
                       self._plans, self._in_shardings)

    def _in_shardings(self, device, scope):
        """``Executor._compile``'s ``in_shardings`` for the GSPMD step:
        feeds over 'dp', state replicated but for the ZeRO-1 names."""
        mesh = self._mesh(device)
        sharded_state = frozenset(
            self._zero_sharded_state(self._program, scope, len(mesh.devices))
            if self._zero_sharded() else ())
        return ("state-sharded", NamedSharding(mesh, P()),
                NamedSharding(mesh, P("dp")), sharded_state)

    def _mesh(self, device):
        if self._places:
            devices = self._places
        else:
            # deliberately GLOBAL (audited): the GSPMD mesh spans every
            # process's devices under jax.distributed — placement of
            # concrete arrays goes through local_devices elsewhere
            devices = [d for d in jax.devices()
                       if d.platform == device.platform]
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
