"""The paper's contribution: DVFS power/performance models, the single-task
optimum, and the EDL theta-readjustment schedulers (offline + online) over a
heterogeneous cluster of machine classes, plus the accelerator-job adapter
that feeds roofline-derived LM jobs into the same algorithms.

Architecture (top to bottom)::

    policies            scheduling.schedule_offline / online.schedule_online
                        (Algorithms 1-6: ordering, arrival grouping, result
                        assembly incl. the bounds.theoretical_bound e_bound
                        column; two thin drivers over one placement core)
        |
    placement           placement.PlacementContext - THE pair-selection
                        subsystem (per-class compact pools, per-task
                        worst fit or a heap finisher + theta-rows, pooled
                        first/best fit, per-task reference loop; offline ==
                        the degenerate one-group-at-t=0 case)
        |
    machine classes     machines.MachineClass / REGISTRY - per-class task
                        constants + scaling box; configure_classes runs
                        Algorithm 1 on every class (one reference class ==
                        the homogeneous paper setup, bit-for-bit)
        |
    ClusterEngine       engine.ClusterEngine - ONE vectorized pair/server
                        state machine (numpy struct-of-arrays with a per-pair
                        class_id column, DRS sweeps, class-restricted
                        worst/best/first-fit selectors, per-class Eq. 6/7
                        finalizer)
        |
    DVFS solvers        single_task.configure_tasks / readjust_batch
                        (Algorithm 1; batched, padded to pow-2 shapes)
        |
    solve dedup/cache   solver_cache.solve_rows - unique-row dedup + the
                        process-wide LRU solve cache (bit-transparent;
                        dedup=True default on every solver entry point);
                        kernels/ops.dvfs_solve_matrix shards miss batches
                        across local devices
        |
    Pallas kernel       kernels/dvfs_opt.dvfs_solve_kernel - the use_kernel
                        fast path: one [n, 16] task matrix per dispatch
                        (per-row interval bounds -> all classes in one call),
                        hierarchical G0 -> G1 frequency sweeps in VMEM
                        (incl. the theta-readjustment case)

See docs/ARCHITECTURE.md for the full picture and docs/EQUATIONS.md for the
equation/algorithm -> code map.
"""

from repro.core import (bounds, cluster, dvfs, engine, jobs, machines,
                        online, placement, scheduling, single_task,
                        solver_cache, tasks)
from repro.core.bounds import theoretical_bound
from repro.core.dvfs import DvfsParams, ScalingInterval, NARROW, WIDE
from repro.core.engine import ClusterEngine
from repro.core.machines import REGISTRY, MachineClass
from repro.core.online import schedule_online
from repro.core.scheduling import schedule_offline
from repro.core.single_task import configure_tasks, solve_unconstrained, solve_with_deadline
from repro.core.tasks import TaskSet, app_library, generate_offline, generate_online

__all__ = [
    "DvfsParams", "ScalingInterval", "NARROW", "WIDE", "TaskSet",
    "ClusterEngine", "MachineClass", "REGISTRY",
    "app_library", "generate_offline", "generate_online",
    "configure_tasks", "solve_unconstrained", "solve_with_deadline",
    "schedule_offline", "schedule_online", "theoretical_bound",
    "bounds", "cluster", "dvfs", "engine", "jobs", "machines", "online",
    "placement", "scheduling", "single_task", "solver_cache", "tasks",
]
