"""The scheduler stack's layer DAG, as data.

This is the machine-readable form of the eight-layer diagram in
``docs/ARCHITECTURE.md`` (kept in sync by hand; the diagram is prose, this
is the contract the ``layer-contract`` lint rule enforces).  Layers are
listed top to bottom; a module may import modules of its own layer or any
layer *below* it, plus the shared leaf modules, plus any explicitly
documented extra edge.

Shared leaves (``SHARED``) are pure vocabulary/model modules with no
scheduler state — any layer may import them, and they may only import each
other:

* ``repro.kernels.layout``  — the declared solver-matrix column schema,
* ``repro.core.dvfs``       — the Eq. 1-4 power/time/energy model,
* ``repro.core.cluster``    — state-free result records + Algorithm-3 helper,
* ``repro.core.tasks``      — task-set synthesis,
* ``repro.core.jobs``       — trace/job synthesis on top of tasks,
* ``repro.core.obs``        — the scheduler's spans and per-call counters
  (every layer opens its spans and counts its work through it).

``EXTRA_EDGES`` documents the deliberate exceptions: the SSD-scan oracle in
``kernels/ref.py`` reuses the reference recurrence from ``models/ssm.py``
rather than duplicating it.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Optional, Tuple

#: Top-to-bottom layers of docs/ARCHITECTURE.md.  Lower index = higher
#: layer; importing a HIGHER layer (smaller index) is a violation.
LAYERS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("policies", ("repro.core.scheduling", "repro.core.online",
                  "repro.core.bounds")),
    ("faults", ("repro.core.faults",)),
    ("placement", ("repro.core.placement",)),
    ("machines", ("repro.core.machines",)),
    ("engine", ("repro.core.engine",)),
    ("solvers", ("repro.core.single_task", "repro.kernels.ref")),
    ("solver-throughput", ("repro.core.solver_cache", "repro.kernels.ops")),
    ("kernel", ("repro.kernels.dvfs_opt", "repro.kernels.flash_attention",
                "repro.kernels.ssd_scan")),
)

#: Shared leaf modules: importable from every layer, may only import each
#: other (checked).
SHARED: FrozenSet[str] = frozenset({
    "repro.kernels.layout",
    "repro.core.dvfs",
    "repro.core.cluster",
    "repro.core.tasks",
    "repro.core.jobs",
    "repro.core.obs",
})

#: Documented exceptions to the layer rule: importer -> allowed extra
#: targets (modules outside the DAG or above the importer).
EXTRA_EDGES: Dict[str, FrozenSet[str]] = {
    # The SSD oracle reuses the reference recurrence instead of forking it.
    "repro.kernels.ref": frozenset({"repro.models.ssm"}),
}

#: Module -> layer index (position in LAYERS).
RANK: Dict[str, int] = {
    mod: i for i, (_, mods) in enumerate(LAYERS) for mod in mods
}

#: Module -> layer name.
LAYER_OF: Dict[str, str] = {
    mod: name for name, mods in LAYERS for mod in mods
}


def rank_of(module: str) -> Optional[int]:
    """Layer index of ``module``, or None if it is not a ranked DAG node."""
    return RANK.get(module)


def in_dag(module: str) -> bool:
    """True if ``module`` participates in the layer contract at all."""
    return module in RANK or module in SHARED
