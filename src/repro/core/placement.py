"""The shared placement subsystem: pair selection over the ClusterEngine
columns for BOTH schedulers (paper §4.2 — the packing half of Algorithms
2, 5 and 6).

The offline batch packer (:func:`repro.core.scheduling.schedule_offline`)
and the online arrival-group simulator
(:func:`repro.core.online.schedule_online`) run the *same* placement rules:
order the tasks, try each task's machine classes min-energy-feasible first,
pick a pair of the class by the policy rule (worst fit / best fit / first
fit, with the EDL θ-readjustment shrinking a non-fitting task's window),
and fall back to a fresh pair of the task's primary class.  This module
owns that machinery once, parameterized by a :class:`PlacementContext`:

* **offline** is the degenerate "one group at ``t = 0``" case — the engine
  runs ``servers=False``, a fresh pair is a single standalone
  :meth:`~repro.core.engine.ClusterEngine.open_pair`, and every pair is
  always eligible;
* **online** places one arrival group per call at its slot time — the
  engine runs ``servers=True``, a fresh pair is a DRS power-on of ``l``
  pairs (:meth:`~repro.core.engine.ClusterEngine.acquire_pair`), and only
  pairs of powered-on servers are eligible.

Three placement paths per context, all bit-identical by construction:

* :meth:`PlacementContext.place_group_vector` — the worst-fit/SPT path
  (Algorithm 2/5 EDL and the plain worst-fit policy) over per-class
  *compact pools* (:class:`_GroupPools`) of the engine's ``mu``/``class_id``
  columns, by one of two rules.  The per-task rule takes an argmin over the
  class pool for each task; it places every multi-class group (class
  fallback is per task) and every single-class group below a crossover
  size (``_SMALL_GROUP``, which falls as the pool grows; a paper day's
  arrival groups hold ~3 tasks).  A larger single-class group takes a heap
  finisher keyed ``(mu, pair id)``, whose top is the argmin: offline the
  heap holds the whole pool and fresh pairs open in bulk after the loop;
  online it is seeded with the group's ``k`` smallest pool entries and
  each fresh power-on pushes its pairs.
* :meth:`PlacementContext.place_group_select` — the pooled first-fit
  (``"ff"``) / best-fit (``"bf"``) path (offline ``lpt-ff``/``edf-bf`` and
  the online Algorithm-6 first-fit), per-task probes vectorized over the
  class pools.
* :meth:`PlacementContext.place_group_scalar` — the per-task reference
  loop over the engine's own ``worst_fit``/``best_fit``/``first_fit``
  selectors; the bit-identity oracle the two paths above are pinned
  against (``tests/test_placement.py`` offline,
  ``tests/test_event_engine.py`` online).

The vectorized paths defer every engine write to one group commit
(:meth:`~repro.core.engine.ClusterEngine.book_assignments` +
:meth:`~repro.core.engine.ClusterEngine.sync_mu`) and write the group's
records into the call's column store (:class:`~repro.core.cluster.Records`)
as slices, their settings gathered from the config columns in one shot; only
fresh-server power-ons touch the engine live (they are DRS events).  The
per-task rules append rows to the same store.  θ-readjustment rows are
*not* solved here — a readjusted task occupies exactly its window, so the
rows are queued as :data:`PendingRow` and batch-priced after packing
(:func:`repro.core.scheduling.fill_readjusted`).

See docs/ARCHITECTURE.md (placement subsystem layer) and docs/EQUATIONS.md
for the full equation/algorithm -> code map.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import cluster as cl, machines, obs
from repro.core.engine import ClusterEngine
from repro.core.single_task import TaskConfig

_EPS = 1e-9

# The crossover between the two worst-fit rules of
# PlacementContext.place_group_vector for a single-class group: the
# per-task rule costs one argmin over the class pool per task, dearer as
# the pool grows; the heap finisher costs a seed build over the pool per
# group and a heap step per task.  A group of k tasks over a pool of n
# pairs takes the per-task rule while k * (1 + n / _SMALL_GROUP_POOL) <
# _SMALL_GROUP: _SMALL_GROUP is the crossover on an empty pool, and it
# halves at _SMALL_GROUP_POOL pairs.
# The values come from a TPU v5e host measurement that compared the
# per-task rule with the batched prefix rounds that then sat on the other
# side (benchmarks/placement_crossover.py): the per-task rule won up to 48
# tasks over 712 pairs and up to 16 over 13,864; the rule puts the
# crossover at 58.8 and 23.4.
_SMALL_GROUP = 64
_SMALL_GROUP_POOL = 8000

#: pending θ-readjustment row: (record_row, task_index, window, class_id)
PendingRow = Tuple[int, int, float, int]

#: offline algorithm name -> pair-selection rule
OFFLINE_RULES = {"edl": "wf", "edf-wf": "wf", "edf-bf": "bf", "lpt-ff": "ff"}


def add_record(records: cl.Records, task: int, pair: int, start: float,
               cfg: TaskConfig, duration: Optional[float] = None,
               readjusted: bool = False, class_id: int = 0) -> int:
    """Append a record at the task's configured setting; a readjusted one
    gets its finish pinned to ``start + duration`` and its DVFS fields
    filled in later by :func:`repro.core.scheduling.fill_readjusted`."""
    t = cfg.t_hat[task] if duration is None else duration
    return records.append(task, pair, float(start), float(start + t),
                          float(cfg.v[task]), float(cfg.fc[task]),
                          float(cfg.fm[task]), float(cfg.p_hat[task]),
                          float(cfg.e_hat[task]), readjusted, class_id)


def _settings(cfg: TaskConfig, idx=slice(None)) -> np.ndarray:
    """A class's record setting columns ``[v, fc, fm, p_hat, e_hat]`` of
    the tasks ``idx``."""
    return np.stack([np.asarray(cfg.v, np.float64)[idx],
                     np.asarray(cfg.fc, np.float64)[idx],
                     np.asarray(cfg.fm, np.float64)[idx],
                     np.asarray(cfg.p_hat, np.float64)[idx],
                     np.asarray(cfg.e_hat, np.float64)[idx]])


def precompute(cfgs: Sequence[TaskConfig], order_cls: np.ndarray) -> dict:
    """Per-run lookups for the vectorized paths: config columns as numpy
    arrays (batch gathers) and as plain lists (the per-task loops read
    per-task floats ~20x faster off a list than off a numpy scalar)."""
    t_hat = [np.asarray(c.t_hat) for c in cfgs]
    t_min = [np.asarray(c.t_min) for c in cfgs]
    n = t_hat[0].shape[0]
    # Record setting columns of every class side by side, class c's task g
    # at column c * n + g: one fancy index gathers a group's settings
    # whatever its classes.  ``cols[c]`` is class c's view of the block.
    setting = np.empty((len(cl.SETTING), len(cfgs) * n))
    for c, cfg in enumerate(cfgs):
        setting[:, c * n:(c + 1) * n] = _settings(cfg)
    return {
        "t_hat": t_hat,
        "t_hat_l": [a.tolist() for a in t_hat],
        "t_min_l": [a.tolist() for a in t_min],
        # a task's classes in preference order, as a tuple of ints (the
        # collector stops tracking such tuples at its first pass)
        "order_cols": list(zip(*order_cls.tolist())) if len(cfgs) > 1
        else None,
        "n": n,
        "setting": setting,
        "cols": [setting[:, c * n:(c + 1) * n] for c in range(len(cfgs))],
    }


class _GroupPools:
    """Per-class compact pools for one placement call — or, in persistent
    mode, carried across every call of a run.

    A pool is the pair-id-ascending snapshot of the eligible pairs of one
    class as ``[ids, mus, n]``, kept in sync for the rest of the call while
    the engine itself is only written at the group commit.

    **Persistent mode** (``PlacementContext(incremental=True)``, the
    pipelined online path): pools survive from one arrival group to the
    next instead of a per-group rebuild.  The placement paths write every
    ``mu`` they move into the pool and :meth:`acquire` splices a power-on's
    pairs in, so between groups only two things can leave a pool stale —

    * *power-off deletion*: servers the engine's DRS settle powered off
      (``ClusterEngine.drain_offs``) have their contiguous pair block cut
      out of the pool;
    * *epoch invalidation*: any fault transition (``fail_pairs`` /
      ``revive_pairs`` bump ``ClusterEngine.pool_epoch``) mutates pairs
      behind the pool's back — eligibility masks, mu truncations, orphan
      re-placements — so every pool is dropped and lazily rebuilt from the
      live engine.  Failures are rare events; correctness over cleverness.
    """

    __slots__ = ("eng", "t_now", "grain", "t_hat_l", "pools", "pid_col",
                 "start_col", "dur_col", "cls_col", "epoch")

    def __init__(self, ctx: "PlacementContext", t_now: float,
                 pid_col: np.ndarray, start_col: np.ndarray,
                 dur_col: np.ndarray, cls_col: np.ndarray):
        self.eng = ctx.eng
        self.t_now = t_now
        self.grain = ctx.grain
        self.t_hat_l = ctx.pre["t_hat_l"]
        self.pools: Dict[int, list] = {}
        self.pid_col = pid_col
        self.start_col = start_col
        self.dur_col = dur_col
        self.cls_col = cls_col
        self.epoch = 0

    def begin_group(self, t_now: float, pid_col: np.ndarray,
                    start_col: np.ndarray, dur_col: np.ndarray,
                    cls_col: np.ndarray):
        """Rebind the per-group output columns and reconcile the carried
        pools with everything the engine did since the last group."""
        self.t_now = t_now
        self.pid_col = pid_col
        self.start_col = start_col
        self.dur_col = dur_col
        self.cls_col = cls_col
        eng = self.eng
        if eng.pool_epoch != self.epoch:
            self.epoch = eng.pool_epoch
            self.pools.clear()
            eng.drain_offs()
            return
        offs = eng.drain_offs()
        if offs:
            self.apply_offs(offs)

    def apply_offs(self, sids):
        """Cut the powered-off servers' contiguous pair blocks out of their
        class pools.  A powered-on server always has its whole ``grain``
        block in the pool, so the whole batch is one keep-mask compaction
        per class — a per-``sid`` slice shift is O(offs * pool) and
        collapses on diurnal traces, where a falling edge powers off
        thousands of servers at once.  Order-preserving, so it commutes
        with the loop form."""
        grain = self.grain
        eng = self.eng
        multi = len(eng.classes) > 1
        if multi:
            by_class: Dict[int, list] = {}
            for sid in sids:
                by_class.setdefault(eng.server_class(sid), []).append(sid)
        else:
            by_class = {0: list(sids)}
        for c, csids in by_class.items():
            st = self.pools.get(c)
            if st is None:
                continue
            ids, mus, n = st
            live = ids[:n]
            lo_id = np.asarray(sorted(csids), dtype=np.int64) * grain
            lo = np.searchsorted(live, lo_id)
            hi = np.searchsorted(live, lo_id + grain)
            if not np.any(hi > lo):
                continue
            # Deleted-coverage mask over positions via a range-diff sweep.
            diff = np.zeros(n + 1, dtype=np.int64)
            np.add.at(diff, lo, 1)
            np.add.at(diff, hi, -1)
            keep = np.cumsum(diff[:n]) == 0
            m = int(keep.sum())
            if m == n:
                continue
            ids[:m] = live[keep]
            mus[:m] = mus[:n][keep]
            st[2] = m

    def pool(self, c: int):
        """Compact (pair-id ascending) snapshot of the eligible pairs of
        class c as ``[ids, mus, n]`` (capacity-grown append arrays).  Built
        lazily; pairs acquired later in the call are spliced in by
        :meth:`acquire`, which always creates the pool first — so the lazy
        snapshot can never miss a same-class pair."""
        st = self.pools.get(c)
        if st is None:
            ids = self.eng.pool_ids(c)
            st = self.pools[c] = [ids,
                                  self.eng.mu[ids].astype(np.float64,
                                                          copy=True),
                                  ids.size]
        return st

    def acquire(self, i: int, g: int, c: int):
        """Fresh-pair fallback: open a fresh pair of class ``c`` (offline a
        standalone pair, online a DRS power-on of ``grain = l`` pairs — a
        live engine event), splice the new pairs into the class pool, and
        assign the first one to task ``g`` at position ``i``.  Returns the
        first new pair's pool position and whether it was inserted
        mid-pool (positions from there on shifted by ``grain``)."""
        t_now = self.t_now
        grain = self.grain
        eng = self.eng
        # Snapshot the pool BEFORE firing the engine event: pool_ids reads
        # live engine state, so a pool built after the power-on would
        # already contain the fresh pairs the splice below adds.
        st = self.pool(c)
        pid = eng.acquire_pair(t_now, class_id=c) if eng.server_mode \
            else eng.open_pair(class_id=c)
        ids, mus, n = st
        if n == 0 or pid > ids[n - 1]:            # append (always offline)
            pos = n
            if n + grain > ids.shape[0]:          # grow capacity, amortized
                grow = max(n + grain, 2 * ids.shape[0])
                st[0] = ids = np.concatenate(
                    [ids, np.empty(grow - ids.shape[0], dtype=np.int64)])
                st[1] = mus = np.concatenate(
                    [mus, np.empty(grow - mus.shape[0])])
        else:
            # waking a lower-id server inserts mid-pool
            pos = int(np.searchsorted(ids[:n], pid))
            st[0] = ids = np.insert(ids[:n], pos,
                                    np.zeros(grain, dtype=np.int64))
            st[1] = mus = np.insert(mus[:n], pos, np.zeros(grain))
        th = self.t_hat_l[c][g]
        if grain == 1:                            # offline: one standalone pair
            ids[pos] = pid
        else:
            ids[pos: pos + grain] = pid + np.arange(grain)
            mus[pos + 1: pos + grain] = t_now
        st[2] = n + grain
        mus[pos] = t_now + th                     # a fresh pair is free *now*
        self.pid_col[i] = pid
        self.start_col[i] = t_now
        self.dur_col[i] = th
        self.cls_col[i] = c
        return pos, pos != n


class PlacementContext:
    """One scheduler run's placement state: the engine handle, the per-class
    Algorithm-1 configs and their precomputed column lookups, the policy
    knobs (θ, readjustment on/off) and the output sinks (the record store
    and the pending θ-readjustment rows).

    ``readjust`` enables the EDL θ-readjustment on the worst-fit rule; it
    only takes effect for ``theta < 1`` (at ``θ = 1`` the readjustment
    window ``max(θ·t_hat, t_min)`` equals ``t_hat`` and can never admit a
    task the plain fit test rejected).  The fresh-pair granularity follows
    the engine mode: a standalone pair offline, a server of ``l`` pairs
    online.
    """

    def __init__(self, eng: ClusterEngine, cfgs: Sequence[TaskConfig],
                 deadline: np.ndarray, *, theta: float = 1.0,
                 readjust: bool = False,
                 records: Optional[cl.Records] = None,
                 pending: Optional[List[PendingRow]] = None,
                 order_cls: Optional[np.ndarray] = None,
                 incremental: bool = False):
        self.eng = eng
        self.cfgs = list(cfgs)
        self.deadline = np.asarray(deadline, dtype=np.float64)
        self.theta = float(theta)
        self.readjust = bool(readjust) and self.theta < 1.0
        self.records = records if records is not None else cl.Records()
        self.pending = pending if pending is not None else []
        self.order_cls = order_cls if order_cls is not None \
            else machines.class_order(self.cfgs)
        self.primary = self.order_cls[0]
        self.grain = eng.l if eng.server_mode else 1
        self._pre = None
        # Incremental mode (the pipelined online scheduler): pools persist
        # across groups with delta reconciliation instead of a per-group
        # rebuild; the engine logs power-offs for the deletion deltas.
        self.incremental = bool(incremental)
        self._gp: Optional[_GroupPools] = None
        if self.incremental:
            eng.track_offs = True

    @property
    def pre(self) -> dict:
        """The :func:`precompute` column lookups, built on first use (the
        scalar reference path never touches them)."""
        if self._pre is None:
            with obs.span("placement.prepare"):
                self._pre = precompute(self.cfgs, self.order_cls)
        return self._pre

    @obs.spanned("placement.prepare")
    def update_tasks(self, idx):
        """Refresh the :attr:`pre` lookups for the tasks in ``idx`` (an
        index array, or a contiguous ``slice`` — what the pipelined driver
        passes for slot-sorted traces) after their config columns were
        filled in place (the pipelined config prefetch consumes Algorithm-1
        solutions chunk by chunk).  The numpy entries of ``pre`` alias the
        config arrays, so only the derived list mirrors and the stacked
        setting columns need resyncing — all mutated in place so aliases
        held by a persistent pool stay live."""
        if self._pre is None:
            # First chunk: the plain build snapshots current (chunk-filled)
            # values; unfilled tasks hold garbage until their own refresh.
            self._pre = precompute(self.cfgs, self.order_cls)
            return
        pre = self._pre
        for c, cfg in enumerate(self.cfgs):
            pre["cols"][c][:, idx] = _settings(cfg, idx)
            th = np.asarray(cfg.t_hat)[idx].tolist()
            tm = np.asarray(cfg.t_min)[idx].tolist()
            th_l = pre["t_hat_l"][c]
            tm_l = pre["t_min_l"][c]
            if isinstance(idx, slice):
                th_l[idx] = th
                tm_l[idx] = tm
            else:
                for j, i in enumerate(idx.tolist()):
                    th_l[i] = th[j]
                    tm_l[i] = tm[j]
        if pre["order_cols"] is not None:
            oc = list(zip(*self.order_cls[:, idx].tolist()))
            order_cols = pre["order_cols"]
            if isinstance(idx, slice):
                order_cols[idx] = oc
            else:
                for j, i in enumerate(idx.tolist()):
                    order_cols[i] = oc[j]

    def _group_pools(self, t_now: float, pid_col: np.ndarray,
                     start_col: np.ndarray, dur_col: np.ndarray,
                     cls_col: np.ndarray) -> _GroupPools:
        """The per-call pool state: a throwaway instance normally, the
        carried one (delta-reconciled) in incremental mode."""
        if not self.incremental:
            return _GroupPools(self, t_now, pid_col, start_col, dur_col,
                               cls_col)
        gp = self._gp
        if gp is None:
            gp = self._gp = _GroupPools(self, t_now, pid_col, start_col,
                                        dur_col, cls_col)
            gp.epoch = self.eng.pool_epoch
            self.eng.drain_offs()   # nothing existed to reconcile yet
        else:
            gp.begin_group(t_now, pid_col, start_col, dur_col, cls_col)
        return gp

    def acquire_fresh(self, t_now: float, class_id: int) -> int:
        """A fresh pair of ``class_id`` through the engine-mode-appropriate
        primitive: offline a standalone pair, online a DRS power-on."""
        if self.eng.server_mode:
            return self.eng.acquire_pair(t_now, class_id=class_id)
        return self.eng.open_pair(class_id=class_id)

    # -- group commit --------------------------------------------------------

    def _commit_group(self, gidx: np.ndarray, pid_col: np.ndarray,
                      start_col: np.ndarray, dur_col: np.ndarray,
                      readj_col: np.ndarray, cls_col: np.ndarray):
        """Commit one placed group to the engine in one shot (power-ons
        already wrote their pairs live; only assigned pairs moved, and for
        a pair assigned twice the chronologically last finish wins), then
        write the group's records."""
        k = gidx.shape[0]
        self.eng.book_assignments(pid_col, start_col, dur_col)
        _, last = np.unique(pid_col[::-1], return_index=True)
        last = k - 1 - last
        self.eng.sync_mu(pid_col[last], start_col[last] + dur_col[last])
        self._gather(gidx, pid_col, start_col, dur_col, readj_col, cls_col)

    def _gather(self, gidx: np.ndarray, pid_col: np.ndarray,
                start_col: np.ndarray, dur_col: np.ndarray,
                readj_col: np.ndarray, cls_col: np.ndarray):
        """Write the group's records as slices of the record columns, the
        settings gathered from the config columns by one fancy index."""
        pre = self.pre
        rec = self.records
        b = rec.add(gidx.shape[0])
        e = rec.n
        rec.task[b:e] = gidx
        rec.pair[b:e] = pid_col
        rec.class_id[b:e] = cls_col
        rec.start[b:e] = start_col
        np.add(start_col, dur_col, out=rec.finish[b:e])
        rec.readjusted[b:e] = readj_col
        rec.setting[:, b:e] = pre["setting"][
            :, gidx if len(self.cfgs) == 1 else cls_col * pre["n"] + gidx]

    # -- placement paths -----------------------------------------------------

    @obs.spanned("placement.pin")
    def pin_fresh(self, tids: np.ndarray):
        """Each task on its OWN fresh pair of its primary class at ``t = 0``
        (the offline deadline-prior phase: these tasks must start
        immediately), opened and committed in bulk."""
        tids = np.asarray(tids, dtype=np.int64)
        k = tids.shape[0]
        if k == 0:
            return
        obs.count("placement.pinned", k)
        cls = self.primary[tids].astype(np.int64, copy=True)
        t_hat = np.empty(k)
        for c in np.unique(cls):
            m = cls == c
            t_hat[m] = self.pre["t_hat"][int(c)][tids[m]]
        base = self.eng.open_pairs(cls)
        pids = base + np.arange(k, dtype=np.int64)
        starts = np.zeros(k)
        self.eng.book_assignments(pids, starts, t_hat)
        self.eng.sync_mu(pids, t_hat)
        self._gather(tids, pids, starts, t_hat, np.zeros(k, dtype=bool), cls)

    @obs.spanned("placement.prepare")
    def prepare_chunk(self, groups):
        """Hoist the per-group prologue of :meth:`place_group_vector` for a
        run of arrival groups (the pipelined driver's chunk): ONE stable
        lexsort replaces each group's stable deadline argsort (equal
        permutations — the group id is the primary key and lexsort keeps
        arrival order on deadline ties, exactly like the per-group
        ``kind="stable"`` argsort), and the task-column gathers vectorize
        across the whole chunk.  Returns one ``(gidx, prim, d)`` tuple per
        group, each bit-identical to the inline prologue."""
        sizes = [idx.shape[0] for _, idx in groups]
        cat = np.concatenate([idx for _, idx in groups])
        gid = np.repeat(np.arange(len(sizes)), sizes)
        d_cat = self.deadline[cat]
        order = np.lexsort((d_cat, gid))
        gidx = cat[order]
        d_s = d_cat[order]
        prim = self.primary[gidx]
        out = []
        off = 0
        for s in sizes:
            sl = slice(off, off + s)
            out.append((gidx[sl], prim[sl], d_s[sl]))
            off += s
        return out

    def place_group_vector(self, idx, order, t_now: float, prep=None):
        """Worst-fit/SPT (+ θ-readjustment) placement for one ordered group
        — Algorithm 2/5's pair rule — by one of two rules over the pools.

        A multi-class group, or a single-class group of ``k`` tasks over a
        pool of ``n`` pairs with ``k * (1 + n / _SMALL_GROUP_POOL) <
        _SMALL_GROUP``, takes the per-task rule: an argmin over the class
        pool for each task, with class fallback, θ-readjustment and the
        fresh-pair power-on.  A larger single-class group takes a heap
        finisher, which costs a heap step per task instead of an argmin
        over the pool (~59 tasks over a paper day's ~700 pairs, ~23 over
        14k; see ``_SMALL_GROUP``).  Bit-identical to
        :meth:`place_group_scalar` (rule ``"wf"``) by construction.

        ``prep`` injects the group's :meth:`prepare_chunk` tuple; ``idx``
        and ``order`` are ignored then (the tuple already IS the ordered
        group).
        """
        if prep is not None:
            gidx, prim, d = prep
            k = gidx.shape[0]
            if k == 0:
                return
        else:
            k = order.shape[0]
            if k == 0:
                return
            gidx = np.asarray(idx)[order]         # [k] task ids, EDF order
            prim = self.primary[gidx]             # [k] primary class per task
            d = self.deadline[gidx]
        pre = self.pre
        theta = self.theta
        readjust_on = self.readjust
        pending = self.pending
        t_hat_l = pre["t_hat_l"]
        t_min_l = pre["t_min_l"]
        order_cols = pre["order_cols"]
        grain = self.grain

        # Per-group record columns, filled by the rule; records and engine
        # state are committed once at the end.
        pid_col = np.empty(k, dtype=np.int64)
        start_col = np.empty(k)
        dur_col = np.empty(k)
        cls_col = prim.astype(np.int64, copy=True)
        readj_col = np.zeros(k, dtype=bool)
        base = self.records.n

        gp = self._group_pools(t_now, pid_col, start_col, dur_col, cls_col)
        pool = gp.pool

        def place_one(i: int):
            """The per-task rule over the pools (argmin over a pool's
            contiguous mu column is worst-fit with the identical
            lowest-pair-id tie-break)."""
            g = int(gidx[i])
            dd = d[i]
            for c in (order_cols[g] if order_cols is not None else (0,)):
                ids, mus, n = pool(c)
                if not n:
                    continue
                j = int(mus[:n].argmin())
                mu_j = mus[j]
                start = t_now if mu_j < t_now else float(mu_j)
                th = t_hat_l[c][g]
                if dd - start >= th - _EPS:
                    mus[j] = start + th
                    pid_col[i], start_col[i], dur_col[i], cls_col[i] = \
                        ids[j], start, th, c
                    return
                elif readjust_on:
                    t_theta = theta * th
                    t_mn = t_min_l[c][g]
                    if t_theta < t_mn:
                        t_theta = t_mn
                    window = dd - start
                    if window >= t_theta - _EPS:
                        mus[j] = start + window
                        pending.append((base + i, g, window, c))
                        pid_col[i], start_col[i], dur_col[i], cls_col[i] = \
                            ids[j], start, window, c
                        readj_col[i] = True
                        return
            gp.acquire(i, g, int(prim[i]))

        def finish_scalar():
            """The per-task rule for a single-class online group as a tight
            loop over a frontier heap keyed ``(mu, pair id)`` — exactly
            argmin's lowest-pair-id tie-break — whose entries carry their
            pool position.  The heap is seeded with the ``k`` smallest pool
            entries: every entry left out compares greater and keeps its
            ``mu`` until a task takes it, and each task takes at most one
            heap entry, so a seed entry is always left and the heap top is
            the pool's argmin for every task.  A power-on pushes its fresh
            pairs.  Per-task reads come off plain python lists and the
            record columns are written back in bulk."""
            gl = gidx.tolist()
            dl = d.tolist()
            th_l = t_hat_l[0]
            tm_l = t_min_l[0]
            pid_l, st_l, du_l, rj_l = [], [], [], []
            ids, mus, n = pool(0)
            m_live = mus[:n]
            if k < n:
                part = np.argpartition(m_live, k - 1)[:k]
                cp = np.flatnonzero(m_live <= m_live[part].max())
                cp = cp[np.lexsort((cp, m_live[cp]))][:k]
            else:
                cp = np.arange(n)
            heap = list(zip(m_live[cp].tolist(), ids[cp].tolist(),
                            cp.tolist()))
            heapq.heapify(heap)
            heappush = heapq.heappush
            heapreplace = heapq.heapreplace
            for j in range(k):
                g = gl[j]
                dd = dl[j]
                if heap:
                    mu_p, pid, p = heap[0]
                    start = t_now if mu_p < t_now else mu_p
                    th = th_l[g]
                    if dd - start >= th - _EPS:
                        heapreplace(heap, (start + th, pid, p))
                        mus[p] = start + th
                        pid_l.append(pid)
                        st_l.append(start)
                        du_l.append(th)
                        rj_l.append(False)
                        continue
                    if readjust_on:
                        t_theta = theta * th
                        t_mn = tm_l[g]
                        if t_theta < t_mn:
                            t_theta = t_mn
                        window = dd - start
                        if window >= t_theta - _EPS:
                            heapreplace(heap, (start + window, pid, p))
                            mus[p] = start + window
                            pending.append((base + j, g, window, 0))
                            pid_l.append(pid)
                            st_l.append(start)
                            du_l.append(window)
                            rj_l.append(True)
                            continue
                pos, mid = gp.acquire(j, g, 0)
                ids, mus, _ = gp.pools[0]
                if mid:
                    # positions past the insertion point shifted; the keys
                    # did not, so the list is still a heap
                    heap = [(m_, pi_, p_ + grain if p_ >= pos else p_)
                            for m_, pi_, p_ in heap]
                npid = int(ids[pos])
                heappush(heap, (float(mus[pos]), npid, pos))
                for jj in range(1, grain):
                    heappush(heap, (t_now, npid + jj, pos + jj))
                pid_l.append(pid_col[j])
                st_l.append(t_now)
                du_l.append(dur_col[j])
                rj_l.append(False)
            pid_col[:] = pid_l
            start_col[:] = st_l
            dur_col[:] = du_l
            readj_col[:] = rj_l

        def finish_offline():
            """The offline (single-class, ``grain == 1``) heap finisher: the
            per-task worst-fit rule as a frontier heap over plain python
            floats.

            With no power-on granule and no eligibility churn the WHOLE
            pool fits in the heap (a ``(mu, pair id)`` heap top IS argmin's
            lowest-pair-id tie-break, and every mutation is a
            ``heapreplace`` of the top), and fresh pairs are deferred to
            ONE bulk :meth:`~repro.core.engine.ClusterEngine.open_pairs` —
            offline pair ids are sequential, so they are known without
            touching the engine inside the loop.  Bit-identical to the
            per-task rule by construction: the list mirrors hold the exact
            float64 values of the pool columns."""
            eng = self.eng
            ids_a, mus_a, n = pool(0)
            gl = gidx.tolist()
            dl = d.tolist()
            th_l = t_hat_l[0]
            tm_l = t_min_l[0]
            pid_l, st_l, du_l, rj_l = [], [], [], []
            heap = list(zip(mus_a[:n].tolist(), ids_a[:n].tolist()))
            heapq.heapify(heap)
            heappush = heapq.heappush
            heapreplace = heapq.heapreplace
            pid_next = eng.n_pairs
            n_fresh = 0
            for j in range(k):
                g = gl[j]
                dd = dl[j]
                if heap:
                    mu_p, pid = heap[0]
                    start = t_now if mu_p < t_now else mu_p
                    th = th_l[g]
                    if dd - start >= th - _EPS:
                        heapreplace(heap, (start + th, pid))
                        pid_l.append(pid)
                        st_l.append(start)
                        du_l.append(th)
                        rj_l.append(False)
                        continue
                    if readjust_on:
                        t_theta = theta * th
                        t_mn = tm_l[g]
                        if t_theta < t_mn:
                            t_theta = t_mn
                        window = dd - start
                        if window >= t_theta - _EPS:
                            heapreplace(heap, (start + window, pid))
                            pending.append((base + j, g, window, 0))
                            pid_l.append(pid)
                            st_l.append(start)
                            du_l.append(window)
                            rj_l.append(True)
                            continue
                # fresh standalone pair: id known in advance (sequential),
                # opened in bulk after the loop; class 0 == the primary
                # cls_col already holds
                pid = pid_next + n_fresh
                n_fresh += 1
                th = th_l[g]
                heappush(heap, (t_now + th, pid))
                pid_l.append(pid)
                st_l.append(t_now)
                du_l.append(th)
                rj_l.append(False)
            if n_fresh:
                eng.open_pairs(np.zeros(n_fresh, dtype=np.int64))
            pid_col[:] = pid_l
            start_col[:] = st_l
            dur_col[:] = du_l
            readj_col[:] = rj_l

        if order_cols is not None or k * (_SMALL_GROUP_POOL + pool(0)[2]) \
                < _SMALL_GROUP * _SMALL_GROUP_POOL:
            for j in range(k):
                place_one(j)
        elif self.eng.server_mode:
            finish_scalar()
        else:
            finish_offline()
        obs.count("placement.scalar", k)

        self._commit_group(gidx, pid_col, start_col, dur_col, readj_col,
                           cls_col)

    def place_group_select(self, idx, order, t_now: float, rule: str):
        """Pooled first-fit (``"ff"``) / best-fit (``"bf"``) placement for
        one ordered group (offline ``lpt-ff``/``edf-bf``, online
        Algorithm-6 first-fit).

        The per-task probes become array ops over the per-class compact
        pools — id-ascending, so ``argmax(fit)`` is exactly the scalar
        ``first_fit`` tie-break and ``argmax`` over the fit-masked ``mu``
        column is exactly ``best_fit`` — with the engine written once at
        the group commit.  Bit-identical to :meth:`place_group_scalar` by
        construction.
        """
        k = order.shape[0]
        if k == 0:
            return
        obs.count("placement.scalar", k)
        pre = self.pre
        gidx = np.asarray(idx)[order]
        gl = gidx.tolist()
        dl = self.deadline[gidx].tolist()
        prim = self.primary[gidx]
        t_hat_l = pre["t_hat_l"]
        order_cols = pre["order_cols"]
        best = rule == "bf"

        pid_col = np.empty(k, dtype=np.int64)
        start_col = np.empty(k)
        dur_col = np.empty(k)
        cls_col = np.empty(k, dtype=np.int64)
        gp = self._group_pools(t_now, pid_col, start_col, dur_col, cls_col)
        pool = gp.pool

        for i in range(k):
            g = gl[i]
            dd = dl[i]
            placed = False
            for c in (order_cols[g] if order_cols is not None else (0,)):
                ids, mus, n = pool(c)
                if not n:
                    continue
                th = t_hat_l[c][g]
                m = mus[:n]
                starts = np.maximum(t_now, m)
                fit = dd - starts >= th - _EPS
                if best:
                    if not fit.any():
                        continue
                    j = int(np.argmax(np.where(fit, m, -np.inf)))
                else:
                    j = int(np.argmax(fit))
                    if not fit[j]:
                        continue
                start = float(starts[j])
                mus[j] = start + th
                pid_col[i] = ids[j]
                start_col[i] = start
                dur_col[i] = th
                cls_col[i] = c
                placed = True
                break
            if not placed:
                gp.acquire(i, g, int(prim[i]))
        self._commit_group(gidx, pid_col, start_col, dur_col,
                           np.zeros(k, dtype=bool), cls_col)

    def place_group_scalar(self, idx, order, t_now: float, rule: str):
        """The per-task reference loop over the engine's own selectors:
        class preference order, worst fit (``"wf"``, with θ-readjustment
        when the context enables it) / best fit (``"bf"``) / first fit
        (``"ff"``), and the fresh-pair fallback.  The bit-identity oracle
        for the vectorized paths."""
        eng = self.eng
        cfgs = self.cfgs
        deadline = self.deadline
        order_cls = self.order_cls
        theta = self.theta
        readjust_on = self.readjust
        records = self.records
        pending = self.pending
        obs.count("placement.scalar", len(order))
        for r in order:
            gidx = int(idx[int(r)])
            d = deadline[gidx]

            placed = False
            for c in order_cls[:, gidx]:
                c = int(c)
                cfg_c = cfgs[c]
                t_hat = float(cfg_c.t_hat[gidx])
                if rule == "wf":
                    pid = eng.worst_fit(class_id=c)  # SPT: pair free first
                    if pid < 0:
                        continue
                    start = max(t_now, float(eng.mu[pid]))
                    if d - start >= t_hat - _EPS:
                        eng.assign(pid, start, t_hat)
                        add_record(records, gidx, pid, start, cfg_c,
                                   class_id=c)
                        placed = True
                        break
                    elif readjust_on:
                        t_theta = max(theta * t_hat,
                                      float(cfg_c.t_min[gidx]))
                        window = d - start
                        if window >= t_theta - _EPS:
                            eng.assign(pid, start, window)
                            pending.append((records.n, gidx, window, c))
                            add_record(records, gidx, pid, start, cfg_c,
                                       duration=window, readjusted=True,
                                       class_id=c)
                            placed = True
                            break
                else:
                    pid = eng.best_fit(t_now, d, t_hat, class_id=c) \
                        if rule == "bf" \
                        else eng.first_fit(t_now, d, t_hat, class_id=c)
                    if pid >= 0:
                        start = max(t_now, float(eng.mu[pid]))
                        eng.assign(pid, start, t_hat)
                        add_record(records, gidx, pid, start, cfg_c,
                                   class_id=c)
                        placed = True
                        break
            if not placed:
                c = int(self.primary[gidx])
                cfg_c = cfgs[c]
                pid = self.acquire_fresh(t_now, c)
                start = max(t_now, float(eng.mu[pid]))
                eng.assign(pid, start, float(cfg_c.t_hat[gidx]))
                add_record(records, gidx, pid, start, cfg_c, class_id=c)

    @obs.spanned("placement.group")
    def place_orphans(self, tids: np.ndarray, t_now: float, rule: str,
                      degrade=None) -> Tuple[int, int]:
        """Deadline-aware re-placement of tasks orphaned by a pair failure
        (the fault-recovery half of :mod:`repro.core.faults`).

        One scalar loop shared verbatim by the scalar and vector placement
        modes — failures are rare events, so bit-identity between the modes
        under injection comes for free instead of by a second batched
        implementation.  Policy, in EDF order per orphan:

        * try the classes in the task's preference order with the normal
          pair rule (``"wf"`` worst fit for EDL, ``"ff"`` first fit for the
          bin baseline); a fit at the optimal length is placed like any
          arrival;
        * EDL only: when the worst-fit pair cannot host the optimal length,
          shrink to the remaining window ``d - start`` down to the class's
          ``t_min`` floor and queue the boundary re-solve on the shared
          deferred ``readjust_batch`` dispatch.  θ is deliberately ignored
          here — recovery prefers a deadline met at higher speed over a
          counted violation;
        * otherwise fall back to a fresh pair of the primary class; if even
          a fresh pair cannot meet the deadline, the *graceful degradation*
          step books the task anyway — at the ``degrade`` callback's
          max-speed setting (EDL) or the configured setting (bin) — so the
          miss is counted as a violation and a failure trace can never
          crash a run.

        Returns ``(n_restarted, n_degraded)``."""
        tids = np.asarray(tids, dtype=np.int64)
        if tids.size == 0:
            return 0, 0
        obs.count("placement.scalar", tids.size)
        eng = self.eng
        cfgs = self.cfgs
        deadline = self.deadline
        records = self.records
        pending = self.pending
        n_degraded = 0
        order = np.argsort(deadline[tids], kind="stable")     # EDF
        for g in tids[order].tolist():
            d = float(deadline[g])
            placed = False
            for c in self.order_cls[:, g]:
                c = int(c)
                cfg_c = cfgs[c]
                t_hat = float(cfg_c.t_hat[g])
                if rule == "wf":
                    pid = eng.worst_fit(class_id=c)
                    if pid < 0:
                        continue
                    start = max(t_now, float(eng.mu[pid]))
                    window = d - start
                    if window >= t_hat - _EPS:
                        eng.assign(pid, start, t_hat)
                        add_record(records, g, pid, start, cfg_c,
                                   class_id=c)
                        placed = True
                        break
                    if window >= float(cfg_c.t_min[g]) - _EPS:
                        eng.assign(pid, start, window)
                        pending.append((records.n, g, window, c))
                        add_record(records, g, pid, start, cfg_c,
                                   duration=window, readjusted=True,
                                   class_id=c)
                        placed = True
                        break
                else:
                    pid = eng.first_fit(t_now, d, t_hat, class_id=c)
                    if pid >= 0:
                        start = max(t_now, float(eng.mu[pid]))
                        eng.assign(pid, start, t_hat)
                        add_record(records, g, pid, start, cfg_c,
                                   class_id=c)
                        placed = True
                        break
            if placed:
                continue
            c = int(self.primary[g])
            cfg_c = cfgs[c]
            t_hat = float(cfg_c.t_hat[g])
            pid = self.acquire_fresh(t_now, c)
            start = max(t_now, float(eng.mu[pid]))            # == t_now
            window = d - start
            if window < t_hat - _EPS:
                if rule == "wf" and window >= float(cfg_c.t_min[g]) - _EPS:
                    eng.assign(pid, start, window)
                    pending.append((records.n, g, window, c))
                    add_record(records, g, pid, start, cfg_c,
                               duration=window, readjusted=True, class_id=c)
                    continue
                n_degraded += 1
                if rule == "wf" and degrade is not None:
                    v, fc, fm, t_run, p = degrade(g, c)
                    eng.assign(pid, start, t_run)
                    records.append(g, pid, start, start + t_run, v, fc, fm,
                                   p, p * t_run, class_id=c)
                    continue
            eng.assign(pid, start, t_hat)
            add_record(records, g, pid, start, cfg_c, class_id=c)
        return int(tids.size), n_degraded

    def binpack_offline_util(self, idx, order, t_now: float):
        """Algorithm 6, lines 1-7 (the online baseline's offline phase):
        worst-fit on task *utilization*, cap at 1.0.

        The *optimal task utilization* is ``u_hat = t_hat / (d - a)``; the
        worst-fit heuristic sends each task to the pair with the lowest
        current utilization (among pairs of the candidate class), opening a
        fresh pair of the task's primary class when no candidate fits.
        """
        eng = self.eng
        cfgs = self.cfgs
        deadline = self.deadline
        util = np.zeros(0)
        obs.count("placement.scalar", len(order))

        def grow():
            nonlocal util
            if util.shape[0] < eng.n_pairs:
                util = np.concatenate(
                    [util, np.zeros(eng.n_pairs - util.shape[0])])

        for r in order:
            gidx = int(idx[int(r)])
            d = deadline[gidx]
            grow()
            placed = False
            for c in self.order_cls[:, gidx]:
                c = int(c)
                cfg_c = cfgs[c]
                t_hat = float(cfg_c.t_hat[gidx])
                u_hat = t_hat / max(d - t_now, _EPS)
                on = eng.eligible_mask(class_id=c)
                if on is None:
                    on = np.ones(eng.n_pairs, dtype=bool)
                if not on.any():
                    continue
                pid = int(np.argmin(np.where(on, util[: eng.n_pairs],
                                             np.inf)))
                start = max(t_now, float(eng.mu[pid]))
                if util[pid] + u_hat > 1.0 + _EPS or d - start < t_hat - _EPS:
                    continue
                eng.assign(pid, start, t_hat)
                util[pid] += u_hat
                add_record(self.records, gidx, pid, start, cfg_c, class_id=c)
                placed = True
                break
            if not placed:
                c = int(self.primary[gidx])
                cfg_c = cfgs[c]
                t_hat = float(cfg_c.t_hat[gidx])
                u_hat = t_hat / max(d - t_now, _EPS)
                pid = self.acquire_fresh(t_now, c)
                grow()
                start = max(t_now, float(eng.mu[pid]))
                eng.assign(pid, start, t_hat)
                util[pid] += u_hat
                add_record(self.records, gidx, pid, start, cfg_c, class_id=c)
