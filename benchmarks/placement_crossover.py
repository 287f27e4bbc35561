"""Per-group cost of the two worst-fit rules of
``PlacementContext.place_group_vector``: the batched rounds against the
per-task scalar rule, for groups of 1 to 256 tasks, over a warm
incremental pool taken at the busiest slot of a real day.

    PYTHONPATH=src python -m benchmarks.placement_crossover [--reps 41]

Each day (a paper day and the 100k-task uniform day) runs through
``schedule_online`` (pipelined, vector placement, the paper's ``l = 4``,
``theta = 0.9``) up to the slot whose pool holds the most eligible pairs;
there the placement context is copied, and each group size is placed on
fresh copies of it, both rules in turn.  One JSON line per day and group
size: the pool's eligible pairs, and the median wall time of a group under
each rule, in milliseconds.  Times are the host's: run it on the machine
whose host decides the crossover (``placement._SMALL_GROUP`` and
``_SMALL_GROUP_POOL``).
"""

from __future__ import annotations

import argparse
import copy
import json
import statistics
import time

import numpy as np

from repro.core import cluster as cl, machines, online, placement, tasks

SIZES = (1, 2, 4, 8, 16, 24, 32, 48, 64, 128, 256)
KW = dict(l=4, theta=0.9, bound=False)
# values of placement._SMALL_GROUP that force each rule on every group
RULES = (("batched", 0), ("scalar", 1 << 40))


def days(seed: int):
    return {"paper-day": tasks.generate_online(0.4, 1.6, seed=seed),
            "uniform-100k": tasks.generate_trace(100_000, "uniform",
                                                 seed=seed)}


def run_day(ts, cfgs, on_group):
    """One pipelined day with ``on_group(ctx, t_now)`` called before each
    arrival group is placed.  The configs are injected whole, so a copy
    taken mid-day can place any later task."""
    orig = placement.PlacementContext.place_group_vector

    def hooked(self, idx, order, t_now, prep=None):
        on_group(self, t_now)
        return orig(self, idx, order, t_now, prep=prep)

    placement.PlacementContext.place_group_vector = hooked
    try:
        online.schedule_online(ts, cfgs=cfgs, **KW)
    finally:
        placement.PlacementContext.place_group_vector = orig


def shared(ctx):
    """The read-only objects a copy may share: config lookups, deadlines."""
    keep = [ctx.pre, ctx.cfgs, ctx.deadline, ctx.order_cls, ctx.primary]
    for v in ctx.pre.values():
        keep.append(v)
        if isinstance(v, list):
            keep.extend(v)
    return {id(x): x for x in keep}


def snapshot(ctx):
    """A copy of the context without its records."""
    held = ctx.assignments, ctx.pending
    ctx.assignments, ctx.pending = [], []
    try:
        return copy.deepcopy(ctx, shared(ctx))
    finally:
        ctx.assignments, ctx.pending = held


def time_group(snap, idx, t_now):
    ctx = copy.deepcopy(snap, shared(snap))
    order = np.argsort(ctx.deadline[idx], kind="stable")
    t0 = time.perf_counter()
    ctx.place_group_vector(idx, order, t_now)
    return time.perf_counter() - t0


def sweep(name, ts, reps):
    cfgs = online.online_configs(ts, machines.resolve_classes(
        None, p_idle=cl.P_IDLE, delta_on=cl.DELTA_ON))
    pool_at = {}
    run_day(ts, cfgs, lambda ctx, t: pool_at.__setitem__(
        t, ctx.eng.pool_ids(0).size))
    t_cut = max(pool_at, key=pool_at.get)
    # Reach the slot as the batched rule does, merging the carried stream
    # every group, so neither rule inherits the other's deferred work.
    got = []
    placement._SMALL_GROUP = RULES[0][1]
    run_day(ts, cfgs, lambda ctx, t: got.append(snapshot(ctx))
            if t == t_cut and not got else None)
    slots = online.arrival_slots(ts)
    later = np.flatnonzero(slots > t_cut)
    later = later[np.argsort(slots[later], kind="stable")]
    for k in SIZES:
        ms = {rule: [] for rule, _ in RULES}
        for r in range(reps):
            for rule, small_group in (RULES if r % 2 else RULES[::-1]):
                placement._SMALL_GROUP = small_group
                ms[rule].append(1e3 * time_group(got[0], later[:k], t_cut))
        print(json.dumps({
            "day": name, "slot": t_cut, "pool_pairs": pool_at[t_cut],
            "k": k, "reps": reps,
            **{f"{rule}_ms": statistics.median(v) for rule, v in ms.items()}
        }), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=41)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    keep = placement._SMALL_GROUP
    try:
        for name, ts in days(args.seed).items():
            sweep(name, ts, args.reps)
    finally:
        placement._SMALL_GROUP = keep


if __name__ == "__main__":
    main()
