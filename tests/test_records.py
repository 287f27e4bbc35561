"""A call's records as columns (``cluster.Records``).

Every record path writes the same column store: the vector group commit
and the offline pinning as slices, the per-task rules, the orphan
re-placement and the bin-packing phase as rows, the θ-readjustment fills
and the fault truncations as writes at rows.  These tests pin

* the columns of every entry x {one class, two classes} x {vector,
  scalar} placement, and online x {pipelined, sync} x {no faults, a
  fault trace}, bit for bit against the records the object-per-record
  path of the scheduler made on the same inputs before the columns
  (``GOLDEN``: a SHA-256 of the columns and ``e_total``);
* that ``ScheduleResult.assignments``, the object view, holds exactly
  the columns' rows;
* that a fleet-sized call's kept result leaves the garbage collector
  nothing to track per record;
* the column store's growth.
"""

import dataclasses
import gc
import hashlib

import numpy as np
import pytest

from repro.core import cluster as cl, online, scheduling, tasks
from repro.core.faults import FaultTrace

KW = dict(l=4, theta=0.9, bound=False)
CLASSES = {"one": None, "two": ("tesla-t4", "v100-sxm2")}
FIELDS = tuple(f.name for f in dataclasses.fields(cl.Assignment))
DTYPES = {"task": np.int64, "pair": np.int64, "class_id": np.int64,
          "readjusted": bool, "failed": bool}


def case_ids():
    for classes in sorted(CLASSES):
        for placement in ("vector", "scalar"):
            yield f"offline-{classes}-{placement}"
            for mode in ("pipelined", "sync"):
                for faults in ("faults", "clean"):
                    yield f"online-{classes}-{placement}-{mode}-{faults}"


def run(case: str):
    entry, classes, placement, *rest = case.split("-")
    kw = dict(KW, classes=CLASSES[classes], placement=placement)
    if entry == "offline":
        return scheduling.schedule_offline(
            tasks.generate_offline(0.3, seed=1), **kw)
    mode, faults = rest
    ts = tasks.generate_online(0.1, 0.3, horizon=60, seed=2)
    trace = FaultTrace.sample(40, 70.0, mtbf=60.0, mttr=5.0, seed=7) \
        if faults == "faults" else None
    return online.schedule_online(ts, pipeline=(mode == "pipelined"),
                                  faults=trace, **kw)


def columns_of(assignments) -> dict:
    return {f: np.asarray([getattr(a, f) for a in assignments],
                          DTYPES.get(f, np.float64)) for f in FIELDS}


def digest(cols: dict) -> str:
    h = hashlib.sha256()
    for f in FIELDS:
        h.update(f.encode())
        h.update(np.ascontiguousarray(cols[f], DTYPES.get(f, np.float64))
                 .tobytes())
    return h.hexdigest()


#: (entry, classes, faults) -> (SHA-256 of the columns, e_total as
#: ``float.hex``), from the scheduler that built one Assignment object per
#: record, hashed as ``digest(columns_of(result.assignments))``.  The
#: placement rules and the pipelined and sync drivers make the same
#: records, so their cases share an entry.
GOLDEN = {
    "offline-one": (
        "c3a3606fb29d30c310508d802d9c600e7a0f2f710f40e9dc23ce8a6635d3d625",
        "0x1.4eae32c0c088bp+23"),
    "online-one-clean": (
        "d6ad3ed78342b79fdde3a6120a7dda453ebe4640dfac1170e68314ec1d65a722",
        "0x1.0b16c7581112ap+24"),
    "online-one-faults": (                      # 309 orphans, 36 degraded
        "e009bcd8ae75f3c13496a1cf44a8d5ffb342abae2e62f520cefa6e21ac0deaf5",
        "0x1.1ed012779d68fp+24"),
    "offline-two": (
        "1ec392ae57a4af74bb330ec471eff9eb1a849845743b5c984f48ce8b6e485a32",
        "0x1.2ba408ff6a47fp+23"),
    "online-two-clean": (
        "27b26bc130d8a67b12d27da022c50e325dc8e1cd5f410036dc43ecc7c389859b",
        "0x1.cff88430a311dp+23"),
    "online-two-faults": (                      # 440 orphans, 27 degraded
        "72a9f10cb3405b07bf951ee58787257a57ee2bb397040efcbdc6dca7f4429aac",
        "0x1.d7eb5fc00a3dfp+23"),
}


def golden(case: str):
    entry, classes, _, *rest = case.split("-")
    return GOLDEN["-".join([entry, classes] + rest[1:])]


@pytest.mark.parametrize("case", list(case_ids()))
def test_columns_match_the_object_records(case):
    r = run(case)
    rec = r.records
    cols = {f: getattr(rec, f)[:rec.n] for f in FIELDS}
    want_digest, want_e = golden(case)
    assert digest(cols) == want_digest
    assert r.e_total.hex() == want_e
    # the object view holds the columns' rows, built once
    view = r.assignments
    assert view is r.assignments
    assert len(view) == rec.n
    assert digest(columns_of(view)) == want_digest
    assert [dataclasses.astuple(a) for a in view] == \
        list(zip(*(cols[f].tolist() for f in FIELDS)))
    if case.endswith("-faults"):
        assert r.fault_stats["orphans"] > 0 and rec.failed[:rec.n].any()
        assert rec.n > len(set(rec.task[:rec.n].tolist()))


def test_a_kept_result_leaves_no_tracked_object_per_record():
    """A fleet-sized day on two classes: once its result is kept and the
    collector has run, the objects the collector tracks grew by a handful,
    not by one per record (nor per task's class order)."""
    ts = tasks.generate_online(0.4, 2.0, horizon=240, seed=5)
    kw = dict(KW, classes=CLASSES["two"])
    online.schedule_online(ts, **kw)            # compile, fill the cache
    gc.collect()
    before = len(gc.get_objects())
    r = online.schedule_online(ts, **kw)
    gc.collect()
    grown = len(gc.get_objects()) - before
    assert r.records.n == len(ts) > 2000
    assert grown < 200, grown


def test_the_store_grows_and_keeps_its_rows():
    rec = cl.Records(2)
    rows = [(t, 10 + t, 0.5 * t, 0.5 * t + 1.0, 1.0, 0.9, 0.8,
             100.0 + t, 100.0 + t, bool(t % 2), t % 3) for t in range(20)]
    for row in rows:
        assert rec.append(*row) == row[0]
    b = rec.add(3)
    assert b == 20 and len(rec) == 23 and rec.task.shape[0] >= 23
    rec.task[b:rec.n] = (20, 21, 22)
    got = rec.assignments()
    assert [dataclasses.astuple(a) for a in got[:20]] == \
        [row + (False,) for row in rows]
    assert [a.task for a in got[20:]] == [20, 21, 22]
    assert not any(a.readjusted or a.failed for a in got[20:])
    # the named setting columns are rows of the block, after growth too
    rec.energy[:rec.n] = np.arange(23.0)
    assert rec.setting[4, :23].tolist() == list(range(23))
    assert rec.v.base is rec.setting
