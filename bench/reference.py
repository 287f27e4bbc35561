"""The plain reference that decides ``correct``.

It imports nothing of the program.  From the task set the benchmark drew
and the deployment in the configuration file, it judges the schedule the
timed path returned, record by record:

* ``missing``   - tasks without exactly one live record;
* ``overlap``   - records that start before their arrival slot or before
  the previous record on the same pair has finished;
* ``late``      - records that finish past the deadline of a task the
  reference finds feasible on the record's class;
* ``violations_gap`` - the program's reported violation count against the
  reference's count;
* ``off_box``   - records whose setting lies outside the class's scaling
  box or above the voltage curve;
* ``model_gap`` - the widest relative gap between a record's power, run
  time and energy and the paper's model (Eq. 1, 2, 4) at the record's own
  setting;
* ``solve_gap`` - the widest relative gap between a sampled record's
  energy and the least energy the reference finds for the same task on the
  same class under the deadline (Sec. 4.1): the Algorithm-1 optimum for
  the window from the arrival slot to the deadline where that optimum fits
  between the record's start and the deadline, else the optimum for that
  shorter window (theta-readjustment, Algorithm 2 lines 16-19);
* ``account_gap`` - the relative gap between the program's ``e_total`` and
  Eq. 7 rebuilt from the records: run energy, idle energy of the servers
  as dynamic resource sleep (online) or Algorithm 3 (offline) powers them,
  and turn-on overhead;
* ``run_time_gap`` - the widest relative gap between a record's run time
  and the one Algorithm 1 and EDL give it from its start: the optimum's
  where that fits before the deadline, else the window left;
* ``placement_errors`` - records placed where EDL would not place them,
  replayed in EDL's order (:func:`replay`): a pair other than the one free
  first, a fresh pair where a pair in use would take the task, a start
  later than the pair frees up, a window below the readjustment floor.

``solve_gap``, ``run_time_gap`` and ``placement_errors`` see a schedule
that is valid but worse: a setting off the optimum, a fresh pair where
one was free, a readjustment skipped.
"""

from __future__ import annotations

import math

import numpy as np

G1_A, G1_B, G1_C = 0.5, 2.0, 0.5      # g1(V) = sqrt((V - A) / B) + C
FIELDS = ("p0", "gamma", "c", "big_d", "delta", "t0")
BOX = ("v_min", "v_max", "fc_min", "fm_min", "fm_max")

#: Each number compared and its limit; the readings each limit was set
#: from are in PERF.md.
LIMITS = {
    "missing": 0,
    "overlap": 0,
    "late": 0,
    "violations_gap": 0,
    "off_box": 0,
    "model_gap": 1e-4,
    "solve_gap": 1e-3,
    "account_gap": 3e-6,
    "run_time_gap": 1e-2,
    "placement_errors": 0,
}

#: Placement decisions within this share of the reference's run times or
#: energies may go either way (``replay``): a run time within the limit of
#: ``run_time_gap`` is a sound one.
BAND = LIMITS["run_time_gap"]

# Rounding allowances inside the exact checks: times are f32 solver values
# carried in f64, so a finish may exceed its deadline by the f32 residual.
T_ABS = 1e-6
T_REL = 1e-9


def g1(v):
    return np.sqrt(np.maximum(v - G1_A, 0.0) / G1_B) + G1_C


def g1_inv(fc):
    return G1_B * np.square(np.maximum(fc - G1_C, 0.0)) + G1_A


def power(p: dict, v, fc, fm):
    """Eq. 1."""
    return p["p0"] + p["gamma"] * fm + p["c"] * v * v * fc


def exec_time(p: dict, fc, fm):
    """Eq. 2."""
    return p["big_d"] * (p["delta"] / fc + (1.0 - p["delta"]) / fm) + p["t0"]


def adapt(task: dict, cls: dict) -> dict:
    """A class's model constants for the tasks, from the reference fit:
    times divided by ``speed``; power scaled by ``power_scale`` and, where
    given, re-split into static / memory / core shares."""
    p0, gamma, c = (np.asarray(task[f], np.float64) for f in ("p0", "gamma",
                                                               "c"))
    scale = cls.get("power_scale", 1.0)
    if cls.get("p0_frac") is not None:
        p_star = (p0 + gamma + c) * scale
        p0 = p_star * cls["p0_frac"]
        gamma = p_star * cls["gamma_frac"]
        c = p_star - p0 - gamma
    else:
        p0, gamma, c = p0 * scale, gamma * scale, c * scale
    speed = cls.get("speed", 1.0)
    return dict(p0=p0, gamma=gamma, c=c,
                big_d=np.asarray(task["big_d"], np.float64) / speed,
                delta=np.asarray(task["delta"], np.float64),
                t0=np.asarray(task["t0"], np.float64) / speed)


def class_box(cls: dict, run_box: dict) -> dict:
    return dict(cls["interval"] if cls.get("interval") else run_box)


def min_time(p: dict, box: dict):
    return exec_time(p, g1(np.float64(box["v_max"])), box["fm_max"])


# ---------------------------------------------------------------------------
# Algorithm 1, solved plainly.
# ---------------------------------------------------------------------------

def _energy_at_fc(p: dict, window, box: dict, fc, xp):
    """Least energy with ``t <= window`` at core frequency ``fc`` (rows x
    grid).  Power rises with V, so V is the least that sustains fc; for a
    fixed (V, fc) energy is convex in fm, so fm is the closed-form optimum
    clipped to the box and to the time budget."""
    v = xp.maximum(box["v_min"], G1_B * xp.square(
        xp.maximum(fc - G1_C, 0.0)) + G1_A)
    a = p["p0"] + p["c"] * v * v * fc
    b = p["t0"] + p["big_d"] * p["delta"] / fc
    k = p["big_d"] * (1.0 - p["delta"])
    slack = window - b
    fm_unc = xp.where(p["gamma"] > 0,
                      xp.sqrt(a * k / (p["gamma"] * b)), box["fm_max"])
    fm_req = xp.where(k > 0, k / slack, 0.0)
    ok = (slack > 0) | ((k <= 0) & (slack >= 0))
    lo = xp.maximum(box["fm_min"], fm_req)
    ok &= lo <= box["fm_max"]
    fm = xp.clip(fm_unc, lo, box["fm_max"])
    e = (a + p["gamma"] * fm) * (b + k / fm)
    return xp.where(ok, e, xp.inf), v, fm


def solve(p: dict, window, box: dict, dtype=np.float64, xp=np,
          grid: int = 129, rounds: int = 5):
    """Least energy over the scaling box with ``t <= window``, per row.

    A grid over fc, then ``rounds`` finer grids around the best point.
    Every step is computed in ``dtype`` with the array module ``xp`` (the
    control runs this in bfloat16 with ``jax.numpy``).  Returns
    ``(v, fc, fm, t, p, e, feasible)``; a row whose window is below its
    fastest time gets the fastest setting, flagged infeasible."""
    def col(x, n=None):
        x = np.asarray(x, np.float64)
        if n is not None:
            x = np.broadcast_to(x, (n,))
        return xp.asarray(x).astype(dtype)[:, None]

    pp = {f: col(p[f]) for f in FIELDS}
    n = pp["p0"].shape[0]
    bx = {f: col(box[f], n) for f in BOX}
    w = col(window, n)
    fc_max = col(g1(np.broadcast_to(np.asarray(box["v_max"], np.float64),
                                    (n,))))
    lo, hi = bx["fc_min"], fc_max
    frac = xp.asarray(np.linspace(0.0, 1.0, grid)).astype(dtype)[None, :]
    with np.errstate(all="ignore"):
        for _ in range(rounds + 1):
            fc = lo + (hi - lo) * frac
            e, _, _ = _energy_at_fc(pp, w, bx, fc, xp)
            best = xp.argmin(e, axis=1)[:, None]
            step = (hi - lo) / (grid - 1)
            centre = xp.take_along_axis(fc, best, axis=1)
            lo = xp.maximum(bx["fc_min"], centre - step)
            hi = xp.minimum(fc_max, centre + step)
        e, v, fm = _energy_at_fc(pp, w, bx, centre, xp)
        feasible = xp.isfinite(e)
        v = xp.where(feasible, v, bx["v_max"])
        fc = xp.where(feasible, centre, fc_max)
        fm = xp.where(feasible, fm, bx["fm_max"])
        t = exec_time(pp, fc, fm)
        pw = power(pp, v, fc, fm)
        e = pw * t
    return tuple(x[:, 0] for x in (v, fc, fm, t, pw, e, feasible))


def solve_blocks(p: dict, window, box: dict, block: int = 8192):
    """:func:`solve` in float64 over blocks of rows, so that the grid's
    temporaries stay small for a large task set."""
    n = np.shape(p["p0"])[0]
    window = np.broadcast_to(np.asarray(window, np.float64), (n,))
    box = {f: np.broadcast_to(np.asarray(box[f], np.float64), (n,))
           for f in BOX}
    parts = [solve({f: p[f][a:a + block] for f in FIELDS},
                   window[a:a + block], {f: box[f][a:a + block] for f in BOX})
             for a in range(0, n, block)]
    return tuple(np.concatenate(x) for x in zip(*parts))


def algorithm1(task: dict, deploy: dict, window) -> list:
    """Algorithm 1 for every task on every class (Sec. 4.1): per class the
    adapted constants ``p``, the box, and the configuration: ``t_hat`` and
    ``e_hat`` (the unconstrained optimum where it fits the window, else the
    optimum on the deadline boundary, else the fastest setting),
    ``t_unc`` (the unconstrained optimum's run time), ``t_min`` and
    ``feasible``."""
    out = []
    n = task["arrival"].shape[0]
    for cls in deploy["class_model"]:
        p = adapt({f: task[f] for f in FIELDS}, cls)
        box = class_box(cls, deploy["interval"])
        t_min = min_time(p, box)
        feasible = window >= t_min - 1e-6
        unc = solve_blocks(p, np.full(n, np.inf), box)
        con = solve_blocks(p, window, box)
        dp = unc[3] > window + 1e-6
        t_hat = np.where(dp & feasible, window, con[3])
        out.append(dict(p=p, box=box, t_min=t_min, feasible=feasible,
                        t_hat=t_hat, t_unc=unc[3], e_hat=con[4] * t_hat))
    return out


def class_order(cfgs: list) -> np.ndarray:
    """Per task, its classes by optimized energy, feasible ones first;
    row 0 is each task's primary class."""
    e = np.stack([c["e_hat"] for c in cfgs])
    feas = np.stack([c["feasible"] for c in cfgs])
    return np.argsort(np.where(feas, e, e + 1e30), axis=0, kind="stable")


def _ranked_before(cfgs: list, i: int, c: int, band: float) -> list:
    """Classes that certainly come before class ``c`` in task ``i``'s
    order: feasible where ``c`` is not, or of lower optimized energy by
    more than ``band``."""
    key = [cfg["e_hat"][i] + (0.0 if cfg["feasible"][i] else 1e30)
           for cfg in cfgs]
    return [k for k in range(len(cfgs))
            if k != c and key[k] < key[c] * (1.0 - band)]


def replay(task: dict, r: dict, cfgs: list, deploy: dict, online: bool,
           band: float) -> int:
    """Placement decisions of the records that EDL would not make.

    The records are replayed in EDL's order on a cluster that holds
    exactly what the records say: offline the pinned tasks (deadline-prior
    on their primary class, each alone on a fresh pair at ``T = 0``), then
    the rest by deadline; online the arrival groups by slot, each by
    deadline after the servers idle since ``rho`` slots are powered off.
    Before each record is applied it is judged against that state
    (Algorithms 2, 4, 5):

    * on a pair in use, that pair is the usable pair of its class free
      first, the task starts when that pair frees up (or on arrival), and
      the window left is at least ``max(theta * t_hat, t_min)``; no class
      that certainly comes first would certainly have taken it;
    * on a fresh pair, no usable pair of any class would certainly have
      taken the task, and online the pair's server is the lowest
      powered-off server of the class or, where none is off, a new one.

    "Certainly" leaves out decisions within ``band`` (relative) of the
    reference's run times and energies, where the program's float32
    solver and the reference may rightly differ.  ``r``: the live records
    as arrays; ``cfgs``: :func:`algorithm1` for the windows from the
    arrival slot."""
    l, theta = int(deploy["l"]), float(deploy["theta"])
    rho = float(deploy["rho"])
    deadline = task["deadline"]
    t, pid, cid = r["task"], r["pair"], r["class_id"].astype(np.int64)
    n_pairs = int(pid.max()) + 1 if pid.size else 0
    n_srv = -(-n_pairs // l)
    mu = np.zeros(n_pairs + l)
    cls = np.full(n_pairs + l, -1, np.int64)
    seen = np.zeros(n_pairs + l, bool)
    on = np.zeros(n_srv + 1, bool)
    built = np.zeros(n_srv + 1, bool)
    mu_srv = np.zeros(n_srv + 1)
    srv_cls = np.full(n_srv + 1, -1, np.int64)
    errors = 0

    def free_first(c):
        ok = seen[:n_pairs] & (cls[:n_pairs] == c)
        if online:
            ok &= np.repeat(on[:n_srv], l)[:n_pairs]
        return float(np.min(mu[:n_pairs][ok])) if ok.any() else None

    def takes(i, c, t_now):
        """Would the class's pair free first certainly take task ``i``?"""
        m = free_first(c)
        if m is None:
            return False
        cfg = cfgs[c]
        need = max(theta * cfg["t_hat"][i], cfg["t_min"][i])
        return deadline[i] - max(t_now, m) >= need * (1.0 + band) + T_ABS

    def judge(j, t_now):
        nonlocal errors
        i, p, c = int(t[j]), int(pid[j]), int(cid[j])
        s, f = float(r["start"][j]), float(r["finish"][j])
        sid = p // l
        fresh = not on[sid] if online else not seen[p]
        bad = False
        if fresh:
            bad |= any(takes(i, k, t_now) for k in range(len(cfgs)))
            bad |= abs(s - t_now) > T_ABS
            if online:
                off = np.flatnonzero(built[:n_srv] & ~on[:n_srv]
                                     & (srv_cls[:n_srv] == c))
                bad |= bool(sid != off[0]) if off.size else bool(built[sid])
                built[sid], on[sid], srv_cls[sid] = True, True, c
                mu_srv[sid] = t_now
                mu[sid * l:(sid + 1) * l] = t_now
                cls[sid * l:(sid + 1) * l] = c
                seen[sid * l:(sid + 1) * l] = True
            else:
                cls[p], seen[p] = c, True
        else:
            m = free_first(c)
            bad |= bool(cls[p] != c) or m is None \
                or mu[p] > m + T_ABS + T_REL * abs(m)
            bad |= abs(s - max(t_now, mu[p])) > T_ABS + T_REL * abs(s)
            need = max(theta * cfgs[c]["t_hat"][i], cfgs[c]["t_min"][i])
            bad |= deadline[i] - s < need * (1.0 - band) - T_ABS
            bad |= any(takes(i, k, t_now)
                       for k in _ranked_before(cfgs, i, c, band))
        errors += int(bad)
        mu[p] = f
        if online:
            mu_srv[sid] = max(mu_srv[sid], f)

    if online:
        slots = np.ceil(task["arrival"][t]).astype(np.int64)
        by_slot = np.lexsort((t, deadline[t], slots))
        cuts = np.flatnonzero(np.diff(slots[by_slot])) + 1
        for js in np.split(by_slot, cuts):
            t_now = float(slots[js[0]])
            off = on[:n_srv] & (mu_srv[:n_srv] + rho <= t_now + 1e-9)
            on[:n_srv][off] = False
            for j in js:
                judge(int(j), t_now)
        return errors

    # Offline: the pins are the first pairs, opened by deadline, each for
    # one task that is deadline-prior on its primary class (or within
    # ``band`` of it) and starts at 0.
    window = deadline - task["arrival"]
    primary = class_order(cfgs)[0]
    unc_p = np.take_along_axis(np.stack([c["t_unc"] for c in cfgs]),
                               primary[None], axis=0)[0]
    maybe_dp = unc_p > window * (1.0 - band)
    sure_dp = unc_p > window * (1.0 + band) + 1e-6
    first = {}
    for j in np.lexsort((r["start"], pid)):
        first.setdefault(int(pid[j]), int(j))
    n_pin = 0
    while n_pin in first and r["start"][first[n_pin]] <= T_ABS \
            and maybe_dp[t[first[n_pin]]]:
        n_pin += 1
    pinned = np.zeros(t.shape[0], bool)
    pinned[[first[p] for p in range(n_pin)]] = True
    errors += int(np.sum(sure_dp[t] & ~pinned))
    for j in np.flatnonzero(pinned):
        p = int(pid[j])
        cls[p], seen[p], mu[p] = cid[j], True, r["finish"][j]
    rest = np.flatnonzero(~pinned)
    for j in rest[np.lexsort((t[rest], deadline[t[rest]]))]:
        judge(int(j), 0.0)
    return errors


# ---------------------------------------------------------------------------
# The schedule's checks.
# ---------------------------------------------------------------------------

def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-300)


def _server_energy(rec: dict, n_cls: int, l: int, rho: float):
    """Online Eq. 7 idle span and turn-ons per class: a server of ``l``
    pairs is powered on when a task takes one of its pairs while it is
    off, and goes off ``rho`` after its last pair frees up."""
    srv = rec["pair"] // l
    order = np.lexsort((rec["start"], srv))
    on_time = np.zeros(n_cls)
    turn_ons = np.zeros(n_cls)
    cur, since, top, cls = -1, 0.0, 0.0, 0
    for s, st, fi, c in zip(srv[order].tolist(), rec["start"][order].tolist(),
                            rec["finish"][order].tolist(),
                            rec["class_id"][order].tolist()):
        if s != cur or top + rho <= st + 1e-9:
            if cur >= 0:
                on_time[cls] += top + rho - since
            cur, since, top, cls = s, st, fi, c
            turn_ons[c] += l
        else:
            top = max(top, fi)
    if cur >= 0:
        on_time[cls] += top + rho - since
    return on_time * l, turn_ons


def _offline_energy(rec: dict, n_cls: int, l: int):
    """Algorithm 3: per class, pairs sorted by finish time descending and
    grouped ``l`` to a server powered for its longest pair."""
    span = np.zeros(n_cls)
    for k in range(n_cls):
        m = rec["class_id"] == k
        if not m.any():
            continue
        pairs, inv = np.unique(rec["pair"][m], return_inverse=True)
        mu = np.zeros(pairs.shape[0])
        np.maximum.at(mu, inv, rec["finish"][m])
        mu = np.sort(mu)[::-1]
        span[k] = mu[::l].sum() * l
    return span, np.zeros(n_cls)


def check(task: dict, rec: dict, result: dict, deploy: dict, online: bool,
          rng: np.random.Generator, n_solve: int = 2048) -> dict:
    """All numbers of one schedule.

    ``task``: the drawn arrays; ``rec``: the records as arrays (``task``,
    ``pair``, ``start``, ``finish``, ``v``, ``fc``, ``fm``, ``power``,
    ``energy``, ``class_id``, ``failed``); ``result``: ``e_total`` and
    ``violations`` as the program reported them; ``deploy``: the
    configuration file."""
    n = task["arrival"].shape[0]
    classes = deploy["class_model"]
    run_box = deploy["interval"]
    live = ~rec["failed"].astype(bool)
    known = (rec["task"] >= 0) & (rec["task"] < n)
    tid = rec["task"][live & known]
    counts = np.bincount(tid, minlength=n)
    out = {"missing": int(np.sum(counts != 1)) + int(np.sum(~known))}
    live &= known

    r = {k: np.asarray(v)[live] for k, v in rec.items()}
    t = r["task"]
    cid = r["class_id"].astype(np.int64)
    dur = r["finish"] - r["start"]

    # Pair timelines.
    arrive = np.ceil(task["arrival"][t]) if online else task["arrival"][t]
    early = r["start"] < arrive - T_ABS
    order = np.lexsort((r["start"], r["pair"]))
    sp, ss, sf = r["pair"][order], r["start"][order], r["finish"][order]
    same = sp[1:] == sp[:-1]
    tol = T_ABS + T_REL * np.abs(sf[:-1])
    clash = same & (ss[1:] < sf[:-1] - tol)
    mixed = same & (r["class_id"][order][1:] != r["class_id"][order][:-1])
    out["overlap"] = int(early.sum() + clash.sum() + mixed.sum()
                         + (dur < 0).sum())

    # Per-record model constants on the record's class.
    p = {f: np.empty(t.shape[0]) for f in FIELDS}
    box = {f: np.empty(t.shape[0]) for f in BOX}
    for k, cls in enumerate(classes):
        m = cid == k
        if not m.any():
            continue
        pk = adapt({f: task[f][t[m]] for f in FIELDS}, cls)
        bk = class_box(cls, run_box)
        for f in FIELDS:
            p[f][m] = pk[f]
        for f in BOX:
            box[f][m] = bk[f]
    window = task["deadline"][t] - arrive
    feasible = min_time(p, box) <= window + T_ABS
    over = r["finish"] > task["deadline"][t] + T_ABS + T_REL * r["finish"]
    out["late"] = int(np.sum(over & feasible))
    ref_viol = int(np.sum(over | ~feasible))
    out["violations_gap"] = abs(int(result["violations"]) - ref_viol)

    # The records against the model at their own setting.
    v, fc, fm = r["v"], r["fc"], r["fm"]
    p_mod = power(p, v, fc, fm)
    t_mod = exec_time(p, fc, fm)
    outside = ((v < box["v_min"] - 1e-6) | (v > box["v_max"] + 1e-6)
               | (fc < box["fc_min"] - 1e-6) | (g1_inv(fc) > v + 1e-6)
               | (fm < box["fm_min"] - 1e-6) | (fm > box["fm_max"] + 1e-6))
    gaps = [_rel(r["power"], p_mod), _rel(dur, t_mod),
            _rel(r["energy"], r["power"] * dur)]
    out["model_gap"] = float(max(g.max() for g in gaps))
    out["off_box"] = int(outside.sum())

    # Algorithm 1 by the reference for every task and class, over the
    # window from the arrival slot.  A record's expected run time is the
    # optimum's where that fits between its start and the deadline (or
    # where no setting meets the deadline), else the window left: a
    # theta-readjustment.
    arrive_all = np.ceil(task["arrival"]) if online else task["arrival"]
    cfgs = algorithm1(task, deploy, task["deadline"] - arrive_all)
    t_hat, e_hat = np.empty(t.shape[0]), np.empty(t.shape[0])
    feas = np.zeros(t.shape[0], bool)
    for c, cfg in enumerate(cfgs):
        m = cid == c
        t_hat[m], e_hat[m] = cfg["t_hat"][t[m]], cfg["e_hat"][t[m]]
        feas[m] = cfg["feasible"][t[m]]
    left = task["deadline"][t] - r["start"]
    keep = (left >= t_hat * (1.0 - 1e-6) - T_ABS) | ~feas
    out["run_time_gap"] = float(np.max(_rel(dur, np.where(keep, t_hat,
                                                           left))))

    # Each sampled record's energy against the least the reference finds
    # for it under the same rule, the longest record among them.
    k = min(n_solve, t.shape[0])
    pick = rng.choice(t.shape[0], size=k, replace=False)
    pick = np.union1d(pick, [int(np.argmax(dur))])
    e_opt = e_hat[pick]
    tight = ~keep[pick]
    if tight.any():
        ps = {f: p[f][pick][tight] for f in FIELDS}
        bs = {f: box[f][pick][tight] for f in BOX}
        e_opt[tight] = solve_blocks(ps, left[pick][tight], bs)[5]
    out["solve_gap"] = float(np.max(_rel(r["energy"][pick], e_opt)))
    out["placement_errors"] = replay(task, r, cfgs, deploy, online, BAND)

    # Eq. 7 rebuilt from the records.
    l, n_cls = int(deploy["l"]), len(classes)
    if online:
        span, turn_ons = _server_energy(r, n_cls, l, float(deploy["rho"]))
    else:
        span, turn_ons = _offline_energy(r, n_cls, l)
    busy = np.bincount(cid, weights=dur, minlength=n_cls)
    e_idle = sum(c["p_idle"] * (span[k] - busy[k])
                 for k, c in enumerate(classes))
    e_over = sum(c["delta_on"] * turn_ons[k] for k, c in enumerate(classes))
    e_ref_total = float(np.sum(p_mod * dur)) + e_idle + e_over
    out["account_gap"] = abs(float(result["e_total"]) - e_ref_total) \
        / abs(e_ref_total)
    return out


def passed(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] and math.isfinite(numbers[k])
               for k in LIMITS)
