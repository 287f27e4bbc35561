"""Fault-tolerant training loop: checkpoint/restart, failure recovery,
straggler watchdog.

The loop is the part of the stack that must survive a 1000-node fleet:

* **Checkpoint/restart** — periodic async checkpoints; on (re)start the
  loop restores the latest complete checkpoint and resumes from its step;
  the data pipeline is keyed by step so the replayed stream is exact.
* **Failure recovery** — any exception from the step function (device
  loss, preemption; simulated in tests via ``failure_hook``) triggers
  restore-from-latest + retry, up to ``max_recoveries``.
* **Straggler watchdog** — an EWMA of step wall-time; steps slower than
  ``straggler_factor`` x EWMA are counted and surfaced in metrics.  On a
  real fleet this signal feeds the scheduler's DRS/hot-swap decision (the
  paper's θ-readjustment consumes exactly this kind of runtime signal).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, Optional

from repro.checkpoint.store import CheckpointStore


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    keep: int = 3
    straggler_factor: float = 3.0
    max_recoveries: int = 5
    log_every: int = 10
    metrics_path: Optional[str] = None


def run_loop(step_fn: Callable, state, data, cfg: LoopConfig, *,
             state_shardings=None,
             put_batch: Callable = None,
             failure_hook: Callable[[int], None] = None,
             log: Callable[[str], None] = print) -> Dict[str, Any]:
    """Run ``state = step_fn(state, batch)`` for ``cfg.total_steps``.

    ``data.batch(step)`` supplies batches; ``failure_hook(step)`` (tests)
    may raise to simulate node failure.  Returns the final state and
    summary stats."""
    store = (CheckpointStore(cfg.checkpoint_dir, cfg.keep)
             if cfg.checkpoint_dir else None)
    start = 0
    if store and store.latest_step() is not None:
        state = store.restore(state, shardings=state_shardings)
        start = int(store.latest_step()) + 1
        log(f"[loop] restored checkpoint, resuming at step {start}")

    ewma = None
    stragglers = 0
    recoveries = 0
    losses = []
    metrics_f = open(cfg.metrics_path, "a") if cfg.metrics_path else None

    step = start
    while step < cfg.total_steps:
        try:
            if failure_hook is not None:
                failure_hook(step)
            batch = data.batch(step)
            if put_batch is not None:
                batch = put_batch(batch)
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            if ewma is None:
                ewma = dt
            elif dt > cfg.straggler_factor * ewma and step > start + 2:
                stragglers += 1
                log(f"[loop] step {step}: straggler ({dt:.2f}s vs "
                    f"EWMA {ewma:.2f}s)")
            ewma = 0.9 * ewma + 0.1 * dt if ewma else dt
            losses.append(loss)
            if metrics_f:
                row = {"step": step, "loss": loss, "time_s": dt}
                row.update({k: float(v) for k, v in metrics.items()
                            if k != "loss"})
                metrics_f.write(json.dumps(row) + "\n")
                metrics_f.flush()
            if cfg.log_every and step % cfg.log_every == 0:
                log(f"[loop] step {step}: loss={loss:.4f} ({dt:.2f}s)")
            if store and cfg.checkpoint_every and \
                    step % cfg.checkpoint_every == 0 and step > start:
                store.save(step, state)
            step += 1
        except KeyboardInterrupt:
            raise
        except Exception as e:  # noqa: BLE001 — node-failure surface
            recoveries += 1
            if recoveries > cfg.max_recoveries or store is None:
                raise
            log(f"[loop] step {step}: FAILURE {type(e).__name__}: {e}; "
                f"restoring latest checkpoint "
                f"({recoveries}/{cfg.max_recoveries})")
            store.wait()    # a save in flight would publish mid-restore
            last = store.latest_step()
            if last is not None:
                state = store.restore(state, step=last,
                                      shardings=state_shardings)
                step = int(last) + 1
            else:
                step = start  # nothing saved yet: restart from scratch

    if store:
        store.save(step - 1, state, blocking=True)
    if metrics_f:
        metrics_f.close()
    return {"state": state, "losses": losses, "stragglers": stragglers,
            "recoveries": recoveries, "final_step": step}
