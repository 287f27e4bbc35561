"""Integration tests for the dry-run harness and elastic restore, run in
subprocesses with forced host-device counts (so this pytest process keeps
its single default device)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


@pytest.mark.slow
def test_dryrun_cell_on_mini_mesh():
    """The dry-run harness end to end (build/lower/compile/capture/correct)
    on a 4x4 mini-mesh with a small arch — the same code path the 512-device
    production run uses."""
    code = """
    import json
    import jax
    from repro import partition
    from repro.launch import dryrun as dr
    from repro.launch.mesh import _mesh

    mesh = _mesh((4, 4), ("data", "model"))
    fn, args, sh, don, rules, mb = dr.build_cell(
        "whisper-base", "train_4k", mesh, batch_rows=16, microbatches=1)
    with partition.use_rules(rules), mesh:
        comp = jax.jit(fn, in_shardings=sh,
                       donate_argnums=don or None).lower(*args).compile()
    cap = dr.capture(comp)
    assert cap["cost"]["flops"] > 0
    assert cap["collectives"]["n_collectives"] > 0
    assert cap["memory"]["live_bytes"] > 0
    print("MINI_MESH_OK", json.dumps(
        {"flops": cap["cost"]["flops"],
         "colls": cap["collectives"]["n_collectives"]}))
    """
    r = run_py(code, devices=16)
    assert "MINI_MESH_OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.slow
def test_elastic_restore_across_topologies(tmp_path):
    """Save a TrainState on a (2,2) mesh, restore it onto a (4,1) mesh —
    the 'restart on a different pod count' path."""
    ckdir = str(tmp_path / "ck")
    save_code = f"""
    import jax
    from repro import partition
    from repro.checkpoint.store import CheckpointStore
    from repro.configs import get_config
    from repro.launch.mesh import _mesh
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    from repro.train.trainer import init_state
    mesh = _mesh((2, 2), ("data", "model"))
    cfg = get_config("h2o-danube-1.8b").reduced()
    model = Model(cfg)
    opt = AdamW()
    with partition.use_rules(partition.fsdp_rules(mesh, 8)), mesh:
        state = init_state(model, opt, jax.random.key(7))
    CheckpointStore({ckdir!r}).save(3, state, blocking=True)
    print("SAVED", float(jax.tree.leaves(state.params)[0].sum()))
    """
    r1 = run_py(save_code, devices=4)
    assert "SAVED" in r1.stdout, r1.stderr[-3000:]
    saved_sum = float(r1.stdout.split("SAVED")[1].strip())

    restore_code = f"""
    import jax
    from repro import partition
    from repro.checkpoint.store import CheckpointStore
    from repro.configs import get_config
    from repro.launch.mesh import _mesh
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    from repro.train.trainer import init_state, make_state_axes
    mesh = _mesh((4, 1), ("data", "model"))   # NEW topology
    cfg = get_config("h2o-danube-1.8b").reduced()
    model = Model(cfg)
    opt = AdamW()
    rules = partition.fsdp_rules(mesh, 8)
    with partition.use_rules(rules), mesh:
        like = init_state(model, opt, jax.random.key(0))
        sh = jax.tree.map(rules.sharding, make_state_axes(model.param_axes()),
                          is_leaf=partition.is_axes)
        state = CheckpointStore({ckdir!r}).restore(like, shardings=sh)
    leaf = jax.tree.leaves(state.params)[0]
    assert "data" in str(leaf.sharding.spec) or True
    print("RESTORED", float(leaf.sum()))
    """
    r2 = run_py(restore_code, devices=4)
    assert "RESTORED" in r2.stdout, r2.stderr[-3000:]
    restored_sum = float(r2.stdout.split("RESTORED")[1].strip())
    assert abs(saved_sum - restored_sum) < 1e-3 * max(abs(saved_sum), 1.0)
