import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against ShapeDtypeStruct inputs (no allocation), and capture

* ``compiled.memory_analysis()``  — proves the cell fits per-device HBM,
* ``compiled.cost_analysis()``    — per-device HLO FLOPs / bytes,
* collective bytes parsed from the partitioned HLO (``compiled.as_text()``),

into one JSON per cell under ``--out``.  ``benchmarks/roofline.py`` turns
these into the three-term roofline table.

Loop-body correction: XLA cost analysis counts a ``lax.scan`` (while) body
ONCE regardless of trip count (verified empirically), so each cell also
compiles two small *probe* programs — the same step on a 1-unit and a
2-unit model with the layer loop UNROLLED.  ``B = cost(2u) - cost(u)`` is
the exact per-unit cost and ``F = cost(u) - B`` the layer-independent part;
the corrected totals are ``M * (F + L_units * B)`` (M = gradient-
accumulation microbatches; the optimizer mis-scaling this introduces is
< 1e-5 of step FLOPs, noted in EXPERIMENTS.md).

Run::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
        --shape train_4k --mesh single --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun
"""

import argparse
import dataclasses
import json
import math
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import partition
from repro.configs import registry
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train.trainer import init_state, make_state_axes, make_train_step

HBM_BYTES = 16 * 2**30          # v5e-class: 16 GiB per chip
ACT_BUDGET = 6 * 2**30          # live-activation napkin budget for microbatching


# ---------------------------------------------------------------------------
# Microbatch policy (grad accumulation keeps live activations under budget).
# ---------------------------------------------------------------------------


def dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def choose_microbatches(cfg, spec, mesh) -> int:
    if spec.mode != "train":
        return 1
    dp = dp_size(mesh)
    B, S = spec.global_batch, spec.seq_len
    d_eff = max(cfg.d_model, cfg.d_inner if cfg.family == "ssm" else 0,
                cfg.rnn_width_ if cfg.family == "hybrid" else 0)
    # Per-layer live bytes per sequence row under per-layer remat: the saved
    # residual plus scan carries; alpha=2 safety.
    per_row_layer = S * d_eff * 2 * 2
    m = 1
    while True:
        rows_per_chip = max(1, (B // m) // dp)
        live = cfg.n_layers * rows_per_chip * per_row_layer
        if live <= ACT_BUDGET or (B // (2 * m)) % dp != 0 or B // (2 * m) < dp:
            return m
        m *= 2


# ---------------------------------------------------------------------------
# Collective parsing (ring model).
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(?P<shape>[^=]*?)\s+(?P<op>all-reduce-start|all-gather-start|"
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute-start|"
    r"collective-permute)\(")
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred|"
                       r"c64|c128)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{\{")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Per-device collective byte accounting from partitioned HLO.

    Returns operand-byte sums per op kind (the prompt's prescription) and a
    ring-model wire-bytes estimate per device."""
    per_op: Dict[str, float] = {}
    wire = 0.0
    operand = 0.0
    count = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        op = m.group("op").replace("-start", "")
        result_bytes = _shape_bytes(m.group("shape"))
        if result_bytes == 0:
            continue
        gi = _GROUPS_IOTA_RE.search(line)
        if gi:
            gsize = int(gi.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            gsize = len(gl.group(1).split(",")) if gl else 1
        n = max(gsize, 1)
        if op == "all-reduce":
            op_bytes = result_bytes
            w = 2.0 * result_bytes * (n - 1) / n
        elif op == "all-gather":
            op_bytes = result_bytes / n          # operand is the local shard
            w = result_bytes * (n - 1) / n
        elif op == "reduce-scatter":
            op_bytes = result_bytes * n          # operand is the full tensor
            w = result_bytes * (n - 1)
        elif op == "all-to-all":
            op_bytes = result_bytes
            w = result_bytes * (n - 1) / n
        else:  # collective-permute
            op_bytes = result_bytes
            w = float(result_bytes)
        per_op[op] = per_op.get(op, 0.0) + op_bytes
        wire += w
        operand += op_bytes
        count += 1
    return {"per_op_operand_bytes": per_op, "operand_bytes": operand,
            "ring_wire_bytes": wire, "n_collectives": count}


# ---------------------------------------------------------------------------
# Cell construction.
# ---------------------------------------------------------------------------


def _probe_cfg(cfg, units: int):
    """A config with ``units`` pattern units of layers (for probes)."""
    if cfg.family == "hybrid":
        n = units * len(cfg.block_pattern)
    else:
        n = units
    kw = dict(n_layers=n)
    if cfg.family == "encdec":
        kw["n_enc_layers"] = units
    return dataclasses.replace(cfg, **kw)


def n_units(cfg) -> float:
    if cfg.family == "hybrid":
        return cfg.n_layers / len(cfg.block_pattern)
    return float(cfg.n_layers)


def _capture_axes(fn):
    """Run ``fn`` (returning (arrays, axes)) under eval_shape; capture axes."""
    box = {}

    def inner(*a):
        out, axes = fn(*a)
        box["axes"] = axes
        return out

    shapes = jax.eval_shape(inner)
    return shapes, box["axes"]


def build_cell(arch: str, shape: str, mesh, *, cfg=None, unroll=False,
               microbatches: Optional[int] = None, rules_kind="fsdp",
               remat=True, extra_rules: Optional[dict] = None,
               batch_rows: Optional[int] = None):
    """Returns (fn, arg_shapes tuple, in_shardings tuple, donate_argnums).

    ``batch_rows`` overrides the global batch (roofline probes run the step
    on exactly one microbatch so the M x (F + L x B) correction scales both
    activation and per-microbatch gradient collectives correctly)."""
    spec = registry.SHAPES[shape]
    cfg = cfg or registry.get_config(arch)
    model = Model(cfg, unroll=unroll)
    rows = batch_rows or spec.global_batch
    if rules_kind == "fsdp":
        rules = partition.fsdp_rules(mesh, rows)
    elif rules_kind == "serve":
        rules = partition.serve_rules(mesh, rows)
    else:
        rules = partition.replicated_rules(mesh, rows)
    if extra_rules:
        rules = partition.Rules(mesh=mesh, table={**rules.table, **extra_rules})

    mb = microbatches if microbatches is not None else \
        choose_microbatches(cfg, spec, mesh)

    inputs = registry.input_specs(arch, shape)
    in_axes = registry.input_logical_axes(arch, shape)
    if batch_rows is not None:
        inputs = {k: jax.ShapeDtypeStruct((rows,) + v.shape[1:], v.dtype)
                  for k, v in inputs.items()}
    batch_sh = {k: rules.sharding(in_axes[k]) for k in inputs}

    params_shapes, param_axes = _capture_axes(
        lambda: model.init(jax.random.key(0)))

    if spec.mode == "train":
        opt = AdamW(learning_rate=cosine_schedule(3e-4, 100, 10_000))
        step = make_train_step(model, opt, microbatches=mb, remat=remat,
                               param_axes=param_axes)
        state_shapes = jax.eval_shape(
            lambda: init_state(model, opt, jax.random.key(0)))
        state_axes = make_state_axes(param_axes)
        state_sh = jax.tree.map(lambda a: rules.sharding(a), state_axes,
                                is_leaf=_is_axes_leaf)
        fn = step
        args = (state_shapes, inputs)
        shardings = (state_sh, batch_sh)
        donate = (0,)
    elif spec.mode == "prefill":
        def fn(params, batch):
            return model.prefill(params, batch, max_seq=spec.seq_len)

        params_sh = jax.tree.map(lambda a: rules.sharding(a), param_axes,
                                 is_leaf=_is_axes_leaf)
        args = (params_shapes, inputs)
        shardings = (params_sh, batch_sh)
        donate = ()
    else:  # decode
        cache_shapes, cache_axes = _capture_axes(
            lambda: model.init_cache(rows, spec.seq_len))
        if rules_kind == "serve":
            # serving stores weights in bf16 (no optimizer on this path)
            params_shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, jnp.bfloat16 if x.dtype == jnp.float32
                    else x.dtype), params_shapes)
        params_sh = jax.tree.map(lambda a: rules.sharding(a), param_axes,
                                 is_leaf=_is_axes_leaf)
        cache_sh = jax.tree.map(lambda a: rules.sharding(a), cache_axes,
                                is_leaf=_is_axes_leaf)

        def fn(params, cache, token, pos):
            return model.decode_step(params, cache, token, pos)

        args = (params_shapes, cache_shapes, inputs["token"],
                jax.ShapeDtypeStruct((), jnp.int32))
        shardings = (params_sh, cache_sh, batch_sh["token"],
                     rules.sharding(()))
        donate = (1,)
    return fn, args, shardings, donate, rules, mb


def _is_axes_leaf(x) -> bool:
    return partition.is_axes(x)


# ---------------------------------------------------------------------------
# Lower + compile + capture.
# ---------------------------------------------------------------------------


def compile_cell(arch: str, shape: str, mesh, **kw):
    fn, args, shardings, donate, rules, mb = build_cell(arch, shape, mesh,
                                                        **kw)
    t0 = time.time()
    with partition.use_rules(rules), mesh:
        jitted = jax.jit(fn, in_shardings=shardings,
                         donate_argnums=donate or None)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_total = time.time() - t0
    return compiled, dict(lower_s=round(t_lower, 2),
                          compile_s=round(t_total - t_lower, 2),
                          microbatches=mb)


def capture(compiled) -> Dict[str, Any]:
    ma = compiled.memory_analysis()
    mem = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem[f] = int(getattr(ma, f, 0) or 0)
    # Live-bytes estimate: donated outputs alias arguments.
    mem["live_bytes"] = (mem["argument_size_in_bytes"]
                         + mem["temp_size_in_bytes"]
                         + max(0, mem["output_size_in_bytes"]
                               - mem["alias_size_in_bytes"]))
    ca = compiled.cost_analysis() or {}
    cost = {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    text = compiled.as_text()
    coll = parse_collectives(text)
    return {"memory": mem, "cost": cost, "collectives": coll,
            "hlo_chars": len(text)}


def hbm_napkin(cfg, spec, mesh, mb: int) -> Dict[str, float]:
    """Analytic per-chip HBM budget (bytes) for the TPU target.

    The CPU backend's ``temp_size`` includes an f32 round-trip of the remat
    stash introduced by CPU fusion of dynamic-update-slice (verified on
    qwen2-72b: the while carry itself is bf16); the napkin is the
    TPU-expected budget and both are reported."""
    chips = math.prod(mesh.shape.values())
    dp = dp_size(mesh)
    params = cfg.param_count()
    p_bytes = params * 4 / chips              # f32 master, fully sharded
    opt_bytes = 2 * p_bytes                   # adam m, v
    grad_bytes = params * 4 / chips
    out = {"params": p_bytes, "opt": opt_bytes}
    if spec.mode == "train":
        rows = max(1, (spec.global_batch // mb) // dp)
        d_eff = max(cfg.d_model, cfg.d_inner if cfg.family == "ssm" else 0,
                    cfg.rnn_width_ if cfg.family == "hybrid" else 0)
        stash = cfg.n_layers * rows * spec.seq_len * cfg.d_model * 2
        out.update(grads=grad_bytes, remat_stash=stash,
                   layer_transient=rows * spec.seq_len * d_eff * 2 * 8)
    elif spec.mode == "decode":
        rows = max(1, spec.global_batch // dp)
        model_shards = mesh.shape.get("model", 1)
        if cfg.family == "ssm":
            cache = cfg.n_layers * rows * (
                cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
                + (cfg.conv_width - 1) * (cfg.d_inner + 2 * cfg.ssm_state) * 2)
        else:
            w = min(spec.seq_len, cfg.sliding_window or spec.seq_len)
            cache = (cfg.n_layers * rows * (w / model_shards)
                     * cfg.n_kv_heads * cfg.head_dim_ * 2 * 2)
        out["kv_cache"] = cache
    else:  # prefill
        rows = max(1, spec.global_batch // dp)
        out["activations"] = rows * spec.seq_len * cfg.d_model * 2 * 8
        model_shards = mesh.shape.get("model", 1)
        out["kv_cache_out"] = (cfg.n_layers * rows
                               * (spec.seq_len / model_shards)
                               * cfg.n_kv_heads * cfg.head_dim_ * 2 * 2)
    out["total"] = float(sum(out.values()))
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, *, probes=True,
             out_dir: Optional[str] = None, microbatches=None,
             rules_kind="fsdp", tag="baseline", extra_rules=None,
             remat=True) -> Dict[str, Any]:
    spec = registry.SHAPES[shape]
    cfg = registry.get_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec: Dict[str, Any] = dict(arch=arch, shape=shape, mesh=mesh_kind,
                               mode=spec.mode, tag=tag, ok=False)
    try:
        compiled, meta = compile_cell(arch, shape, mesh,
                                      microbatches=microbatches,
                                      rules_kind=rules_kind,
                                      extra_rules=extra_rules, remat=remat)
        rec.update(meta)
        rec["full"] = capture(compiled)
        rec["hbm_napkin"] = hbm_napkin(cfg, spec, mesh, rec["microbatches"])
        del compiled
        rec["ok"] = True

        if probes:
            pr = {}
            mb_real = rec.get("microbatches", 1)
            rows = spec.global_batch // mb_real
            for units in (1, 2):
                pcfg = _probe_cfg(cfg, units)
                # Probe = one microbatch of the real step, layers unrolled.
                c, _ = compile_cell(arch, shape, mesh, cfg=pcfg, unroll=True,
                                    microbatches=1, batch_rows=rows,
                                    rules_kind=rules_kind,
                                    extra_rules=extra_rules, remat=remat)
                pr[f"u{units}"] = capture(c)
                del c
            rec["probes"] = pr
            rec["corrected"] = correct(rec, cfg)
    except Exception as e:  # noqa: BLE001 — record the failure verbatim
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}__{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def correct(rec: Dict[str, Any], cfg) -> Dict[str, Any]:
    """Loop-body-corrected totals: M * (F + L_units * B) per metric."""
    u1, u2 = rec["probes"]["u1"], rec["probes"]["u2"]
    L = n_units(cfg)
    M = rec.get("microbatches", 1)
    out = {}
    for key, get in (
            ("flops", lambda c: c["cost"]["flops"]),
            ("bytes_accessed", lambda c: c["cost"]["bytes_accessed"]),
            ("collective_operand_bytes",
             lambda c: c["collectives"]["operand_bytes"]),
            ("collective_wire_bytes",
             lambda c: c["collectives"]["ring_wire_bytes"])):
        b = get(u2) - get(u1)
        f = get(u1) - b
        out[key] = M * (f + L * b)
        out[key + "_per_unit"] = b
        out[key + "_fixed"] = f
    return out


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--rules", default="fsdp")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--remat", default="on", choices=["on", "off"])
    args = ap.parse_args()

    if args.list:
        for a, s in registry.list_cells():
            print(f"{a:24s} {s}")
        return

    cells = registry.list_cells() if args.all else [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for arch, shape in cells:
        reason = registry.cell_skip_reason(arch, shape)
        if reason:
            print(f"SKIP {arch}/{shape}: {reason}")
            continue
        for mk in meshes:
            t0 = time.time()
            rec = run_cell(arch, shape, mk, probes=not args.no_probes,
                           out_dir=args.out, microbatches=args.microbatches,
                           rules_kind=args.rules, tag=args.tag,
                           remat=(args.remat == "on"))
            status = "OK " if rec["ok"] else "FAIL"
            dt = time.time() - t0
            if rec["ok"]:
                mem = rec["full"]["memory"]
                per_dev = mem["live_bytes"] / 2**30
                print(f"{status} {arch}/{shape}/{mk} mb={rec['microbatches']} "
                      f"mem/dev={per_dev:.2f}GiB "
                      f"flops={rec['full']['cost']['flops']:.3g} "
                      f"coll={rec['full']['collectives']['n_collectives']} "
                      f"({dt:.0f}s)", flush=True)
            else:
                print(f"{status} {arch}/{shape}/{mk}: {rec['error']} "
                      f"({dt:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
