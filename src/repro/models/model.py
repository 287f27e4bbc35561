"""Unified model: one class covering every assigned architecture family.

``Model(cfg)`` exposes four entry points, all pure functions of a params
pytree (so every one of them is ``jax.eval_shape``-able for the dry-run):

* ``init(key) -> (params, axes)`` — axes is a matching pytree of
  logical-axis tuples consumed by :mod:`repro.partition`.
* ``loss_fn(params, batch) -> (loss, metrics)`` — next-token CE (chunked
  vocab-parallel-friendly), plus MoE aux losses where applicable.
* ``prefill(params, batch) -> (last_logits, cache)`` — processes a prompt
  and builds the decode cache.
* ``decode_step(params, cache, token, pos) -> (logits, cache)`` — one new
  token against the cache; caches are O(seq) KV for attention families and
  O(1) recurrent state for SSM/hybrid families.

Layer stacks run as ``lax.scan`` over stacked weights (a single HLO while
body regardless of depth — this is what keeps 66 dry-run compiles
tractable), with optional per-layer ``jax.checkpoint`` for training.
Hybrid (RecurrentGemma) stacks scan over complete pattern *units*
(rec, rec, attn) and unroll the remainder.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import partition
from repro.models import (attention as attn_lib, moe as moe_lib,
                          rglru as rglru_lib, ssm as ssm_lib)
from repro.models.config import ModelConfig
from repro.models.layers import (COMPUTE_DTYPE, ParamBuilder, Params,
                                 embed_lookup, init_mlp, layer_norm, mlp,
                                 rms_norm, sinusoidal_positions)

CE_CHUNK = 512  # sequence chunk for the checkpointed cross-entropy


def _is_axes(x) -> bool:
    return partition.is_axes(x)


def _prefix_layers(axes):
    return jax.tree.map(lambda a: ("layers",) + a, axes, is_leaf=_is_axes)


def _built(build_fn, key, *args):
    b = ParamBuilder(key)
    params = build_fn(b, *args)
    return params, {k: v for k, v in b.axes.items() if k in params}


def stack_layers(key: jax.Array, n: int, build_fn):
    """Stack ``n`` layers built by ``build_fn(key) -> (params, axes)``."""
    _, axes = build_fn(jax.random.key(0))  # structure + axes side-channel
    params = jax.vmap(lambda k: build_fn(k)[0])(jax.random.split(key, n))
    return params, _prefix_layers(axes)


# ---------------------------------------------------------------------------
# Norm helpers (rms for LM families, layernorm for whisper).
# ---------------------------------------------------------------------------


def _init_norm(b: ParamBuilder, d: int, kind: str, name: str) -> Params:
    if kind == "rms":
        return {"scale": b.param(f"{name}_s", (d,), ("embed",), init="zeros")}
    return {"scale": b.param(f"{name}_s", (d,), ("embed",), init="ones"),
            "bias": b.param(f"{name}_b", (d,), ("embed",), init="zeros")}


def _norm(p: Params, x: jax.Array, kind: str, eps: float) -> jax.Array:
    if kind == "rms":
        return rms_norm(x, p["scale"], eps)
    return layer_norm(x, p["scale"], p["bias"], eps)


def _norm_axes(kind: str) -> Dict[str, tuple]:
    if kind == "rms":
        return {"scale": ("embed",)}
    return {"scale": ("embed",), "bias": ("embed",)}


# ---------------------------------------------------------------------------
# Per-family layer builders: build(key) -> (params, axes).
# ---------------------------------------------------------------------------


def _build_attn_mlp_layer(key, cfg: ModelConfig, norm_kind: str,
                          use_moe: bool = False):
    b = ParamBuilder(key)
    attn_p, attn_a = _built(attn_lib.init_attention, b.next_key(), cfg)
    if use_moe:
        mlp_p, mlp_a = _built(moe_lib.init_moe, b.next_key(), cfg)
    else:
        mlp_p, mlp_a = _built(init_mlp, b.next_key(), cfg.d_model, cfg.d_ff,
                              cfg.mlp_type)
    nb = ParamBuilder(b.next_key())
    params = {
        "ln1": _init_norm(nb, cfg.d_model, norm_kind, "ln1"),
        "attn": attn_p,
        "ln2": _init_norm(nb, cfg.d_model, norm_kind, "ln2"),
        "mlp": mlp_p,
    }
    axes = {
        "ln1": _norm_axes(norm_kind), "attn": attn_a,
        "ln2": _norm_axes(norm_kind), "mlp": mlp_a,
    }
    return params, axes


def _build_ssm_layer(key, cfg: ModelConfig):
    b = ParamBuilder(key)
    mix_p, mix_a = _built(ssm_lib.init_mamba2, b.next_key(), cfg)
    nb = ParamBuilder(b.next_key())
    return ({"ln": _init_norm(nb, cfg.d_model, "rms", "ln"), "mixer": mix_p},
            {"ln": _norm_axes("rms"), "mixer": mix_a})


def _build_hybrid_layer(key, cfg: ModelConfig, kind: str):
    b = ParamBuilder(key)
    if kind == "rec":
        blk_p, blk_a = _built(rglru_lib.init_rglru_block, b.next_key(), cfg)
    else:
        blk_p, blk_a = _built(attn_lib.init_attention, b.next_key(), cfg)
    mlp_p, mlp_a = _built(init_mlp, b.next_key(), cfg.d_model, cfg.d_ff,
                          cfg.mlp_type)
    nb = ParamBuilder(b.next_key())
    return ({"ln1": _init_norm(nb, cfg.d_model, "rms", "ln1"), "block": blk_p,
             "ln2": _init_norm(nb, cfg.d_model, "rms", "ln2"), "mlp": mlp_p},
            {"ln1": _norm_axes("rms"), "block": blk_a,
             "ln2": _norm_axes("rms"), "mlp": mlp_a})


def _build_decoder_xattn_layer(key, cfg: ModelConfig):
    """Whisper decoder layer: self-attn + cross-attn + mlp, layernorm."""
    b = ParamBuilder(key)
    self_p, self_a = _built(attn_lib.init_attention, b.next_key(), cfg)
    cross_p, cross_a = _built(attn_lib.init_attention, b.next_key(), cfg)
    mlp_p, mlp_a = _built(init_mlp, b.next_key(), cfg.d_model, cfg.d_ff,
                          cfg.mlp_type)
    nb = ParamBuilder(b.next_key())
    return ({"ln1": _init_norm(nb, cfg.d_model, "ln", "ln1"), "self": self_p,
             "ln2": _init_norm(nb, cfg.d_model, "ln", "ln2"), "cross": cross_p,
             "ln3": _init_norm(nb, cfg.d_model, "ln", "ln3"), "mlp": mlp_p},
            {"ln1": _norm_axes("ln"), "self": self_a,
             "ln2": _norm_axes("ln"), "cross": cross_a,
             "ln3": _norm_axes("ln"), "mlp": mlp_a})


# ---------------------------------------------------------------------------
# Chunked cross-entropy (keeps [B, S, V] logits out of live memory).
# ---------------------------------------------------------------------------


def chunked_cross_entropy(x: jax.Array, head: jax.Array, labels: jax.Array,
                          mask: Optional[jax.Array] = None,
                          chunk: int = CE_CHUNK,
                          unroll: bool = False,
                          valid_vocab: Optional[int] = None) -> jax.Array:
    """Mean next-token CE; computes logits per sequence-chunk inside a
    checkpointed scan so only one chunk's [B, c, V] is ever live."""
    B, S, d = x.shape
    c = min(chunk, S)
    while S % c:
        c //= 2
    n = S // c
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = jnp.broadcast_to(mask, (B, S))
    xc = x.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, c).transpose(1, 0, 2)
    mc = mask.astype(jnp.float32).reshape(B, n, c).transpose(1, 0, 2)

    head = partition.constrain(head.astype(COMPUTE_DTYPE), (None, "vocab"))

    @jax.checkpoint
    def body(carry, inp):
        xi, li, mi = inp
        logits = (xi @ head).astype(jnp.float32)
        logits = partition.constrain(logits, ("batch", None, "vocab"))
        if valid_vocab is not None and valid_vocab < logits.shape[-1]:
            pad = jnp.arange(logits.shape[-1]) >= valid_vocab
            logits = jnp.where(pad, -1e30, logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        nll_sum = jnp.sum((lse - gold) * mi)
        tot, cnt = carry
        return (tot + nll_sum, cnt + jnp.sum(mi)), None

    if unroll:
        carry = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        for i in range(n):
            carry, _ = body(carry, (xc[i], lc[i], mc[i]))
        tot, cnt = carry
    else:
        (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


# §Perf H1 (EXPERIMENTS.md): the checkpointed chunk body above used to
# re-gather the FSDP-sharded head EVERY chunk in f32 (16 x 128 MiB
# all-gathers per microbatch on stablelm-12b).  The fix is the single
# bf16 (None, "vocab") constrain before the scan: the partitioner gathers
# one bf16 copy that the chunk scan reuses (jax.checkpoint saves
# scan-invariant inputs; no per-chunk re-gather).


# ---------------------------------------------------------------------------
# The unified model.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    # Unrolled layer loops (python loop over the stacked weights instead of
    # lax.scan).  Production keeps scan (compact HLO); the roofline probes
    # unroll so ``cost_analysis`` counts every layer exactly.
    unroll: bool = False
    _paxes: Any = dataclasses.field(default=None, repr=False, compare=False)

    def param_axes(self):
        """Full logical-axes pytree (computed without allocating params)."""
        if self._paxes is None:
            box = {}

            def f():
                p, a = self.init(jax.random.key(0))
                box["a"] = a
                return p

            jax.eval_shape(f)
            self._paxes = box["a"]
        return self._paxes

    def _sliced_axes(self, key: str):
        """Per-layer axes for one stacked group ('layers'/'enc_layers'):
        the leading 'layers' entry stripped from every leaf."""
        ax = self.param_axes()[key]
        return jax.tree.map(lambda a: a[1:], ax, is_leaf=partition.is_axes)

    def _constrain_layer(self, p, key: str = "layers"):
        """Constrain a sliced layer's params inside the scan body.  The
        transpose of with_sharding_constraint is the same constraint, so
        this forces the per-layer weight *cotangents* back to the fully
        sharded layout before they are stacked into the backward scan's
        carry — without it the grad stash is only model-sharded
        (~18 GiB/chip on qwen2-72b instead of ~1.1 GiB)."""
        if partition.current_rules() is None:
            return p
        return jax.tree.map(lambda t, a: partition.constrain(t, a),
                            p, self._sliced_axes(key))

    def _scan(self, body, carry, xs):
        if not self.unroll:
            return jax.lax.scan(body, carry, xs)
        n = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(n):
            carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        if ys and ys[0] is not None:
            ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
        else:
            ys = None
        return carry, ys

    # ----- construction -----------------------------------------------------
    @property
    def norm_kind(self) -> str:
        return "ln" if self.cfg.family == "encdec" else "rms"

    def init(self, key: jax.Array) -> Tuple[Params, Any]:
        cfg = self.cfg
        b = ParamBuilder(key)
        params: Dict[str, Any] = {}
        axes: Dict[str, Any] = {}

        # Vocab padded to a multiple of 256 for even TP sharding; logits
        # above cfg.vocab_size are masked to -inf everywhere they surface.
        params["embed"] = b.param("embed", (cfg.padded_vocab, cfg.d_model),
                                  ("vocab", "embed"), scale=0.02)
        axes["embed"] = ("vocab", "embed")
        if not cfg.tie_embeddings:
            params["head"] = b.param("head", (cfg.d_model, cfg.padded_vocab),
                                     ("embed", "vocab"), scale=0.02)
            axes["head"] = ("embed", "vocab")

        nb = ParamBuilder(b.next_key())
        params["final_norm"] = _init_norm(nb, cfg.d_model, self.norm_kind, "fn")
        axes["final_norm"] = _norm_axes(self.norm_kind)

        fam = cfg.family
        if fam in ("dense", "vlm", "moe"):
            build = functools.partial(_build_attn_mlp_layer, cfg=cfg,
                                      norm_kind="rms", use_moe=(fam == "moe"))
            params["layers"], axes["layers"] = stack_layers(
                b.next_key(), cfg.n_layers, build)
        elif fam == "ssm":
            params["layers"], axes["layers"] = stack_layers(
                b.next_key(), cfg.n_layers,
                functools.partial(_build_ssm_layer, cfg=cfg))
        elif fam == "hybrid":
            pattern = cfg.block_pattern or ("attn",)
            n_units, rem = divmod(cfg.n_layers, len(pattern))

            def build_unit(k):
                ps, as_ = [], []
                for i, kind in enumerate(pattern):
                    p, a = _build_hybrid_layer(jax.random.fold_in(k, i), cfg, kind)
                    ps.append(p)
                    as_.append(a)
                return tuple(ps), tuple(as_)

            params["layers"], axes["layers"] = stack_layers(
                b.next_key(), n_units, build_unit)
            rem_p, rem_a = [], []
            for i in range(rem):
                p, a = _build_hybrid_layer(b.next_key(), cfg, pattern[i])
                rem_p.append(p)
                rem_a.append(a)
            if rem_p:  # omit when empty: keeps params/axes trees congruent
                params["rem_layers"] = tuple(rem_p)
                axes["rem_layers"] = tuple(rem_a)
        elif fam == "encdec":
            params["enc_layers"], axes["enc_layers"] = stack_layers(
                b.next_key(), cfg.n_enc_layers,
                functools.partial(_build_attn_mlp_layer, cfg=cfg,
                                  norm_kind="ln"))
            enb = ParamBuilder(b.next_key())
            params["enc_norm"] = _init_norm(enb, cfg.d_model, "ln", "en")
            axes["enc_norm"] = _norm_axes("ln")
            params["layers"], axes["layers"] = stack_layers(
                b.next_key(), cfg.n_layers,
                functools.partial(_build_decoder_xattn_layer, cfg=cfg))
        else:
            raise ValueError(fam)
        return params, axes

    # ----- layer application -------------------------------------------------
    def _attn_mlp_layer(self, p: Params, x: jax.Array, positions, *,
                        causal=True, window=None, prefix=0, kv_x=None,
                        aux_carry=None, rope=True):
        cfg = self.cfg
        h = _norm(p["ln1"], x, "rms" if self.norm_kind == "rms" else "ln",
                  cfg.norm_eps)
        out = attn_lib.attention(p["attn"], h, cfg, positions=positions,
                                 causal=causal, window=window, rope=rope,
                                 bidirectional_prefix=prefix, kv_x=kv_x)
        # §Perf H6: barrier keeps the TP partial-sum all-reduce in bf16
        # (the downstream norm's f32 convert otherwise hoists before it).
        x = x + jax.lax.optimization_barrier(out)
        h = _norm(p["ln2"], x, "rms" if self.norm_kind == "rms" else "ln",
                  cfg.norm_eps)
        if cfg.family == "moe":
            y, aux = moe_lib.moe_mlp(p["mlp"], h, cfg)
            x = x + jax.lax.optimization_barrier(y)
            if aux_carry is not None:
                aux_carry = aux_carry + aux
        else:
            x = x + jax.lax.optimization_barrier(
                mlp(p["mlp"], h, cfg.mlp_type))
        x = partition.constrain(x, ("batch", "seq", "act_embed"))
        return x, aux_carry

    def _hybrid_layer(self, p: Params, x, positions, kind: str):
        cfg = self.cfg
        h = _norm(p["ln1"], x, "rms", cfg.norm_eps)
        if kind == "rec":
            x = x + rglru_lib.recurrent_block(p["block"], h, cfg)
        else:
            x = x + attn_lib.attention(p["block"], h, cfg, positions=positions,
                                       causal=True, window=cfg.local_window)
        h = _norm(p["ln2"], x, "rms", cfg.norm_eps)
        x = x + mlp(p["mlp"], h, cfg.mlp_type)
        return partition.constrain(x, ("batch", "seq", "act_embed"))

    # ----- forward (training) -------------------------------------------------
    def forward(self, params: Params, batch: Dict[str, jax.Array], *,
                remat: bool = True) -> Tuple[jax.Array, jax.Array]:
        """Returns (pre-head hidden states [B, S, d], aux loss scalar)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed_lookup(params["embed"], tokens)
        if cfg.family == "vlm":
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x[:, cfg.n_patches:]], axis=1)
        positions = jnp.arange(S)[None, :]
        aux0 = jnp.zeros((), jnp.float32)

        fam = cfg.family
        if fam in ("dense", "vlm", "moe"):
            prefix = cfg.n_patches if fam == "vlm" else 0

            def body(carry, p):
                # optimization_barrier: stops XLA convert-motion from
                # stashing the remat carry as f32 (2x stash memory).
                x, aux = jax.lax.optimization_barrier(carry)
                p = self._constrain_layer(p)
                x, aux = self._attn_mlp_layer(p, x, positions,
                                              window=cfg.sliding_window,
                                              prefix=prefix, aux_carry=aux)
                return (x, aux), None

            body_fn = jax.checkpoint(body) if remat else body
            (x, aux), _ = self._scan(body_fn, (x, aux0), params["layers"])
        elif fam == "ssm":
            def body(x, p):
                x = jax.lax.optimization_barrier(x)
                p = self._constrain_layer(p)
                h = _norm(p["ln"], x, "rms", cfg.norm_eps)
                x = x + ssm_lib.mamba2_block(p["mixer"], h, cfg)
                return partition.constrain(x, ("batch", "seq", "act_embed")), None

            body_fn = jax.checkpoint(body) if remat else body
            x, _ = self._scan(body_fn, x, params["layers"])
            aux = aux0
        elif fam == "hybrid":
            pattern = cfg.block_pattern

            def unit_body(x, unit):
                x = jax.lax.optimization_barrier(x)
                unit = self._constrain_layer(unit)
                for i, kind in enumerate(pattern):
                    x = self._hybrid_layer(unit[i], x, positions, kind)
                return x, None

            body_fn = jax.checkpoint(unit_body) if remat else unit_body
            x, _ = self._scan(body_fn, x, params["layers"])
            for i, p in enumerate(params.get("rem_layers", ())):
                x = self._hybrid_layer(p, x, positions, pattern[i])
            aux = aux0
        elif fam == "encdec":
            enc = self._encode(params, batch["frames"], remat=remat)

            def body(x, p):
                x = jax.lax.optimization_barrier(x)
                p = self._constrain_layer(p)
                x = self._decoder_layer(p, x, positions, enc)
                return x, None

            body_fn = jax.checkpoint(body) if remat else body
            x, _ = self._scan(body_fn, x, params["layers"])
            aux = aux0
        else:
            raise ValueError(fam)

        x = _norm(params["final_norm"], x, self.norm_kind, cfg.norm_eps)
        return x, aux

    def _encode(self, params: Params, frames: jax.Array, *,
                remat: bool = True) -> jax.Array:
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        cfg = self.cfg
        F = frames.shape[1]
        pos_table = jnp.asarray(sinusoidal_positions(F, cfg.d_model))
        x = frames.astype(COMPUTE_DTYPE) + pos_table.astype(COMPUTE_DTYPE)
        x = partition.constrain(x, ("batch", "seq", "act_embed"))

        def body(x, p):
            x = jax.lax.optimization_barrier(x)
            p = self._constrain_layer(p, "enc_layers")
            x, _ = self._attn_mlp_layer(p, x, None, causal=False, rope=False)
            return x, None

        body_fn = jax.checkpoint(body) if remat else body
        x, _ = self._scan(body_fn, x, params["enc_layers"])
        return _norm(params["enc_norm"], x, "ln", cfg.norm_eps)

    def _decoder_layer(self, p: Params, x, positions, enc):
        cfg = self.cfg
        h = _norm(p["ln1"], x, "ln", cfg.norm_eps)
        x = x + attn_lib.attention(p["self"], h, cfg, positions=positions,
                                   causal=True)
        h = _norm(p["ln2"], x, "ln", cfg.norm_eps)
        x = x + attn_lib.attention(p["cross"], h, cfg, kv_x=enc, rope=False)
        h = _norm(p["ln3"], x, "ln", cfg.norm_eps)
        x = x + mlp(p["mlp"], h, cfg.mlp_type)
        return partition.constrain(x, ("batch", "seq", "act_embed"))

    # ----- loss ----------------------------------------------------------------
    def head_matrix(self, params: Params) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["head"]

    def _mask_pad_logits(self, logits: jax.Array) -> jax.Array:
        v = self.cfg.vocab_size
        if logits.shape[-1] == v:
            return logits
        return jnp.where(jnp.arange(logits.shape[-1]) >= v, -1e30, logits)

    def loss_fn(self, params: Params, batch: Dict[str, jax.Array], *,
                remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x, aux = self.forward(params, batch, remat=remat)
        labels = batch["labels"]
        mask = batch.get("mask")
        if cfg.family == "vlm":
            pmask = (jnp.arange(labels.shape[1]) >= cfg.n_patches)[None, :]
            mask = pmask if mask is None else (mask * pmask)
        ce = chunked_cross_entropy(x, self.head_matrix(params), labels, mask,
                                   unroll=self.unroll,
                                   valid_vocab=cfg.vocab_size)
        loss = ce + 1e-2 * aux
        return loss, {"ce": ce, "aux": aux}

    # ----- decode cache ----------------------------------------------------------
    def cache_window(self, max_seq: int) -> int:
        cfg = self.cfg
        if cfg.sliding_window:
            return min(max_seq, cfg.sliding_window)
        return max_seq

    def init_cache(self, batch: int, max_seq: int):
        """Zeroed decode cache + matching logical-axes pytree."""
        cfg = self.cfg
        fam = cfg.family
        kv_axes = ("layers", "batch", "cache_seq", None, None)

        def kv(n_layers, window):
            shape = (n_layers, batch, window, cfg.n_kv_heads, cfg.head_dim_)
            return (jnp.zeros(shape, COMPUTE_DTYPE),
                    jnp.zeros(shape, COMPUTE_DTYPE))

        if fam in ("dense", "vlm", "moe"):
            W = self.cache_window(max_seq)
            k, v = kv(cfg.n_layers, W)
            return ({"k": k, "v": v}, {"k": kv_axes, "v": kv_axes})
        if fam == "ssm":
            (conv, ssm_st), (ca, sa) = ssm_lib.init_mamba2_state(cfg, batch)
            L = cfg.n_layers
            return ({"conv": jnp.broadcast_to(conv, (L,) + conv.shape),
                     "ssm": jnp.broadcast_to(ssm_st, (L,) + ssm_st.shape)},
                    {"conv": ("layers",) + ca, "ssm": ("layers",) + sa})
        if fam == "hybrid":
            pattern = cfg.block_pattern
            n_units, rem = divmod(cfg.n_layers, len(pattern))
            W = min(max_seq, cfg.local_window)
            (conv, h), (ca, ha) = rglru_lib.init_rglru_state(cfg, batch)

            def unit_cache(n):
                c, a = [], []
                for kind in pattern:
                    if kind == "rec":
                        c.append({"conv": jnp.broadcast_to(conv, (n,) + conv.shape),
                                  "h": jnp.broadcast_to(h, (n,) + h.shape)})
                        a.append({"conv": ("layers",) + ca, "h": ("layers",) + ha})
                    else:
                        kk, vv = kv(n, W)
                        c.append({"k": kk, "v": vv})
                        a.append({"k": kv_axes, "v": kv_axes})
                return tuple(c), tuple(a)

            cache, axes = unit_cache(n_units)
            rem_c, rem_a = [], []
            for i in range(rem):
                if pattern[i] == "rec":
                    rem_c.append({"conv": conv, "h": h})
                    rem_a.append({"conv": ca, "h": ha})
                else:
                    kk, vv = kv(1, W)
                    rem_c.append({"k": kk[0], "v": vv[0]})
                    rem_a.append({"k": kv_axes[1:], "v": kv_axes[1:]})
            return ({"units": cache, "rem": tuple(rem_c)},
                    {"units": axes, "rem": tuple(rem_a)})
        if fam == "encdec":
            k, v = kv(cfg.n_layers, max_seq)
            xshape = (cfg.n_layers, batch, cfg.n_frames, cfg.n_kv_heads,
                      cfg.head_dim_)
            return ({"k": k, "v": v,
                     "xk": jnp.zeros(xshape, COMPUTE_DTYPE),
                     "xv": jnp.zeros(xshape, COMPUTE_DTYPE)},
                    {"k": kv_axes, "v": kv_axes,
                     "xk": ("layers", "batch", None, None, None),
                     "xv": ("layers", "batch", None, None, None)})
        raise ValueError(fam)

    # ----- prefill -----------------------------------------------------------
    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                max_seq: int):
        """Process a prompt, return (last-token logits [B, V], cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed_lookup(params["embed"], tokens)
        if cfg.family == "vlm":
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x[:, cfg.n_patches:]], axis=1)
        positions = jnp.arange(S)[None, :]
        fam = cfg.family

        if fam in ("dense", "vlm", "moe"):
            W = self.cache_window(max_seq)
            prefix = cfg.n_patches if fam == "vlm" else 0

            def body(x, p):
                h = _norm(p["ln1"], x, "rms", cfg.norm_eps)
                out, (k, v) = attn_lib.attention_with_kv(
                    p["attn"], h, cfg, positions=positions,
                    window=cfg.sliding_window, bidirectional_prefix=prefix)
                x = x + out
                h = _norm(p["ln2"], x, "rms", cfg.norm_eps)
                if fam == "moe":
                    y, _ = moe_lib.moe_mlp(p["mlp"], h, cfg)
                    x = x + y
                else:
                    x = x + mlp(p["mlp"], h, cfg.mlp_type)
                x = partition.constrain(x, ("batch", "seq", "act_embed"))
                return x, attn_lib.pack_cache(k, v, W)

            x, kvs = self._scan(body, x, params["layers"])
            cache = {"k": kvs[0], "v": kvs[1]}
        elif fam == "ssm":
            def body(x, p):
                h = _norm(p["ln"], x, "rms", cfg.norm_eps)
                out, st = ssm_lib.mamba2_block(p["mixer"], h, cfg,
                                               return_state=True)
                x = partition.constrain(x + out, ("batch", "seq", "act_embed"))
                return x, st

            x, (convs, ssms) = self._scan(body, x, params["layers"])
            cache = {"conv": convs, "ssm": ssms}
        elif fam == "hybrid":
            pattern = cfg.block_pattern
            W = min(max_seq, cfg.local_window)

            def apply_layer(p, x, kind):
                h = _norm(p["ln1"], x, "rms", cfg.norm_eps)
                if kind == "rec":
                    out, st = rglru_lib.recurrent_block(p["block"], h, cfg,
                                                        return_state=True)
                    st = {"conv": st[0], "h": st[1]}
                else:
                    out, (k, v) = attn_lib.attention_with_kv(
                        p["block"], h, cfg, positions=positions,
                        window=cfg.local_window)
                    k, v = attn_lib.pack_cache(k, v, W)
                    st = {"k": k, "v": v}
                x = x + out
                h = _norm(p["ln2"], x, "rms", cfg.norm_eps)
                x = x + mlp(p["mlp"], h, cfg.mlp_type)
                return partition.constrain(x, ("batch", "seq", "act_embed")), st

            def unit_body(x, unit):
                sts = []
                for i, kind in enumerate(pattern):
                    x, st = apply_layer(unit[i], x, kind)
                    sts.append(st)
                return x, tuple(sts)

            x, unit_caches = self._scan(unit_body, x, params["layers"])
            rem_caches = []
            for i, p in enumerate(params.get("rem_layers", ())):
                x, st = apply_layer(p, x, pattern[i])
                rem_caches.append(st)
            cache = {"units": unit_caches, "rem": tuple(rem_caches)}
        elif fam == "encdec":
            enc = self._encode(params, batch["frames"], remat=False)

            def body(x, p):
                h = _norm(p["ln1"], x, "ln", cfg.norm_eps)
                out, (k, v) = attn_lib.attention_with_kv(
                    p["self"], h, cfg, positions=positions)
                x = x + out
                h = _norm(p["ln2"], x, "ln", cfg.norm_eps)
                xk, xv = attn_lib.project_kv(p["cross"], enc, cfg)
                x = x + attn_lib.attention(p["cross"], h, cfg, kv_x=enc,
                                           rope=False)
                h = _norm(p["ln3"], x, "ln", cfg.norm_eps)
                x = x + mlp(p["mlp"], h, cfg.mlp_type)
                x = partition.constrain(x, ("batch", "seq", "act_embed"))
                k, v = attn_lib.pack_cache(k, v, max_seq)
                return x, (k, v, xk, xv)

            x, (ks, vs, xks, xvs) = self._scan(body, x, params["layers"])
            cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs}
        else:
            raise ValueError(fam)

        x = _norm(params["final_norm"], x, self.norm_kind, cfg.norm_eps)
        logits = (x[:, -1] @ self.head_matrix(params).astype(COMPUTE_DTYPE))
        return self._mask_pad_logits(logits.astype(jnp.float32)), cache

    # ----- decode -------------------------------------------------------------
    def decode_step(self, params: Params, cache, token: jax.Array,
                    pos: jax.Array):
        """One token.  token: [B] int32; pos: scalar int32 (current length).

        Returns (logits [B, V], new cache)."""
        cfg = self.cfg
        B = token.shape[0]
        x = embed_lookup(params["embed"], token[:, None])[:, 0]   # [B, d]
        fam = cfg.family

        if fam in ("dense", "vlm", "moe"):
            W = cache["k"].shape[2]

            def body(x, layer):
                # barrier: keeps per-layer weight/cache casts inside the
                # loop (CPU hoists them into whole-stack f32 copies).
                p, k, v = jax.lax.optimization_barrier(layer)
                h = _norm(p["ln1"], x[:, None], "rms", cfg.norm_eps)[:, 0]
                out, k, v = attn_lib.decode_attn(p["attn"], h, cfg, k, v, pos, W)
                x = x + out
                h = _norm(p["ln2"], x[:, None], "rms", cfg.norm_eps)
                if fam == "moe":
                    y, _ = moe_lib.moe_mlp(p["mlp"], h, cfg)
                else:
                    y = mlp(p["mlp"], h, cfg.mlp_type)
                return x + y[:, 0], (k, v)

            x, (ks, vs) = self._scan(body, x,
                                       (params["layers"], cache["k"],
                                        cache["v"]))
            new_cache = {"k": ks, "v": vs}
        elif fam == "ssm":
            def body(x, layer):
                p, conv, ssm_st = jax.lax.optimization_barrier(layer)
                h = _norm(p["ln"], x[:, None], "rms", cfg.norm_eps)[:, 0]
                out, (conv, ssm_st) = ssm_lib.mamba2_decode(
                    p["mixer"], h, cfg, (conv, ssm_st))
                return x + out, (conv, ssm_st)

            x, (convs, ssms) = self._scan(
                body, x, (params["layers"], cache["conv"], cache["ssm"]))
            new_cache = {"conv": convs, "ssm": ssms}
        elif fam == "hybrid":
            pattern = cfg.block_pattern

            def apply_layer(p, x, kind, st):
                h = _norm(p["ln1"], x[:, None], "rms", cfg.norm_eps)[:, 0]
                if kind == "rec":
                    out, (conv, hst) = rglru_lib.recurrent_block_decode(
                        p["block"], h, cfg, (st["conv"], st["h"]))
                    st = {"conv": conv, "h": hst}
                else:
                    W = st["k"].shape[1]
                    out, k, v = attn_lib.decode_attn(p["block"], h, cfg,
                                                     st["k"], st["v"], pos, W)
                    st = {"k": k, "v": v}
                x = x + out
                h = _norm(p["ln2"], x[:, None], "rms", cfg.norm_eps)
                x = x + mlp(p["mlp"], h, cfg.mlp_type)[:, 0]
                return x, st

            def unit_body(x, unit):
                ps, sts = jax.lax.optimization_barrier(unit)
                new = []
                for i, kind in enumerate(pattern):
                    x, st = apply_layer(ps[i], x, kind, sts[i])
                    new.append(st)
                return x, tuple(new)

            x, units = self._scan(unit_body, x,
                                    (params["layers"], cache["units"]))
            rem = []
            for i, p in enumerate(params.get("rem_layers", ())):
                x, st = apply_layer(p, x, pattern[i], cache["rem"][i])
                rem.append(st)
            new_cache = {"units": units, "rem": tuple(rem)}
        elif fam == "encdec":
            W = cache["k"].shape[2]

            def body(x, layer):
                p, k, v, xk, xv = jax.lax.optimization_barrier(layer)
                h = _norm(p["ln1"], x[:, None], "ln", cfg.norm_eps)[:, 0]
                out, k, v = attn_lib.decode_attn(p["self"], h, cfg, k, v, pos, W)
                x = x + out
                h = _norm(p["ln2"], x[:, None], "ln", cfg.norm_eps)[:, 0]
                out = attn_lib.decode_cross_attn(p["cross"], h, cfg, xk, xv)
                x = x + out
                h = _norm(p["ln3"], x[:, None], "ln", cfg.norm_eps)
                x = x + mlp(p["mlp"], h, cfg.mlp_type)[:, 0]
                return x, (k, v)

            x, (ks, vs) = self._scan(
                body, x, (params["layers"], cache["k"], cache["v"],
                          cache["xk"], cache["xv"]))
            new_cache = {"k": ks, "v": vs, "xk": cache["xk"],
                         "xv": cache["xv"]}
        else:
            raise ValueError(fam)

        x = _norm(params["final_norm"], x[:, None], self.norm_kind,
                  cfg.norm_eps)[:, 0]
        logits = (x @ self.head_matrix(params).astype(COMPUTE_DTYPE))
        logits = partition.constrain(logits.astype(jnp.float32),
                                     ("batch", "vocab"))
        return self._mask_pad_logits(logits), new_cache
