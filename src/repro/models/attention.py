"""Attention: blockwise (flash-style) training/prefill path, GQA/SWA/bias
variants, and a sequence-sharded flash-decode for serving.

Training/prefill use a pure-JAX blockwise softmax-rescaling scan over KV
chunks: O(S * chunk) live memory instead of O(S^2), which is what makes the
32k-prefill cells compile inside per-chip HBM.  The same algorithm is the
oracle for the Pallas ``flash_attention`` kernel (``repro/kernels``).

Decode shards the KV cache over the *model* mesh axis on the sequence dim
(``cache_seq`` logical axis).  Each shard computes a local
(max, sum-exp, weighted-V) triple and the result is combined with
``psum``/``pmax`` inside ``shard_map`` - no kv-head divisibility constraint
(kv = 1..16 all work on a 16-wide model axis) and per-chip cache bytes are
bounded.  Cache insertion is ownership-masked ``dynamic_update_slice`` so no
collective touches the cache on the hot path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import partition
from repro.models.config import ModelConfig
from repro.models.layers import COMPUTE_DTYPE, ParamBuilder, Params, apply_rope

NEG_INF = -1e30
DEFAULT_CHUNK = 1024


def _shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def init_attention(b: ParamBuilder, cfg: ModelConfig, d_in: Optional[int] = None) -> Params:
    d = d_in or cfg.d_model
    p = {
        "wq": b.param("wq", (d, cfg.q_dim), ("embed", "heads")),
        "wk": b.param("wk", (d, cfg.kv_dim), ("embed", "kv")),
        "wv": b.param("wv", (d, cfg.kv_dim), ("embed", "kv")),
        "wo": b.param("wo", (cfg.q_dim, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = b.param("bq", (cfg.q_dim,), ("heads",), init="zeros")
        p["bk"] = b.param("bk", (cfg.kv_dim,), ("kv",), init="zeros")
        p["bv"] = b.param("bv", (cfg.kv_dim,), ("kv",), init="zeros")
    return p


def _project_qkv(params: Params, x: jax.Array, cfg: ModelConfig,
                 positions: Optional[jax.Array], rope: bool = True):
    B, S, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = x @ partition.wcast(params["wq"], COMPUTE_DTYPE, ("embed", "heads"))
    k = x @ partition.wcast(params["wk"], COMPUTE_DTYPE, ("embed", "kv"))
    v = x @ partition.wcast(params["wv"], COMPUTE_DTYPE, ("embed", "kv"))
    if "bq" in params:
        q = q + params["bq"].astype(COMPUTE_DTYPE)
        k = k + params["bk"].astype(COMPUTE_DTYPE)
        v = v + params["bv"].astype(COMPUTE_DTYPE)
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KV, dh)
    v = v.reshape(B, S, KV, dh)
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _pick_chunk(s: int, chunk: int) -> Tuple[int, int]:
    """Pick a block size and (possibly padded) length for ``s``.

    Prefers the largest divisor of ``s`` in (chunk/2, chunk]; if none
    exists, keeps ``chunk`` and pads ``s`` up to a multiple (padded keys are
    masked, padded queries sliced away).  Never lets the block collapse to a
    tiny divisor — that would unroll O((s/c)^2) blocks at trace time."""
    if s <= chunk:
        return s, s
    for c in range(chunk, chunk // 2, -1):
        if s % c == 0:
            return c, s
    pad = -(-s // chunk) * chunk
    return chunk, pad


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, window: Optional[int] = None,
                        chunk: int = DEFAULT_CHUNK,
                        bidirectional_prefix: int = 0) -> jax.Array:
    """Block attention with *static* block skipping.

    q: [B, Sq, H, dh]; k/v: [B, Sk, KV, dh]  (H = KV * group).
    Both q and kv are split into chunks; for each q chunk only the causally /
    window-wise reachable kv chunks are computed (running-max softmax
    rescaling combines them).  The loops are unrolled in Python with static
    chunk indices, so (a) fully-masked blocks cost **zero** HLO FLOPs - no 2x
    causal waste - and (b) ``cost_analysis()`` counts attention exactly (no
    while-loop undercount).  Live memory is O(Cq * Ck) per block.

    ``bidirectional_prefix``: positions < prefix attend bidirectionally (VLM
    image prefix; must fit the first chunk).  Returns [B, Sq, H, dh].
    """
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    cq, sq_pad = _pick_chunk(Sq, chunk)
    ck, sk_pad = _pick_chunk(Sk, chunk)
    if sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - Sq), (0, 0), (0, 0)))
    if sk_pad != Sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - Sk), (0, 0), (0, 0)))
    kv_limit = Sk if sk_pad != Sk else None   # mask padded keys
    nq, nk = sq_pad // cq, sk_pad // ck
    assert bidirectional_prefix <= cq or nq == 1, "prefix must fit one chunk"
    scale = dh ** -0.5
    qg = q.reshape(B, nq, cq, KV, g, dh).astype(COMPUTE_DTYPE)
    kc = k.reshape(B, nk, ck, KV, dh).astype(COMPUTE_DTYPE)
    vc = v.reshape(B, nk, ck, KV, dh).astype(COMPUTE_DTYPE)

    out_chunks = []
    for qi in range(nq):
        q_lo, q_hi = qi * cq, (qi + 1) * cq
        q_pos = jnp.arange(q_lo, q_hi)
        m = jnp.full((B, KV, g, cq), NEG_INF, jnp.float32)
        l = jnp.zeros((B, KV, g, cq), jnp.float32)
        o = jnp.zeros((B, KV, g, cq, dh), jnp.float32)
        for kj in range(nk):
            k_lo, k_hi = kj * ck, (kj + 1) * ck
            if causal and k_lo > q_hi - 1:
                continue  # strictly-upper block: statically skipped
            if window is not None and k_hi - 1 < q_lo - window + 1 \
                    and not (bidirectional_prefix and k_lo < bidirectional_prefix):
                continue  # outside the sliding window: statically skipped
            k_pos = jnp.arange(k_lo, k_hi)
            s = jnp.einsum("bqkgd,bckd->bkgqc", qg[:, qi], kc[:, kj],
                           preferred_element_type=jnp.float32) * scale
            mask = None
            if causal and k_hi > q_lo:  # diagonal-crossing block
                mask = q_pos[:, None] >= k_pos[None, :]
                if bidirectional_prefix:
                    bidir = (q_pos[:, None] < bidirectional_prefix) & \
                            (k_pos[None, :] < bidirectional_prefix)
                    mask = mask | bidir
            if window is not None and k_lo <= q_hi - window:
                wmask = q_pos[:, None] - k_pos[None, :] < window
                if bidirectional_prefix:
                    wmask = wmask | (k_pos[None, :] < bidirectional_prefix)
                mask = wmask if mask is None else (mask & wmask)
            if kv_limit is not None and k_hi > kv_limit:
                vmask = jnp.broadcast_to(k_pos[None, :] < kv_limit, (cq, ck))
                mask = vmask if mask is None else (mask & vmask)
            if mask is not None:
                s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(COMPUTE_DTYPE),
                            vc[:, kj], preferred_element_type=jnp.float32)
            o = o * corr[..., None] + pv
            m = m_new
        out_chunks.append(o / jnp.maximum(l, 1e-30)[..., None])
    out = jnp.stack(out_chunks, axis=1)  # [B, nq, KV, g, cq, dh]
    out = out.transpose(0, 1, 4, 2, 3, 5).reshape(B, sq_pad, H, dh)
    if sq_pad != Sq:
        out = out[:, :Sq]
    return out.astype(q.dtype)


def attention(params: Params, x: jax.Array, cfg: ModelConfig, *,
              positions: Optional[jax.Array] = None, causal: bool = True,
              window: Optional[int] = None, rope: bool = True,
              bidirectional_prefix: int = 0,
              kv_x: Optional[jax.Array] = None) -> jax.Array:
    """Full attention block (projections + blockwise core + output proj).

    ``kv_x`` switches to cross-attention (keys/values from the encoder)."""
    B, S, _ = x.shape
    if kv_x is None:
        q, k, v = _project_qkv(params, x, cfg, positions, rope)
    else:
        q, _, _ = _project_qkv(params, x, cfg, positions, rope=False)
        k, v = project_kv(params, kv_x, cfg)
        causal = False
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              bidirectional_prefix=bidirectional_prefix)
    out = partition.constrain(out.reshape(B, S, cfg.q_dim),
                              ("batch", "seq", "heads"))
    return out @ partition.wcast(params["wo"], COMPUTE_DTYPE,
                                 ("heads", "embed"))


def project_kv(params: Params, kv_x: jax.Array, cfg: ModelConfig):
    """Project keys/values (no rope) from encoder states: [B, Sk, KV, dh]."""
    B, Sk, _ = kv_x.shape
    k = (kv_x @ params["wk"].astype(COMPUTE_DTYPE))
    v = (kv_x @ params["wv"].astype(COMPUTE_DTYPE))
    if "bk" in params:
        k = k + params["bk"].astype(COMPUTE_DTYPE)
        v = v + params["bv"].astype(COMPUTE_DTYPE)
    return (k.reshape(B, Sk, cfg.n_kv_heads, cfg.head_dim_),
            v.reshape(B, Sk, cfg.n_kv_heads, cfg.head_dim_))


def attention_with_kv(params: Params, x: jax.Array, cfg: ModelConfig, *,
                      positions: Optional[jax.Array] = None,
                      causal: bool = True, window: Optional[int] = None,
                      rope: bool = True, bidirectional_prefix: int = 0):
    """Like :func:`attention` but also returns the (post-rope) K/V for the
    decode cache: (out [B, S, d], (k, v) each [B, S, KV, dh])."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions, rope)
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              bidirectional_prefix=bidirectional_prefix)
    out = partition.constrain(out.reshape(B, S, cfg.q_dim),
                              ("batch", "seq", "heads"))
    return out @ partition.wcast(params["wo"], COMPUTE_DTYPE,
                                 ("heads", "embed")), (k, v)


def pack_cache(k: jax.Array, v: jax.Array, window: int):
    """Lay prefill K/V [B, S, KV, dh] out as a ring cache of ``window`` slots.

    Slot convention is ``slot = pos % window`` (matching the decode insert),
    so for S >= window the last ``window`` tokens land rotated by S % window;
    for S < window tokens sit at slots [0, S) with zero padding above."""

    def one(c):
        B, S = c.shape[:2]
        if S >= window:
            tail = c[:, S - window:]
            return jnp.roll(tail, shift=S % window, axis=1)
        pad = [(0, 0)] * c.ndim
        pad[1] = (0, window - S)
        return jnp.pad(c, pad)

    return one(k), one(v)


def decode_attn(params: Params, x: jax.Array, cfg: ModelConfig,
                k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array,
                window: int):
    """One-token self-attention against a ring cache.

    x: [B, d]; k/v_cache: [B, W, KV, dh]; pos: scalar (current position).
    Returns (out [B, d], new k_cache, new v_cache)."""
    B = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos)[None, None], (B, 1))
    q, k, v = _project_qkv(params, x[:, None], cfg, posb, rope=True)
    k_cache = cache_insert(k_cache, k[:, 0], pos, ring=window)
    v_cache = cache_insert(v_cache, v[:, 0], pos, ring=window)
    eff_len = jnp.minimum(pos + 1, window)
    out = decode_attention_sharded(q[:, 0], k_cache, v_cache, eff_len)
    out = out.reshape(B, cfg.q_dim)
    return out @ params["wo"].astype(COMPUTE_DTYPE), k_cache, v_cache


def decode_cross_attn(params: Params, x: jax.Array, cfg: ModelConfig,
                      xk: jax.Array, xv: jax.Array) -> jax.Array:
    """One-token cross-attention over a fixed encoder cache.

    x: [B, d]; xk/xv: [B, F, KV, dh] (replicated over model axis)."""
    B = x.shape[0]
    q, _, _ = _project_qkv(params, x[:, None], cfg, None, rope=False)
    q = q[:, 0]                                        # [B, H, dh]
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    qg = q.reshape(B, KV, H // KV, dh)
    s = jnp.einsum("bkgd,bfkd->bkgf", qg.astype(COMPUTE_DTYPE),
                   xk.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32) * (dh ** -0.5)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgf,bfkd->bkgd", p.astype(COMPUTE_DTYPE),
                   xv.astype(COMPUTE_DTYPE), preferred_element_type=jnp.float32)
    out = o.reshape(B, cfg.q_dim).astype(x.dtype)
    return out @ params["wo"].astype(COMPUTE_DTYPE)


# ---------------------------------------------------------------------------
# Decode: sequence-sharded KV cache (flash-decode).
# ---------------------------------------------------------------------------


def _local_decode(q, k, v, cache_len, shard_idx, n_shards, s_local, window):
    """One shard's decode-attention partial: returns (o, l, m) un-normalized.

    q: [B, H, dh] local; k/v: [B, s_local, KV, dh] local slice of the cache.
    Positions covered: [shard_idx * s_local, ...).
    """
    B, H, dh = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, KV, g, dh)
    pos = shard_idx * s_local + jnp.arange(s_local)
    valid = pos < cache_len
    if window is not None:
        valid = valid & (pos >= cache_len - window)
    s = jnp.einsum("bkgd,bckd->bkgc", qg.astype(COMPUTE_DTYPE),
                   k.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32) * (dh ** -0.5)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                          # [B, KV, g]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgc,bckd->bkgd", p.astype(COMPUTE_DTYPE),
                   v.astype(COMPUTE_DTYPE), preferred_element_type=jnp.float32)
    return o, l, m


def decode_attention_sharded(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, cache_len: jax.Array,
                             window: Optional[int] = None) -> jax.Array:
    """Flash-decode over a seq-sharded cache.  q: [B, H, dh];
    k/v_cache: [B, S, KV, dh] sharded on S over the model axis."""
    rules = partition.current_rules()
    axis = rules.axis("cache_seq") if rules is not None else None
    if axis is None:
        o, l, m = _local_decode(q, k_cache, v_cache, cache_len, 0,
                                1, k_cache.shape[1], window)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        B, H, dh = q.shape
        return out.reshape(B, H, dh).astype(q.dtype)

    mesh = rules.mesh
    n_shards = mesh.shape[axis]
    S = k_cache.shape[1]
    s_local = S // n_shards
    batch = rules.axis("batch")
    qspec = P(batch, None, None)
    cspec = P(batch, axis, None, None)

    def body(q, k, v, cache_len):
        idx = jax.lax.axis_index(axis)
        o, l, m = _local_decode(q, k, v, cache_len, idx, n_shards,
                                s_local, window)
        m_glob = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(l * corr, axis)
        o_glob = jax.lax.psum(o * corr[..., None], axis)
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        B, KV, g, dh = out.shape
        return out.reshape(B, KV * g, dh).astype(q.dtype)

    return _shard_map(
        body, mesh=mesh, in_specs=(qspec, cspec, cspec, P()),
        out_specs=qspec,
    )(q, k_cache, v_cache, cache_len)


def cache_insert(cache: jax.Array, new: jax.Array, pos: jax.Array,
                 ring: Optional[int] = None) -> jax.Array:
    """Insert one token's K or V at position ``pos`` (mod ring size if a
    sliding-window ring buffer).  cache: [B, S, KV, dh]; new: [B, KV, dh].

    With a seq-sharded cache the insert runs inside shard_map: the owning
    shard does a local dynamic_update_slice, the rest keep their slice."""
    S = cache.shape[1]
    tgt = pos % ring if ring is not None else pos
    rules = partition.current_rules()
    axis = rules.axis("cache_seq") if rules is not None else None

    def local_insert(c, n, owner_base, s_local):
        rel = tgt - owner_base
        owns = (rel >= 0) & (rel < s_local)
        rel_c = jnp.clip(rel, 0, s_local - 1)
        upd = jax.lax.dynamic_update_slice(
            c, n[:, None].astype(c.dtype), (0, rel_c, 0, 0))
        return jnp.where(owns, upd, c)

    if axis is None:
        return local_insert(cache, new, 0, S)

    mesh = rules.mesh
    s_local = S // mesh.shape[axis]
    batch = rules.axis("batch")
    cspec = P(batch, axis, None, None)
    nspec = P(batch, None, None)

    def body(c, n):
        base = jax.lax.axis_index(axis) * s_local
        return local_insert(c, n, base, s_local)

    return _shard_map(body, mesh=mesh, in_specs=(cspec, nspec),
                      out_specs=cspec)(cache, new)


def init_decode_cache(cfg: ModelConfig, n_layers: int, batch: int,
                      max_seq: int, window: Optional[int] = None):
    """Zeroed stacked KV cache [L, B, W, KV, dh] (+ axes tuple)."""
    W = min(max_seq, window) if window else max_seq
    shape = (n_layers, batch, W, cfg.n_kv_heads, cfg.head_dim_)
    axes = ("layers", "batch", "cache_seq", None, None)
    return (jnp.zeros(shape, COMPUTE_DTYPE), jnp.zeros(shape, COMPUTE_DTYPE)), axes
