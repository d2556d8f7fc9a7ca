"""Unified LM assembly for every assigned architecture.

One ``init_lm`` / ``forward`` pair covers:
  * dense decoder-only (gemma3 local:global GQA, minitron, olmo),
  * MoE (deepseek-moe fine-grained shared+routed, grok),
  * attention-free RWKV6,
  * hybrid parallel attn+Mamba (hymba),
  * encoder-decoder audio (whisper; stub conv frontend supplies frame
    embeddings per the assignment),
  * VLM with interleaved cross-attention layers (llama-3.2-vision; stub
    patch embeddings).

The layer stack is ``jax.lax.scan`` over stacked per-layer params so HLO size
is O(1) in depth (62-layer configs compile like 2-layer ones). Per-layer
structural variation rides as data: a bool ``is_global`` selects full vs
sliding-window masks with identical FLOPs; VLM cross-attention uses a group
scan (`cross_attn_every` layers per group, the last cross-attends) so FLOPs
match the real architecture exactly.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.axes import constrain
from repro.models import layers as L
from repro.models import ssm as S

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, dtype, *, cross: bool) -> Params:
    """One decoder layer's params (uniform structure for the scan)."""
    ks = jax.random.split(key, 8)
    p: Params = {"ln1": L.init_norm(ks[0], cfg, cfg.d_model, dtype),
                 "ln2": L.init_norm(ks[1], cfg, cfg.d_model, dtype)}
    if cfg.family == "ssm":  # rwkv: time-mix + channel-mix
        p["time_mix"] = S.init_rwkv_time_mix(ks[2], cfg, dtype)
        p["channel_mix"] = S.init_rwkv_channel_mix(ks[3], cfg, dtype)
        return p
    p["attn"] = L.init_attention(ks[2], cfg, dtype)
    if cfg.parallel_ssm:
        p["mamba"] = S.init_mamba(ks[3], cfg, dtype)
        p["beta_attn"] = jnp.ones((cfg.d_model,), dtype)
        p["beta_ssm"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.moe is not None:
        p["moe"] = L.init_moe(ks[4], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[4], cfg, dtype)
    if cross:
        p["ln_cross"] = L.init_norm(ks[5], cfg, cfg.d_model, dtype)
        p["cross"] = L.init_cross_attention(ks[6], cfg, cfg.d_model, dtype)
        p["cross_gate"] = jnp.zeros((1,), dtype)  # llama-vision gated xattn
    if cfg.encoder is not None and cross:
        pass
    return p


def _init_encoder(key, cfg: ModelConfig, dtype) -> Params:
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    enc = cfg.encoder
    import dataclasses

    enc_cfg = dataclasses.replace(
        cfg, d_model=enc.d_model, n_heads=enc.n_heads,
        n_kv_heads=enc.n_heads, d_ff=enc.d_ff, head_dim=0,
        moe=None, parallel_ssm=False, family="dense",
        norm_type="layernorm", act="gelu",
    )
    def one(k):
        ks = jax.random.split(k, 4)
        return {
            "ln1": L.init_norm(ks[0], enc_cfg, enc.d_model, dtype),
            "attn": L.init_attention(ks[1], enc_cfg, dtype),
            "ln2": L.init_norm(ks[2], enc_cfg, enc.d_model, dtype),
            "mlp": L.init_mlp(ks[3], enc_cfg, dtype),
        }
    keys = jax.random.split(key, enc.n_layers + 2)
    stacked = jax.vmap(one)(keys[:enc.n_layers])
    return {
        "layers": stacked,
        "ln_f": L.init_norm(keys[-2], enc_cfg, enc.d_model, dtype),
        # project encoder width to decoder width if they differ
        "proj": (
            L._dense_init(keys[-1], enc.d_model,
                          (enc.d_model, cfg.d_model), dtype)
            if enc.d_model != cfg.d_model else None
        ),
    }


def init_lm(key, cfg: ModelConfig) -> Params:
    dtype = _dtype(cfg)
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(key)  # accept legacy raw keys
    k_emb, k_layers, k_enc, k_f, k_head = jax.random.split(key, 5)

    p: Params = {
        "embed": L._dense_init(
            k_emb, cfg.d_model, (cfg.vocab, cfg.d_model), dtype
        ),
        "ln_f": L.init_norm(k_f, cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L._dense_init(
            k_head, cfg.d_model, (cfg.d_model, cfg.vocab), dtype
        )

    n = cfg.n_layers
    if cfg.cross_attn_every > 0:
        # VLM group scan: [n_groups, group_size] param stacking; only the
        # last layer of each group carries cross-attention params.
        g = cfg.cross_attn_every
        assert n % g == 0, (n, g)
        n_groups = n // g
        keys = jax.random.split(k_layers, n).reshape(n_groups, g)

        def one_group(gkeys):
            plain = jax.vmap(
                lambda kk: _init_block(kk, cfg, dtype, cross=False)
            )(gkeys[: g - 1])
            last = _init_block(gkeys[g - 1], cfg, dtype, cross=True)
            return {"plain": plain, "cross_layer": last}

        p["groups"] = jax.vmap(one_group)(keys)
    else:
        cross = cfg.encoder is not None  # whisper: every decoder layer
        keys = jax.random.split(k_layers, n)
        p["layers"] = jax.vmap(
            lambda kk: _init_block(kk, cfg, dtype, cross=cross)
        )(keys)
    if cfg.encoder is not None:
        p["encoder"] = _init_encoder(k_enc, cfg, dtype)
    return p


def init_lm_on_device(cfg: ModelConfig, seed: int = 0) -> Params:
    """Random weights made by ONE jitted program on the default device, in
    ``cfg.param_dtype``: a published-width model never exists as a float32
    copy (the normal draws fuse into the cast)."""
    return jax.jit(init_lm, static_argnums=1)(jax.random.PRNGKey(seed), cfg)


# --------------------------------------------------------------------------
# Cache
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, *, per_slot_pos: bool = False,
               kv_store: str = "fp") -> Params:
    """Decode state for every family; entries have a leading layer dim so the
    layer scan threads them as xs/ys.

    ``per_slot_pos=True`` makes ``cache["pos"]`` a ``[batch]`` int32 vector
    (one write offset / valid-kv length per batch slot) instead of the
    lockstep scalar — the serving subsystem's slot-managed layout
    (DESIGN.md §8), which lets heterogeneous prompt lengths decode
    correctly in one batch.  ``forward`` accepts either form.

    ``kv_store`` selects the attention-KV storage format (DESIGN.md §12):
    ``"fp"`` (default) keeps full-precision leaves; ``"int8"`` / ``"int4"``
    store quantized pages plus per-(position, head) ``k_scale`` /
    ``v_scale`` leaves (``repro.kernels.kv_quant``), dequantized on the
    attention read path.  Recurrent state (rwkv / mamba) always stays fp —
    it is O(1) per slot, not the capacity term.
    """
    from repro.kernels.kv_quant import stored_head_dim, validate_kv_store

    validate_kv_store(kv_store)
    dtype = dtype or _dtype(cfg)
    n, d, hd = cfg.n_layers, cfg.d_model, cfg.hd
    pos_shape = (batch,) if per_slot_pos else ()
    cache: Params = {"pos": jnp.zeros(pos_shape, jnp.int32)}
    if cfg.family != "ssm":
        if kv_store == "fp":
            kv_shape = (n, batch, max_len, cfg.n_kv_heads, hd)
            cache["k"] = jnp.zeros(kv_shape, dtype)
            cache["v"] = jnp.zeros(kv_shape, dtype)
        else:
            hd_s = stored_head_dim(kv_store, hd)
            kv_shape = (n, batch, max_len, cfg.n_kv_heads, hd_s)
            sc_shape = (n, batch, max_len, cfg.n_kv_heads)
            cache["k"] = jnp.zeros(kv_shape, jnp.int8)
            cache["v"] = jnp.zeros(kv_shape, jnp.int8)
            # all-zero pages round-trip exactly under scale 1.0
            cache["k_scale"] = jnp.ones(sc_shape, jnp.float32)
            cache["v_scale"] = jnp.ones(sc_shape, jnp.float32)
    if cfg.family == "ssm":
        H, hdr = S.rwkv_heads(cfg)
        cache["rwkv_s"] = jnp.zeros((n, batch, H, hdr, hdr), jnp.float32)
        cache["rwkv_x_tm"] = jnp.zeros((n, batch, d), dtype)
        cache["rwkv_x_cm"] = jnp.zeros((n, batch, d), dtype)
    if cfg.parallel_ssm:
        di, st, cw = S.mamba_dims(cfg)
        cache["mamba_conv"] = jnp.zeros((n, batch, cw - 1, di), dtype)
        cache["mamba_h"] = jnp.zeros((n, batch, di, st), jnp.float32)
    return cache


# --------------------------------------------------------------------------
# Decode-weight prepack (one-time §V-A2 deployment cost)
# --------------------------------------------------------------------------


def _fuse_block_params(p: Params, cfg: ModelConfig) -> Params:
    """Add prepacked fused projections next to the originals in one block.

    ``wqkv`` is the [.., d, (H+2Hkv)*hd] concat of the flattened Q/K/V
    projections; ``w_gateup`` the [.., d, 2f] concat of gate and up.  The
    originals stay: prefill/training keep the einsum path (and the fused
    copies ride the same leading layer-stack dims, so `lax.scan` slices
    them per layer like any other param leaf).
    """

    def flat(w):  # [.., d, H, hd] -> [.., d, H*hd]
        return w.reshape(w.shape[:-2] + (-1,))

    p = dict(p)
    if "attn" in p:
        a = dict(p["attn"])
        a["wqkv"] = jnp.concatenate(
            [flat(a["wq"]), flat(a["wk"]), flat(a["wv"])], axis=-1
        )
        p["attn"] = a
    for mlp_key in ("mlp",):
        if mlp_key in p and "w_gate" in p[mlp_key]:
            m = dict(p[mlp_key])
            m["w_gateup"] = jnp.concatenate(
                [m["w_gate"], m["w_up"]], axis=-1
            )
            p[mlp_key] = m
    if "moe" in p and "shared" in p["moe"] and "w_gate" in p["moe"]["shared"]:
        moe = dict(p["moe"])
        sh = dict(moe["shared"])
        sh["w_gateup"] = jnp.concatenate([sh["w_gate"], sh["w_up"]], axis=-1)
        moe["shared"] = sh
        p["moe"] = moe
    return p


def prepack_decode_params(params: Params, cfg: ModelConfig,
                          mesh=None) -> Params:
    """Prepack fused QKV and MLP gate+up weights for the decode hot path.

    ``dispatch_fused`` concatenates its members at call time — under ``jit``
    that concat executes every decode step, an extra fused-weight write+read
    per token that offsets the program launch/IV amortization (ROADMAP
    follow-up).  This pays the concat ONCE at engine init (the paper's
    one-time placement/deployment cost, §V-A2); ``layers.apply_attention`` /
    ``layers.apply_mlp`` dispatch the prebuilt ``wqkv`` / ``w_gateup``
    matrices through :func:`repro.kernels.dispatch.dispatch_prepacked`
    when present.  Returns a NEW params tree (originals untouched) that is
    a drop-in for ``forward``.

    With ``mesh``, the returned tree is placed with the PIMnast mesh
    planner (``distributed.sharding.plan_params`` — the fused ``wqkv`` /
    ``w_gateup`` leaves get row placement over their concatenated output
    dim), so the spec-carrying params feed straight into a sharded
    ``forward`` without an eager replication round-trip.
    """
    if cfg.family == "ssm":
        packed = params
    else:
        packed = dict(params)
        if "layers" in packed:
            packed["layers"] = _fuse_block_params(packed["layers"], cfg)
        if "groups" in packed:
            g = dict(packed["groups"])
            g["plain"] = _fuse_block_params(g["plain"], cfg)
            g["cross_layer"] = _fuse_block_params(g["cross_layer"], cfg)
            packed["groups"] = g
    if mesh is not None:
        from repro.distributed import sharding as shd

        spec = shd.plan_params(packed, mesh, cfg)
        packed = jax.device_put(packed, shd.to_named(spec, mesh))
    return packed


# --------------------------------------------------------------------------
# Layer bodies
# --------------------------------------------------------------------------


def _self_block(
    p: Params, x, cfg: ModelConfig, positions, window,
    cache_kv, cache_pos, mamba_state=None, gemv=None, cache_scales=None,
    defer_ff=False,
):
    """attention (+ parallel mamba) + FFN/MoE with pre-norms.

    Returns ``(x, new_kv, new_state, aux, ff)``.  Normally ``x`` already
    includes the FFN residual and ``ff`` is None.  With ``defer_ff=True``
    (the deferred-collective decode path, DESIGN.md §14) ``x`` is the
    post-attention residual only and ``ff`` is the FFN output WITHOUT its
    replicated constraint — the caller adds and constrains it one layer
    later, so the FFN's cross-shard all-reduce can overlap the next
    layer's compute.
    """
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["ln1"], x, cfg)
    attn_out, new_kv = L.apply_attention(
        p["attn"], h, cfg, positions=positions, window=window,
        cache_kv=cache_kv, cache_pos=cache_pos, gemv=gemv,
        cache_scales=cache_scales,
    )
    new_state = {}
    if cfg.parallel_ssm:
        ssm_out, (new_conv, new_h) = S.apply_mamba(
            p["mamba"], h, cfg, state=mamba_state
        )
        attn_out = (
            p["beta_attn"] * attn_out + p["beta_ssm"] * ssm_out
        )
        new_state = {"mamba_conv": new_conv, "mamba_h": new_h}
    x = x + attn_out
    h = L.apply_norm(p["ln2"], x, cfg)
    if cfg.moe is not None:
        ff, aux = L.apply_moe(p["moe"], h, cfg, gemv=gemv,
                              defer_output=defer_ff)
    else:
        ff = L.apply_mlp(p["mlp"], h, cfg, gemv=gemv,
                         defer_output=defer_ff)
    if defer_ff:
        return x, new_kv, new_state, aux, ff
    x = x + ff
    return x, new_kv, new_state, aux, None


def _rwkv_block(p: Params, x, cfg: ModelConfig, cache_l):
    tm_state = None
    cm_prev = None
    if cache_l is not None:
        tm_state = (cache_l["rwkv_s"], cache_l["rwkv_x_tm"])
        cm_prev = cache_l["rwkv_x_cm"]
    h = L.apply_norm(p["ln1"], x, cfg)
    y, (new_s, new_x_tm) = S.apply_rwkv_time_mix(p["time_mix"], h, cfg,
                                                 state=tm_state)
    x = x + y
    h = L.apply_norm(p["ln2"], x, cfg)
    y, new_x_cm = S.apply_rwkv_channel_mix(p["channel_mix"], h, cfg,
                                           prev_x=cm_prev)
    x = x + y
    new_cache = {
        "rwkv_s": new_s, "rwkv_x_tm": new_x_tm, "rwkv_x_cm": new_x_cm,
    }
    return x, new_cache


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def _window_for(cfg: ModelConfig, is_global):
    """Per-layer effective window as data: 0 disables the limit."""
    if cfg.attn_pattern != "local_global":
        return 0
    return jnp.where(is_global, 0, cfg.sliding_window)


def _run_encoder(p: Params, frames: jnp.ndarray, cfg: ModelConfig):
    """Whisper encoder over stub frame embeddings [B, T, enc_d]."""
    import dataclasses

    enc = cfg.encoder
    enc_cfg = dataclasses.replace(
        cfg, d_model=enc.d_model, n_heads=enc.n_heads,
        n_kv_heads=enc.n_heads, d_ff=enc.d_ff, head_dim=0, moe=None,
        parallel_ssm=False, family="dense", norm_type="layernorm",
        act="gelu",
    )
    B, T, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def body(x, pl):
        h = L.apply_norm(pl["ln1"], x, enc_cfg)
        q = jnp.einsum("bsd,dhk->bshk", h, pl["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, pl["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, pl["attn"]["wv"])
        q = L.rope(q, positions, enc_cfg.rope_theta)
        k = L.rope(k, positions, enc_cfg.rope_theta)
        o = L.attention_core(q, k, v, q_positions=None, kv_valid_len=None,
                             window=None, causal=False)
        x = x + jnp.einsum("bshk,hkd->bsd", o, pl["attn"]["wo"])
        h = L.apply_norm(pl["ln2"], x, enc_cfg)
        x = x + L.apply_mlp(pl["mlp"], h, enc_cfg)
        return x, None

    if cfg.unroll_layers:
        x = frames
        for i in range(enc.n_layers):
            pl = jax.tree.map(lambda a: a[i], p["layers"])
            x, _ = body(x, pl)
    else:
        x, _ = jax.lax.scan(body, frames, p["layers"])
    x = L.apply_norm(p["ln_f"], x, enc_cfg)
    if p.get("proj") is not None:
        x = x @ p["proj"]
    return constrain(x, ("batch", None, None))


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,                     # [B, S] int32
    *,
    cache: Params | None = None,
    frames: jnp.ndarray | None = None,       # whisper stub embeddings
    vision: jnp.ndarray | None = None,       # vlm stub patch embeddings [B,Nv,d]
    remat: bool | None = None,
    gemv_policy=None,   # DispatchPolicy: route decode GEMVs via the dispatcher
) -> tuple[jnp.ndarray, Params | None, jnp.ndarray]:
    """Returns (logits [B, S, vocab], new_cache, aux_loss).

    ``gemv_policy`` (a ``repro.kernels.dispatch.DispatchPolicy``) engages
    the unified GEMV dispatcher for single-token (decode) projections: the
    QKV projections and MLP gate+up dispatch as **fused GEMV programs**
    (shared input vector, one launch per group), MoE expert FFNs as
    **grouped programs** over the stacked expert weights, and the MLP down
    projection and LM head as single requests.  The dispatcher resolves a
    ``GemvBackend`` (``gemv_policy.backend`` or the host platform) and that
    backend plans kernel/program per shape; ``fuse_programs=False``
    restores per-matrix dispatch.  Prefill and training shapes (Sq > 1)
    keep the plain einsum path — they are matmul-bound, not GEMV-bound.
    """
    B, Sq = tokens.shape
    dtype = jnp.dtype(cfg.compute_dtype)
    x = params["embed"][tokens].astype(dtype)
    x = x * jnp.asarray(jnp.sqrt(cfg.d_model), dtype)  # gemma-style scale
    x = constrain(x, ("batch", None, None))

    # ``pos`` is a lockstep scalar (training / legacy serving) or a [B]
    # per-slot vector (slot-managed KV cache, DESIGN.md §8): reshape to a
    # column so both broadcast to per-slot absolute positions.
    pos0 = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)
    positions = jnp.reshape(pos0, (-1, 1)) + jnp.arange(Sq)[None, :]
    positions = jnp.broadcast_to(positions, (B, Sq))

    ctx = None
    if cfg.encoder is not None:
        assert frames is not None, "whisper needs stub frame embeddings"
        ctx = _run_encoder(params["encoder"], frames.astype(dtype), cfg)
    if cfg.cross_attn_every > 0:
        assert vision is not None, "vlm needs stub patch embeddings"
        ctx = vision.astype(dtype)

    remat = cfg.remat if remat is None else remat
    layer_idx = jnp.arange(cfg.n_layers)
    is_global = (
        layer_idx % max(cfg.global_every, 1) == max(cfg.global_every, 1) - 1
        if cfg.attn_pattern == "local_global"
        else jnp.ones((cfg.n_layers,), bool)
    )

    aux_total = jnp.zeros((), jnp.float32)

    if cfg.cross_attn_every > 0:
        x, new_cache, aux_total = _forward_grouped(
            params, cfg, x, positions, ctx, cache, remat, gemv_policy
        )
    else:
        x, new_cache, aux_total = _forward_flat(
            params, cfg, x, positions, ctx, cache, is_global, remat,
            gemv_policy,
        )

    x = L.apply_norm(params["ln_f"], x, cfg)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    if gemv_policy is not None and Sq == 1:
        from repro.kernels.dispatch import dispatch_dense

        logits = dispatch_dense(x, head.astype(dtype), policy=gemv_policy)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dtype))
    logits = constrain(logits, ("batch", None, "model"))
    if new_cache is not None:
        new_cache["pos"] = pos0 + Sq
    return logits, new_cache, aux_total


def _forward_flat(params, cfg, x, positions, ctx, cache, is_global, remat,
                  gemv=None):
    """Uniform scan over layers (everything except grouped VLM).

    Deferred collectives (DESIGN.md §14): with
    ``gemv.overlap_collectives`` on a decode step, the carry additionally
    threads the previous layer's UNCONSTRAINED FFN output; it is added and
    constrained at the next layer's entry (and flushed once after the
    scan) instead of at the producing layer's exit.  The f32 add sequence
    is exactly ``((x + ff_{n-1}) + attn_n) + ...`` either way — identical
    values, but the replication point for layer n's FFN all-reduce moves
    past layer n+1's dispatch, so GSPMD may overlap them.  Gated off for
    the rwkv family (no FFN residual of this shape) and whisper (the
    cross-attention consumes the completed layer output).
    """
    decode = cache is not None
    defer = (decode and ctx is None and cfg.family != "ssm"
             and gemv is not None
             and getattr(gemv, "overlap_collectives", False))
    if defer and getattr(gemv, "model_shards", 1) > 1:
        from repro.kernels.dispatch import record_overlap

        # Trace-time telemetry (like every dispatch decision counter):
        # each layer's FFN combine is awaited one layer late.
        record_overlap("deferred", deferred_collectives=cfg.n_layers)

    def step(carry, pl, flag_global, cache_l):
        if defer:
            x, pending, aux = carry
            # Await layer n-1's FFN here: the add is the same f32 add the
            # undeferred path did at the producer, one step later.
            x = constrain(x + pending, ("batch", None, None))
        else:
            x, aux = carry
        if cfg.family == "ssm":
            x, new_cache_l = _rwkv_block(pl, x, cfg, cache_l)
            return (x, aux), (new_cache_l if decode else {})
        window = _window_for(cfg, flag_global)
        cache_kv = (cache_l["k"], cache_l["v"]) if decode else None
        cache_scales = (
            (cache_l["k_scale"], cache_l["v_scale"])
            if decode and "k_scale" in cache_l else None
        )
        cache_pos = cache["pos"] if decode else None
        mamba_state = None
        if cfg.parallel_ssm and decode:
            mamba_state = (cache_l["mamba_conv"], cache_l["mamba_h"])
        x, new_kv, new_state, aux_l, ff = _self_block(
            pl, x, cfg, positions, window, cache_kv, cache_pos,
            mamba_state=mamba_state, gemv=gemv, cache_scales=cache_scales,
            defer_ff=defer,
        )
        if ctx is not None and "cross" in pl:  # whisper decoder
            h = L.apply_norm(pl["ln_cross"], x, cfg)
            x = x + L.apply_cross_attention(pl["cross"], h, ctx, cfg)
        new_cache_l = {}
        if decode:
            if new_kv is not None:
                new_cache_l["k"], new_cache_l["v"] = new_kv[0], new_kv[1]
                if len(new_kv) == 4:  # quantized store: scale leaves ride
                    new_cache_l["k_scale"] = new_kv[2]
                    new_cache_l["v_scale"] = new_kv[3]
            new_cache_l.update(new_state)
        if defer:
            return (x, ff, aux + aux_l), new_cache_l
        x = constrain(x, ("batch", None, None))
        return (x, aux + aux_l), new_cache_l

    def init_carry():
        if defer:
            return (x, jnp.zeros_like(x), jnp.zeros((), jnp.float32))
        return (x, jnp.zeros((), jnp.float32))

    def flush(carry):
        """Final carry -> (x, aux); awaits the last layer's deferred FFN."""
        if defer:
            xc, pending, aux = carry
            return constrain(xc + pending, ("batch", None, None)), aux
        return carry

    if cfg.unroll_layers:
        # Python loop (dry-run roofline mode): every layer appears in the
        # HLO so cost_analysis counts are exact, unlike scan whose body is
        # counted once regardless of trip count (see EXPERIMENTS.md §Roofline
        # methodology).
        carry = init_carry()
        new_layers = []
        stepc = jax.checkpoint(step, static_argnums=()) if remat else step
        for i in range(cfg.n_layers):
            pl = jax.tree.map(lambda a: a[i], params["layers"])
            cache_l = (
                jax.tree.map(
                    lambda a: a[i],
                    {k: v for k, v in cache.items() if k != "pos"},
                ) if decode else None
            )
            carry, nc = stepc(carry, pl, is_global[i], cache_l)
            new_layers.append(nc)
        x, aux = flush(carry)
        if decode:
            stacked = jax.tree.map(
                lambda *ls: jnp.stack(ls), *new_layers
            )
            return x, stacked, aux
        return x, None, aux

    if decode:
        cache_xs = {k: v for k, v in cache.items() if k != "pos"}
        body = lambda c, xs: step(c, xs[0], xs[1], xs[2])
        if remat:
            body = jax.checkpoint(body)
        carry, new_cache_stacked = jax.lax.scan(
            body, init_carry(),
            (params["layers"], is_global, cache_xs),
        )
        x, aux = flush(carry)
        return x, new_cache_stacked, aux

    body = lambda c, xs: step(c, xs[0], xs[1], None)
    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["layers"], is_global),
    )
    return x, None, aux


def _forward_grouped(params, cfg, x, positions, ctx, cache, remat,
                     gemv=None):
    """VLM: scan over groups of `cross_attn_every` layers; the group's last
    layer applies gated cross-attention to the vision context."""
    g = cfg.cross_attn_every
    decode = cache is not None
    n_groups = cfg.n_layers // g
    # cache leaf names threaded through the group scan (k/v plus the
    # quantized store's scale leaves when present)
    kv_names = (
        [n for n in ("k", "v", "k_scale", "v_scale") if n in cache]
        if decode else []
    )

    def layer_step(x, pl, cache_kv, cache_pos, cross, cache_scales=None):
        window = 0
        x, new_kv, _, aux, _ = _self_block(
            pl, x, cfg, positions, window, cache_kv, cache_pos, gemv=gemv,
            cache_scales=cache_scales,
        )
        if cross:
            h = L.apply_norm(pl["ln_cross"], x, cfg)
            gate = jnp.tanh(pl["cross_gate"].astype(x.dtype))
            x = x + gate * L.apply_cross_attention(pl["cross"], h, ctx, cfg)
        x = constrain(x, ("batch", None, None))
        return x, new_kv, aux

    def body(carry, xs):
        x, aux = carry
        pg, cache_g = xs  # params for the group; cache [g, ...] slices
        new_leaves = {n: [] for n in kv_names}

        def take(nkv):
            for n, leaf in zip(kv_names, nkv):
                new_leaves[n].append(leaf)

        def args_for(i):
            if not decode:
                return None, None
            ckv = (cache_g["k"][i], cache_g["v"][i])
            cscl = (
                (cache_g["k_scale"][i], cache_g["v_scale"][i])
                if "k_scale" in cache_g else None
            )
            return ckv, cscl

        for i in range(g - 1):
            pl = jax.tree.map(lambda a: a[i], pg["plain"])
            ckv, cscl = args_for(i)
            x, nkv, a = layer_step(
                x, pl, ckv, cache["pos"] if decode else None, cross=False,
                cache_scales=cscl,
            )
            aux = aux + a
            if decode:
                take(nkv)
        ckv, cscl = args_for(g - 1)
        x, nkv, a = layer_step(
            x, pg["cross_layer"], ckv, cache["pos"] if decode else None,
            cross=True, cache_scales=cscl,
        )
        aux = aux + a
        if decode:
            take(nkv)
            new_cache_g = {
                n: jnp.stack(new_leaves[n]) for n in kv_names
            }
        else:
            new_cache_g = {}
        return (x, aux), new_cache_g

    if remat:
        body = jax.checkpoint(body)

    def grouped_cache():
        return {n: cache[n].reshape((n_groups, g) + cache[n].shape[1:])
                for n in kv_names}

    if cfg.unroll_layers:
        carry = (x, jnp.zeros((), jnp.float32))
        new_groups = []
        for gi in range(n_groups):
            pg = jax.tree.map(lambda a: a[gi], params["groups"])
            if decode:
                cg = {n: leaf[gi] for n, leaf in grouped_cache().items()}
                carry, nc = body(carry, (pg, cg))
                new_groups.append(nc)
            else:
                carry, _ = body(carry, (pg, None))
        x, aux = carry
        if decode:
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *new_groups)
            new_cache = {
                n: stacked[n].reshape(cache[n].shape) for n in kv_names
            }
            return x, new_cache, aux
        return x, None, aux

    if decode:
        (x, aux), new_c = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["groups"], grouped_cache()),
        )
        new_cache = {
            n: new_c[n].reshape(cache[n].shape) for n in kv_names
        }
        return x, new_cache, aux
    (x, aux), _ = jax.lax.scan(
        lambda c, s: body(c, (s, None)),
        (x, jnp.zeros((), jnp.float32)), params["groups"],
    )
    return x, None, aux
