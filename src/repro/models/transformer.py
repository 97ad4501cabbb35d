"""Model stacks for every assigned architecture family.

Layers are parameter-stacked (leading L axis) and driven by ``jax.lax.scan``
— the MaxText-style pattern that keeps XLA compile time flat in depth (the
94-layer MoE compiles as one scanned block).  The hybrid (RecurrentGemma)
stack scans over (rec, rec, local-attn) groups.

Three entry points (all pure):
    init_params(cfg, key)
    forward(params, cfg, tokens, ...)         mode: "full" | "decode" | "tree"
    loss_fn(params, cfg, batch)               next-token CE for train_step
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels.ops import (
    gqa_paged_tree_attention,
    gqa_ragged_tree_attention,
    gqa_tree_attention,
    interpret_mode,
)
from repro.models.cache import (
    append_layer_kv,
    attn_mask_from_pos,
    cache_slots,
    init_attn_cache,
    init_paged_attn_cache,
    paged_append_layer_kv,
    paged_layer_view,
    ragged_tree_mask,
    tree_mask_from_pos,
)
from repro.models.layers import (
    attention_weights_init,
    causal_mask,
    gqa_attend,
    init_dense,
    project_qkv,
    rms_norm,
    rope,
    swiglu,
    swiglu_init,
)
from repro.models.act_sharding import pin
from repro.models.moe import init_moe, moe_apply
from repro.models.rglru import init_rglru, rglru_apply
from repro.models.ssm import init_ssm, ssm_apply


# ----------------------------------------------------------------- params ----


def _stack_init(fn, key, n):
    return jax.vmap(fn)(jax.random.split(key, n))


def _attn_mlp_layer_init(cfg, key, cross: bool = False, moe: bool = False, d_ff: int | None = None):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
        "attn": attention_weights_init(cfg, ks[0]),
        "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    p["mlp"] = init_moe(cfg, ks[1]) if moe else swiglu_init(cfg, ks[1], d_ff=d_ff)
    if cross:
        p["ln_x"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["xattn"] = attention_weights_init(cfg, ks[2])
    return p


def init_params(cfg, key) -> dict:
    ks = jax.random.split(key, 8)
    dt = cfg.jdtype
    params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02).astype(dt),
        "final_ln": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(ks[1], cfg.d_model, cfg.vocab, dt)

    if cfg.arch_type in ("dense", "vlm"):
        params["blocks"] = _stack_init(lambda k: _attn_mlp_layer_init(cfg, k), ks[2], cfg.n_layers)
        if cfg.arch_type == "vlm":
            params["patch_proj"] = init_dense(ks[3], cfg.d_model, cfg.d_model, dt)
    elif cfg.arch_type == "moe":
        if cfg.moe_every > 1:
            # interleaved dense/MoE macro-layers (Llama-4 style)
            m = cfg.moe_every
            assert cfg.n_layers % m == 0, "n_layers must divide moe_every"
            dense_ff = cfg.moe_dense_ff or cfg.d_ff

            def macro_init(k):
                kk = jax.random.split(k, m)
                gp = {
                    f"dense{i}": _attn_mlp_layer_init(cfg, kk[i], d_ff=dense_ff)
                    for i in range(m - 1)
                }
                gp["moe"] = _attn_mlp_layer_init(cfg, kk[m - 1], moe=True)
                return gp

            params["blocks"] = _stack_init(macro_init, ks[2], cfg.n_layers // m)
        else:
            params["blocks"] = _stack_init(
                lambda k: _attn_mlp_layer_init(cfg, k, moe=True), ks[2], cfg.n_layers
            )
    elif cfg.arch_type == "ssm":
        params["blocks"] = _stack_init(
            lambda k: {"ln": jnp.zeros((cfg.d_model,), jnp.float32), "ssm": init_ssm(cfg, k)},
            ks[2],
            cfg.n_layers,
        )
    elif cfg.arch_type == "hybrid":
        g = cfg.hybrid_attn_every
        n_groups, rem = divmod(cfg.n_layers, g)

        def group_init(k):
            kk = jax.random.split(k, g)
            gp = {}
            for i in range(g - 1):
                gp[f"rec{i}"] = {
                    "ln": jnp.zeros((cfg.d_model,), jnp.float32),
                    "rec": init_rglru(cfg, kk[i]),
                    "ln_m": jnp.zeros((cfg.d_model,), jnp.float32),
                    "mlp": swiglu_init(cfg, kk[i]),
                }
            gp["attn"] = _attn_mlp_layer_init(cfg, kk[g - 1])
            return gp

        params["blocks"] = _stack_init(group_init, ks[2], n_groups)
        if rem:
            params["tail"] = _stack_init(
                lambda k: {
                    "ln": jnp.zeros((cfg.d_model,), jnp.float32),
                    "rec": init_rglru(cfg, k),
                    "ln_m": jnp.zeros((cfg.d_model,), jnp.float32),
                    "mlp": swiglu_init(cfg, k),
                },
                ks[3],
                rem,
            )
    elif cfg.arch_type == "encdec":
        params["enc_blocks"] = _stack_init(
            lambda k: _attn_mlp_layer_init(cfg, k), ks[2], cfg.n_enc_layers
        )
        params["enc_ln"] = jnp.zeros((cfg.d_model,), jnp.float32)
        params["blocks"] = _stack_init(
            lambda k: _attn_mlp_layer_init(cfg, k, cross=True), ks[3], cfg.n_layers
        )
    else:
        raise ValueError(cfg.arch_type)
    return params


# ----------------------------------------------------------------- blocks ----


def _self_attention(p, cfg, x, positions, mask, layer_cache, window, ragged=None):
    """Shared attention sub-block.  layer_cache: None or (k, v, slots, page)
    with page = None (dense cache) or the (B, max_blocks) block table of a
    paged pool (models/cache.py paged layout).

    ragged: None, or the (N,) owner-row vector of the ragged node-major tree
    pass (see forward).  Then x is (1, N, d), ``slots`` are per-NODE ring
    slots in the owner's row (Smax sentinel = padding lane, dropped), and
    ``mask`` is the (N, 1, 1, Smax) per-node admit mask."""
    B, T, _ = x.shape
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = project_qkv(p["attn"], cfg, h)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    new_kv = None
    page_tbl = None
    if ragged is not None:
        owner = ragged
        kc, vc, slots, page_tbl = layer_cache
        if page_tbl is None:
            kc = kc.at[owner, slots].set(k[0].astype(kc.dtype), mode="drop")
            vc = vc.at[owner, slots].set(v[0].astype(vc.dtype), mode="drop")
        else:
            # scatter each node into its owner's mapped physical lane; padding
            # lanes (slot sentinel) and unmapped blocks route out of range
            block = kc.shape[1]
            smax_l = page_tbl.shape[1] * block
            blk = page_tbl[owner, jnp.minimum(slots, smax_l - 1) // block]
            lanes = kc.shape[0] * block
            phys = jnp.where((slots < smax_l) & (blk >= 0), blk * block + slots % block, lanes)
            kf = kc.reshape((lanes,) + kc.shape[2:])
            vf = vc.reshape((lanes,) + vc.shape[2:])
            kc = kf.at[phys].set(k[0].astype(kc.dtype), mode="drop").reshape(kc.shape)
            vc = vf.at[phys].set(v[0].astype(vc.dtype), mode="drop").reshape(vc.shape)
        new_kv = (kc, vc)
        N = x.shape[1]
        if cfg.attention_impl == "pallas" and page_tbl is not None:
            att = gqa_ragged_tree_attention(
                q[0], kc, vc, page_tbl, owner, mask[:, 0, 0],
                interpret=interpret_mode(),
            )
        else:
            # XLA path: per-node gather of the owner row's logical view
            kd, vd = (kc[owner], vc[owner]) if page_tbl is None else paged_layer_view(
                kc, vc, page_tbl[owner]
            )
            att = gqa_attend(q[0][:, None], kd, vd, mask)[:, 0]
        return x + att.reshape(1, N, -1) @ p["attn"]["wo"], new_kv
    if layer_cache is not None:
        kc, vc, slots, page_tbl = layer_cache
        if page_tbl is None:
            kc, vc = append_layer_kv(kc, vc, k, v, slots)
            k, v = kc, vc
        else:
            kc, vc = paged_append_layer_kv(kc, vc, k, v, slots, page_tbl)
            if not (cfg.attention_impl == "pallas" and mask is not None):
                # XLA reference path: materialize the logical per-stream view
                # (unmapped lanes masked by pos = -1 upstream)
                k, v = paged_layer_view(kc, vc, page_tbl)
        new_kv = (kc, vc)
    if cfg.attention_impl == "pallas" and mask is not None:
        m3 = mask[:, 0] if mask.ndim == 4 else mask
        if page_tbl is not None:
            att = gqa_paged_tree_attention(q, kc, vc, page_tbl, m3,
                                           interpret=interpret_mode())
        else:
            att = gqa_tree_attention(q, k, v, m3, interpret=interpret_mode())
    else:
        att = gqa_attend(q, k, v, mask)
    return x + att.reshape(B, T, -1) @ p["attn"]["wo"], new_kv


def _attn_mlp_block(p, cfg, x, positions, mask, layer_cache, window, moe=False, enc_kv=None,
                    train=False, ragged=None):
    x = pin(x)
    x, new_kv = _self_attention(p, cfg, x, positions, mask, layer_cache, window, ragged=ragged)
    aux = jnp.zeros((), jnp.float32)
    if enc_kv is not None:  # cross attention (enc-dec)
        B, T, _ = x.shape
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        hd = cfg.hd
        q = (h @ p["xattn"]["wq"]).reshape(B, T, cfg.n_heads, hd)
        att = gqa_attend(q, enc_kv[0], enc_kv[1], None)
        x = x + att.reshape(B, T, -1) @ p["xattn"]["wo"]
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if moe:
        y, aux = moe_apply(p["mlp"], cfg, h, train=train)
    else:
        y = swiglu(p["mlp"], h)
    return x + y, new_kv, aux


def _rec_block(p, cfg, x, cache):
    x = pin(x)
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    y, new_cache = rglru_apply(p["rec"], cfg, h, cache)
    x = x + y
    h = rms_norm(x, p["ln_m"], cfg.norm_eps)
    return x + swiglu(p["mlp"], h), new_cache


# ---------------------------------------------------------------- forward ----


def _attn_cache_out(k, v, pos, length, page_tbl):
    """Post-scan attn cache dict; paged pools keep their block table."""
    out = {"k": k, "v": v, "pos": pos, "len": length}
    if page_tbl is not None:
        out["block_tbl"] = page_tbl
    return out



def _pyscan(body, init, xs):
    """Python-unrolled scan (same semantics as lax.scan for our bodies)."""
    n = len(jax.tree.leaves(xs)[0]) if jax.tree.leaves(xs) else 0
    carry = init
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and all(y is not None for y in jax.tree.leaves(ys[0], is_leaf=lambda z: z is None)):
        try:
            ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        except Exception:
            pass
    else:
        ys = None
    return carry, ys


def _mk_masks(cfg, mode, T, pos, positions, anc, slots):
    """Masks for full-attn layers and (hybrid) local-window layers.

    ``pos`` is the slot->absolute-position table *after* writing the new
    tokens, so queries can see themselves and each other causally.
    """
    win = cfg.window if cfg.attention == "sliding_window" else 0
    if mode == "full":
        return causal_mask(T, win), causal_mask(T, cfg.local_window)
    if mode == "decode":
        return (
            attn_mask_from_pos(pos, positions, win),
            attn_mask_from_pos(pos, positions, cfg.local_window),
        )
    return (
        tree_mask_from_pos(pos, positions, anc, slots, win),
        tree_mask_from_pos(pos, positions, anc, slots, cfg.local_window),
    )


def forward(
    params,
    cfg,
    tokens: jax.Array | None,
    *,
    mode: str = "full",
    cache: dict | None = None,
    anc: jax.Array | None = None,
    embeds: jax.Array | None = None,
    enc_embeds: jax.Array | None = None,
    lens: jax.Array | None = None,
    train: bool = False,
    ragged: dict | None = None,
):
    """Returns (logits, new_cache, aux).

    mode "full":   causal pass over tokens (training / prefill); if ``cache``
                   is given it is filled (prefill), else no cache is built.
    mode "decode": T new tokens against the cache (T=1 for serve_step).
    mode "tree":   T speculation-tree tokens with ancestor mask ``anc``.
    embeds:        pre-computed modality embeddings — VLM patches (prepended
                   at "full" time) or a direct replacement for token embeds.
    enc_embeds:    encoder-side frame embeddings (encdec only).
    lens:          per-stream real-token counts (B,) for *padded* cached
                   passes over a per-stream cache (see models/cache.py):
                   row b's tokens beyond lens[b] are padding — their cache
                   slots are written but marked invalid (pos = -1) and the
                   row's length advances by lens[b] only, so the next append
                   overwrites them.  Requires a per-stream cache.  Note this
                   masks *attention state only*; recurrent (ssm/rglru) state
                   integrates every token, so recurrent-arch callers must
                   keep padded rows frozen via cache.merge_streams instead.
    train:         training semantics (set by loss_fn): MoE uses the bounded
                   capacity-factor dispatch instead of the exact dropless
                   one (see models/moe.py).
    ragged:        node-major ragged tree pass (mode "tree" only; replaces
                   ``anc``).  ``tokens`` is (1, N): every active stream's
                   tree flattened into one node buffer.  Dict keys, each
                   (N,) int32 except counts: ``owner`` node->pool-row,
                   ``parent`` flat-index parent (-1 root/padding),
                   ``depth`` node depth in its tree, ``local`` node index
                   within its tree (-1 padding lane), ``counts`` (B,) real
                   nodes appended per row this pass (0 idle).  Padding
                   lanes write nothing (slot sentinel + drop scatters) and
                   attend to nothing.  Requires a per-stream attn cache and
                   arch_type dense/moe.  See docs/serving.md.
    """
    dt = cfg.jdtype
    if tokens is not None:
        x = params["embed"][tokens].astype(dt)
    else:
        x = embeds.astype(dt)
    if cfg.arch_type == "vlm" and embeds is not None and tokens is not None:
        patches = (embeds.astype(dt) @ params["patch_proj"]).astype(dt)
        x = jnp.concatenate([patches, x], axis=1)
    B, T, _ = x.shape

    length = cache["attn"]["len"] if (cache is not None and "attn" in cache) else (
        cache["len"] if cache is not None else jnp.zeros((), jnp.int32)
    )
    per_stream = getattr(length, "ndim", 0) == 1
    q_pos = None
    if ragged is not None:
        assert mode == "tree" and anc is None and lens is None
        assert per_stream and cfg.arch_type in ("dense", "moe")
        q_pos = length[ragged["owner"]] + ragged["depth"]  # (N,) absolute pos
        positions = q_pos[None, :]  # rope over the node axis (B=1, T=N)
    else:
        offs = jnp.arange(T, dtype=jnp.int32) if anc is None else _tree_depths(anc, per_stream)
        if per_stream:
            positions = length[:, None] + (offs if offs.ndim == 2 else offs[None, :])
        else:
            positions = length + offs
    aux_total = jnp.zeros((), jnp.float32)

    # ---------------- encoder (encdec) ----------------
    enc_kv_all = None
    if cfg.arch_type == "encdec":
        if enc_embeds is None:
            # decode steps: encoder states were projected + cached at prefill
            enc_kv_all = (cache["cross_k"], cache["cross_v"])
        else:
            enc = enc_embeds.astype(dt)

            def enc_body(h, pl):
                h, _, _ = _attn_mlp_block(
                    pl, cfg, h, jnp.arange(h.shape[1], dtype=jnp.int32), None, None, 0
                )
                return h, None

            enc, _ = jax.lax.scan(jax.checkpoint(enc_body) if cfg.remat and cache is None else enc_body, enc, params["enc_blocks"])
            enc = rms_norm(enc, params["enc_ln"], cfg.norm_eps)
            hd = cfg.hd

            def cross_kv(pl):
                k = (enc @ pl["xattn"]["wk"]).reshape(B, -1, cfg.n_kv_heads, hd)
                v = (enc @ pl["xattn"]["wv"]).reshape(B, -1, cfg.n_kv_heads, hd)
                return k, v

            enc_kv_all = jax.vmap(cross_kv)(params["blocks"])

    # ---------------- masks & cache slots ----------------
    use_cache = cache is not None
    has_attn = cfg.arch_type != "ssm"
    slots = new_pos = new_len = None
    page_tbl = None
    mask_full = mask_local = None
    if use_cache and mode == "full":
        mode = "decode"  # prefill == appending T tokens causally to an empty cache
    if has_attn:
        if use_cache and "attn" in cache and ragged is not None:
            page_tbl = cache["attn"].get("block_tbl")
            smax = cache["attn"]["pos"].shape[-1]
            owner = ragged["owner"]
            # node i's ring slot in its owner's row — identical to padded
            # column local[i]'s slot, so commit arithmetic is unchanged.
            # Padding lanes (local < 0) get the always-out-of-range sentinel
            # smax: every .at[...].set(mode="drop") write vanishes.
            slots = jnp.where(
                ragged["local"] >= 0,
                (length[owner] + jnp.maximum(ragged["local"], 0)) % smax,
                smax,
            )
            new_pos = cache["attn"]["pos"].at[owner, slots].set(q_pos, mode="drop")
            new_len = length + ragged["counts"]  # idle rows advance by 0
            win = cfg.window if cfg.attention == "sliding_window" else 0
            mask_full = ragged_tree_mask(
                new_pos, q_pos, owner, slots, ragged["parent"], win
            )[:, None, None, :]  # (N, 1, 1, Smax)
            mask_local = mask_full  # unused: dense/moe only
        elif use_cache and "attn" in cache:
            # paged pools keep logical capacity in the pos table; the KV
            # array's slot axis is the physical block size there
            page_tbl = cache["attn"].get("block_tbl")
            smax = cache["attn"]["pos"].shape[-1]
            slots = cache_slots(length, T, smax)
            pos_vals = positions
            if lens is not None:
                valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lens[:, None]
                pos_vals = jnp.where(valid, positions, -1)
            if per_stream:
                bidx = jnp.arange(slots.shape[0])[:, None]
                new_pos = cache["attn"]["pos"].at[bidx, slots].set(pos_vals)
            else:
                new_pos = cache["attn"]["pos"].at[slots].set(pos_vals)
            new_len = length + (T if lens is None else lens)
            mask_full, mask_local = _mk_masks(cfg, mode, T, new_pos, positions, anc, slots)
        else:
            mask_full, mask_local = _mk_masks(cfg, "full", T, None, None, None, None)

    # ---------------- decoder stacks ----------------
    ragged_owner = ragged["owner"] if ragged is not None else None
    new_cache = dict(cache) if use_cache else None
    # activation checkpointing for the training path (backward recompute)
    ckpt = jax.checkpoint if (cfg.remat and not use_cache) else (lambda f: f)
    scan = jax.lax.scan if cfg.scan else _pyscan

    if cfg.arch_type == "moe" and cfg.moe_every > 1:
        # interleaved dense/MoE macro-layers
        m = cfg.moe_every
        ng = cfg.n_layers // m

        def macro_body(h, per):
            pl, lc = per  # lc: None or (k (m,B,S,H,D), v (m,B,S,H,D))
            ks_, vs_ = [], []
            for i in range(m - 1):
                layer_cache = (lc[0][i], lc[1][i], slots, page_tbl) if lc is not None else None
                h, kv, _ = _attn_mlp_block(
                    pl[f"dense{i}"], cfg, h, positions, mask_full, layer_cache, 0,
                    ragged=ragged_owner,
                )
                if kv is not None:
                    ks_.append(kv[0])
                    vs_.append(kv[1])
            layer_cache = (lc[0][m - 1], lc[1][m - 1], slots, page_tbl) if lc is not None else None
            h, kv, aux = _attn_mlp_block(
                pl["moe"], cfg, h, positions, mask_full, layer_cache, 0, moe=True, train=train,
                ragged=ragged_owner,
            )
            if kv is not None:
                ks_.append(kv[0])
                vs_.append(kv[1])
            out_kv = (jnp.stack(ks_), jnp.stack(vs_)) if ks_ else None
            return h, (out_kv, aux)

        if use_cache:
            kc = cache["attn"]["k"].reshape((ng, m) + cache["attn"]["k"].shape[1:])
            vc = cache["attn"]["v"].reshape((ng, m) + cache["attn"]["v"].shape[1:])
            x, (kvs, auxs) = scan(macro_body, x, (params["blocks"], (kc, vc)))
            new_cache["attn"] = _attn_cache_out(
                kvs[0].reshape((cfg.n_layers,) + kvs[0].shape[2:]),
                kvs[1].reshape((cfg.n_layers,) + kvs[1].shape[2:]),
                new_pos, new_len, page_tbl,
            )
        else:
            def macro_nc(h, pl):
                h, (_, aux) = macro_body(h, (pl, None))
                return h, aux

            x, auxs = scan(ckpt(macro_nc), x, params["blocks"])
        aux_total = jnp.sum(auxs if not isinstance(auxs, tuple) else auxs[1])

    elif cfg.arch_type in ("dense", "vlm", "moe", "encdec"):
        moe = cfg.arch_type == "moe"

        def body(h, per):
            if cfg.arch_type == "encdec":
                pl, lc, ekv = per
            else:
                pl, lc = per
                ekv = None
            layer_cache = (lc[0], lc[1], slots, page_tbl) if lc is not None else None
            h, new_kv, aux = _attn_mlp_block(
                pl, cfg, h, positions, mask_full, layer_cache, 0, moe=moe, enc_kv=ekv,
                train=train, ragged=ragged_owner,
            )
            return h, (new_kv, aux)

        if use_cache:
            xs = (
                (params["blocks"], (cache["attn"]["k"], cache["attn"]["v"]), enc_kv_all)
                if cfg.arch_type == "encdec"
                else (params["blocks"], (cache["attn"]["k"], cache["attn"]["v"]))
            )
            x, (kvs, auxs) = scan(body, x, xs)
            new_cache["attn"] = _attn_cache_out(kvs[0], kvs[1], new_pos, new_len, page_tbl)
            if cfg.arch_type == "encdec" and enc_embeds is not None:
                new_cache["cross_k"], new_cache["cross_v"] = enc_kv_all
        else:
            xs = (
                (params["blocks"], None, enc_kv_all)
                if cfg.arch_type == "encdec"
                else (params["blocks"], None)
            )
            # scan cannot carry None xs: wrap with explicit Nones via partial
            def body_nc(h, per):
                if cfg.arch_type == "encdec":
                    pl, ekv = per
                else:
                    pl, ekv = per, None
                h, _, aux = _attn_mlp_block(
                    pl, cfg, h, positions, mask_full, None, 0, moe=moe, enc_kv=ekv,
                    train=train,
                )
                return h, aux

            scan_xs = (params["blocks"], enc_kv_all) if cfg.arch_type == "encdec" else params["blocks"]
            x, auxs = scan(ckpt(body_nc), x, scan_xs)
        aux_total = jnp.sum(auxs[1] if isinstance(auxs, tuple) else auxs) if moe else aux_total

    elif cfg.arch_type == "ssm":

        def body(h, per):
            pl, lc = per
            hn = rms_norm(h, pl["ln"], cfg.norm_eps)
            y, nc = ssm_apply(pl["ssm"], cfg, hn, lc)
            return h + y, nc

        lc = (
            {"state": cache["state"], "conv": cache["conv"]} if use_cache else None
        )
        if use_cache:
            def body_c(h, per):
                pl, st, cv = per
                h = pin(h)
                hn = rms_norm(h, pl["ln"], cfg.norm_eps)
                y, nc = ssm_apply(pl["ssm"], cfg, hn, {"state": st, "conv": cv})
                return h + y, (nc["state"], nc["conv"])

            x, (sts, cvs) = scan(body_c, x, (params["blocks"], cache["state"], cache["conv"]))
            new_cache.update({"state": sts, "conv": cvs, "len": length + (T if lens is None else lens)})
        else:
            def body_nc(h, pl):
                h = pin(h)
                hn = rms_norm(h, pl["ln"], cfg.norm_eps)
                y, _ = ssm_apply(pl["ssm"], cfg, hn, None)
                return h + y, None

            x, _ = scan(ckpt(body_nc), x, params["blocks"])

    elif cfg.arch_type == "hybrid":
        g = cfg.hybrid_attn_every

        def group_body_c(h, per):
            pl, rec_states, rec_convs, kc, vc = per
            new_states, new_convs = [], []
            for i in range(g - 1):
                h, nc = _rec_block(
                    pl[f"rec{i}"], cfg, h, {"state": rec_states[i], "conv": rec_convs[i]}
                )
                new_states.append(nc["state"])
                new_convs.append(nc["conv"])
            h, new_kv, _ = _attn_mlp_block(
                pl["attn"], cfg, h, positions, mask_local, (kc, vc, slots, page_tbl), cfg.local_window
            )
            return h, (jnp.stack(new_states), jnp.stack(new_convs), new_kv[0], new_kv[1])

        def group_body_nc(h, pl):
            for i in range(g - 1):
                h, _ = _rec_block(pl[f"rec{i}"], cfg, h, None)
            h, _, _ = _attn_mlp_block(pl["attn"], cfg, h, positions, mask_local, None, cfg.local_window)
            return h, None

        if use_cache:
            x, (sts, cvs, ks_, vs_) = scan(
                group_body_c,
                x,
                (
                    params["blocks"],
                    cache["rec_state"],
                    cache["rec_conv"],
                    cache["attn"]["k"],
                    cache["attn"]["v"],
                ),
            )
            new_cache["rec_state"], new_cache["rec_conv"] = sts, cvs
            new_cache["attn"] = _attn_cache_out(ks_, vs_, new_pos, new_len, page_tbl)
        else:
            x, _ = scan(ckpt(group_body_nc), x, params["blocks"])
        if "tail" in params:
            def tail_c(h, per):
                pl, st, cv = per
                h, nc = _rec_block(pl, cfg, h, {"state": st, "conv": cv})
                return h, (nc["state"], nc["conv"])

            def tail_nc(h, pl):
                h, _ = _rec_block(pl, cfg, h, None)
                return h, None

            if use_cache:
                x, (tsts, tcvs) = scan(
                    tail_c, x, (params["tail"], cache["tail_state"], cache["tail_conv"])
                )
                new_cache["tail_state"], new_cache["tail_conv"] = tsts, tcvs
            else:
                x, _ = scan(ckpt(tail_nc), x, params["tail"])
        if use_cache:
            new_cache["len"] = length + (T if lens is None else lens)
    else:
        raise ValueError(cfg.arch_type)

    x = pin(rms_norm(x, params["final_ln"], cfg.norm_eps))
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    return logits, new_cache, {"aux": aux_total, "hidden": x}


def _tree_depths(anc: jax.Array, per_stream: bool = False) -> jax.Array:
    """Positions offset of tree tokens = (ancestor count - 1).

    Lockstep caches treat a (B, T, T) anc as sharing one topology (depths
    from row 0); per-stream caches get per-row depths (B, T)."""
    if anc.ndim == 3 and per_stream:
        return jnp.sum(anc.astype(jnp.int32), axis=-1) - 1
    a = anc if anc.ndim == 2 else anc[0]
    return jnp.sum(a.astype(jnp.int32), axis=-1) - 1


# ------------------------------------------------------------------ cache ----


def init_cache(cfg, batch: int, smax: int, enc_len: int | None = None, per_stream: bool = False,
               page: tuple[int, int] | None = None) -> dict:
    """Empty decode cache for every architecture family.

    smax: attention cache capacity (== window for sliding-window archs; the
    ring buffer makes longer logical contexts fit in window slots).
    per_stream: per-row pos/len tables so batch rows hold independent streams
    (the continuous-batching layout; see models/cache.py).
    page: (pool_blocks, block_size) — store attention KV as a paged block
    arena instead of per-stream rings: ``pool_blocks`` usable blocks of
    ``block_size`` slots shared by all rows through per-row block tables,
    with ``smax`` staying each row's *logical* capacity (must divide into
    block_size).  Requires per_stream.  Pure-recurrent caches ignore it.
    """
    assert page is None or per_stream, "paged caches are per-stream by construction"
    dt = cfg.jdtype
    hd = cfg.hd

    def attn_cache(n_layers):
        if page is not None:
            return init_paged_attn_cache(cfg, n_layers, batch, page[0], page[1], smax, dt)
        return init_attn_cache(cfg, n_layers, batch, smax, dt, per_stream=per_stream)

    cache: dict = {"len": jnp.zeros((batch,) if per_stream else (), jnp.int32)}
    if cfg.arch_type in ("dense", "vlm", "moe", "encdec"):
        cache["attn"] = attn_cache(cfg.n_layers)
        del cache["len"]
        if cfg.arch_type == "encdec":
            el = enc_len or cfg.enc_len
            cache["cross_k"] = jnp.zeros((cfg.n_layers, batch, el, cfg.n_kv_heads, hd), dt)
            cache["cross_v"] = jnp.zeros((cfg.n_layers, batch, el, cfg.n_kv_heads, hd), dt)
    elif cfg.arch_type == "ssm":
        H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
        conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        cache["state"] = jnp.zeros((cfg.n_layers, batch, H, P, N), jnp.float32)
        cache["conv"] = jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, conv_dim), dt)
    elif cfg.arch_type == "hybrid":
        g = cfg.hybrid_attn_every
        n_groups, rem = divmod(cfg.n_layers, g)
        dl = cfg.lru_d
        cache["rec_state"] = jnp.zeros((n_groups, g - 1, batch, dl), jnp.float32)
        cache["rec_conv"] = jnp.zeros((n_groups, g - 1, batch, 3, dl), dt)
        cache["attn"] = attn_cache(n_groups)
        if rem:
            cache["tail_state"] = jnp.zeros((rem, batch, dl), jnp.float32)
            cache["tail_conv"] = jnp.zeros((rem, batch, 3, dl), dt)
    else:
        raise ValueError(cfg.arch_type)
    return cache


def cache_length(cfg, cache) -> jax.Array:
    return cache["attn"]["len"] if "attn" in cache else cache["len"]


# --------------------------------------------------------------- training ----


def loss_fn(params, cfg, tokens: jax.Array, labels: jax.Array, embeds=None, enc_embeds=None):
    """Next-token cross-entropy (+ MoE aux).  labels < 0 are masked."""
    logits, _, extras = forward(
        params, cfg, tokens, mode="full", embeds=embeds, enc_embeds=enc_embeds, train=True
    )
    aux = extras["aux"]
    if cfg.arch_type == "vlm" and embeds is not None:
        logits = logits[:, embeds.shape[1] :]
    lp = jax.nn.log_softmax(logits, axis=-1)
    mask = labels >= 0
    ll = jnp.take_along_axis(lp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return ce + cfg.router_aux_weight * aux


def make_train_step(cfg, optimizer):
    def train_step(params, opt_state, batch):
        def lf(p):
            return loss_fn(
                p,
                cfg,
                batch["tokens"],
                batch["labels"],
                embeds=batch.get("embeds"),
                enc_embeds=batch.get("enc_embeds"),
            )

        loss, grads = jax.value_and_grad(lf)(params)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    return train_step
