"""Unified model API over all assigned architecture families.

    model = make_model(cfg)
    params            = model.init(rng)            # real or under eval_shape
    sds, axes         = model.abstract()           # ShapeDtypeStructs + logical axes
    logits, v, aux    = model.forward(params, batch)
    cache, cache_axes = model.init_cache(batch, seq_len)
    logits, v, cache  = model.decode_step(params, cache, tokens, pos)

``batch`` dict keys: "tokens" (B, T) int32; VLM adds "images"
(B, num_image_tokens, D) patch embeddings; audio adds "frames"
(B, num_audio_frames, D) — both are modality-frontend STUBS per the
assignment (the backbone consumes precomputed embeddings).

The model provides a policy head (the LM logits) and a value head — the
heads the Sebulba learner (V-trace) and actor (decode) consume.

``unroll=True`` lays layers out as per-layer parameters and a Python loop
instead of stacked parameters + lax.scan.  The math is identical; the
dry-run uses it because XLA cost analysis counts a scan body once, so only
the unrolled HLO yields honest roofline FLOPs.  Production configs keep the
scan layout (small HLO, fast compiles).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import griffin, layers, mamba2
from repro.models import transformer as tf
from repro.param import ParamBuilder, fan_in_init

Params = Any


class _CacheBuilder:
    """Builds a cache pytree and its logical-axes twin in lockstep."""

    def __init__(self, dtype):
        self.dtype = dtype

    def zeros(self, shape, axes, dtype=None):
        assert len(shape) == len(axes), (shape, axes)
        return jnp.zeros(shape, dtype or self.dtype), axes


def _kv_cache(cb: _CacheBuilder, batch, s, K, h, stacked_layers=0, seq_axis="kv_seq"):
    shape = (batch, s, K, h)
    axes = ("batch", seq_axis, "act_kv_heads", "head_dim")
    if stacked_layers:
        shape = (stacked_layers,) + shape
        axes = ("layers",) + axes
    k, ka = cb.zeros(shape, axes)
    v, va = cb.zeros(shape, axes)
    return {"k": k, "v": v}, {"k": ka, "v": va}


class Model:
    def __init__(self, cfg: ArchConfig, moe_impl: str = "sort",
                 unroll: bool = False, mesh=None):
        self.cfg = cfg
        self.moe_impl = moe_impl
        self.mesh = mesh  # needed only by moe_impl='a2a' (shard_map)
        self.unroll = unroll
        self._axes: dict | None = None
        self.kinds = tf.layer_kinds(cfg)
        uniform = tf.is_uniform(cfg)
        # stacked = scan-over-layers layout applies.  Dense stacks must be
        # uniform GLOBAL attention ("G"): the scan body takes no per-layer
        # window/theta, so a uniform-local ("L") pattern — e.g. the
        # sliding-window long-context variants — must use the looped path.
        self.stacked = not unroll and (
            cfg.family in ("ssm", "audio", "moe")
            or (cfg.family == "dense" and uniform and self.kinds[0] == "G")
        )

    # ------------------------------------------------------------------ init

    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        b = ParamBuilder(rng, dtype=jnp.dtype(cfg.param_dtype))
        layers.init_embedding(b, "embedding", cfg.vocab_size, cfg.d_model,
                              cfg.tie_embeddings)
        if cfg.pos_embed == "learned":
            layers.init_learned_pos(b, "pos", cfg.max_position, cfg.d_model)
        getattr(self, f"_init_{cfg.family}")(b)
        layers.init_rms_norm(b, "final_norm", cfg.d_model)
        with b.scope("value_head"):
            b.param("w", (cfg.d_model, 1), ("embed", None), fan_in_init())
        params, axes = b.build()
        self._axes = axes
        return params

    def abstract(self) -> tuple[Params, Params]:
        sds = jax.eval_shape(self.init, jax.random.key(0))
        return sds, self._axes

    @property
    def axes(self) -> Params:
        if self._axes is None:
            jax.eval_shape(self.init, jax.random.key(0))
        return self._axes

    # -- family inits ---------------------------------------------------------

    def _init_dense(self, b: ParamBuilder) -> None:
        cfg = self.cfg
        if self.stacked:
            def one(bb):
                tf.init_attn_layer(bb, cfg)
                tf.init_ffn_layer(bb, cfg, "dense")
            tf.init_stacked(b, "blocks", cfg.num_layers, one)
        else:
            for i in range(cfg.num_layers):
                with b.scope(f"layer_{i}"):
                    tf.init_attn_layer(b, cfg)
                    tf.init_ffn_layer(b, cfg, "dense")

    def _init_moe(self, b: ParamBuilder) -> None:
        cfg = self.cfg
        if self.stacked:
            for i, kind in enumerate(self.kinds):
                if kind == "D":
                    with b.scope(f"layer_{i}"):
                        tf.init_attn_layer(b, cfg)
                        tf.init_ffn_layer(b, cfg, "dense")
            n_moe = sum(1 for k in self.kinds if k == "M")
            def one(bb):
                tf.init_attn_layer(bb, cfg)
                tf.init_ffn_layer(bb, cfg, "moe")
            tf.init_stacked(b, "blocks", n_moe, one)
        else:
            for i, kind in enumerate(self.kinds):
                with b.scope(f"layer_{i}"):
                    tf.init_attn_layer(b, cfg)
                    tf.init_ffn_layer(b, cfg, "moe" if kind == "M" else "dense")

    def _init_ssm(self, b: ParamBuilder) -> None:
        cfg = self.cfg
        if self.stacked:
            def one(bb):
                mamba2.init_mamba2_block(bb, "mixer", cfg)
            tf.init_stacked(b, "blocks", cfg.num_layers, one)
        else:
            for i in range(cfg.num_layers):
                with b.scope(f"layer_{i}"):
                    mamba2.init_mamba2_block(b, "mixer", cfg)

    def _init_hybrid(self, b: ParamBuilder) -> None:
        cfg = self.cfg
        for i, kind in enumerate(self.kinds):
            with b.scope(f"layer_{i}"):
                if kind == "R":
                    griffin.init_recurrent_block(b, "recurrent", cfg)
                else:
                    tf.init_attn_layer(b, cfg)
                tf.init_ffn_layer(b, cfg, "dense")

    def _init_vlm(self, b: ParamBuilder) -> None:
        cfg = self.cfg
        with b.scope("projector"):
            b.param("w", (cfg.d_model, cfg.d_model), ("embed", "act_embed"),
                    fan_in_init())
        for i in range(cfg.num_layers):
            with b.scope(f"layer_{i}"):
                if self._is_cross(i):
                    tf.init_cross_layer(b, cfg)
                tf.init_attn_layer(b, cfg)
                tf.init_ffn_layer(b, cfg, "dense")

    def _init_audio(self, b: ParamBuilder) -> None:
        cfg = self.cfg
        with b.scope("enc_pos"):
            b.param("table", (cfg.num_audio_frames, cfg.d_model),
                    ("frames", "embed"), fan_in_init())
        def enc_one(bb):
            tf.init_attn_layer(bb, cfg)
            tf.init_ffn_layer(bb, cfg, "dense")
        def dec_one(bb):
            tf.init_attn_layer(bb, cfg)
            tf.init_cross_layer(bb, cfg)
            tf.init_ffn_layer(bb, cfg, "dense")
        if self.stacked:
            tf.init_stacked(b, "encoder", cfg.encoder_layers, enc_one)
            layers.init_rms_norm(b, "enc_norm", cfg.d_model)
            tf.init_stacked(b, "blocks", cfg.num_layers, dec_one)
        else:
            for i in range(cfg.encoder_layers):
                with b.scope(f"enc_layer_{i}"):
                    enc_one(b)
            layers.init_rms_norm(b, "enc_norm", cfg.d_model)
            for i in range(cfg.num_layers):
                with b.scope(f"layer_{i}"):
                    dec_one(b)

    def _is_cross(self, i: int) -> bool:
        every = self.cfg.cross_attn_every
        return every > 0 and (i + 2) % every == 0

    # --------------------------------------------------------------- forward

    def _embed(self, params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = layers.embed(params["embedding"], tokens, jnp.dtype(cfg.param_dtype))
        if "gemma" in cfg.name:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if cfg.pos_embed == "learned":
            pos = jnp.arange(tokens.shape[1])
            x = x + layers.learned_pos(params["pos"], pos).astype(x.dtype)
        return x

    def forward(self, params: Params, batch: dict) -> tuple[jax.Array, jax.Array, jax.Array]:
        """-> (logits (B,T,V) f32, values (B,T) f32, aux loss scalar)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        T = tokens.shape[1]
        x = self._embed(params, tokens)
        positions = jnp.arange(T)
        remat = cfg.remat != "none"

        if cfg.family in ("dense", "moe"):
            x, aux = self._fwd_dense_moe(params, x, positions, remat)
        elif cfg.family == "ssm":
            def body(p, h):
                return h + mamba2.mamba2_block(p["mixer"], h, cfg), jnp.float32(0.0)
            x, aux = self._apply_layers(params, x, body, remat)
        elif cfg.family == "hybrid":
            x, aux = self._fwd_hybrid(params, x, positions, remat)
        elif cfg.family == "vlm":
            x, aux = self._fwd_vlm(params, x, positions, batch["images"], remat)
        elif cfg.family == "audio":
            x, aux = self._fwd_audio(params, x, positions, batch["frames"], remat)
        else:
            raise ValueError(cfg.family)

        x = layers.rms_norm(params["final_norm"], x, cfg.rms_norm_eps)
        logits = layers.unembed(params["embedding"], x)
        values = jnp.einsum(
            "btd,dk->btk", x, params["value_head"]["w"].astype(x.dtype)
        )[..., 0].astype(jnp.float32)
        return logits, values, aux

    def _apply_layers(self, params, x, body, remat, layer_ids=None):
        """Run ``body(p, x) -> (x, aux)`` over the trunk layers, using the
        scan layout when ``self.stacked`` else a Python loop."""
        if self.stacked:
            return tf.scan_layers(params["blocks"], x, body, remat=remat)
        aux = jnp.float32(0.0)
        ids = layer_ids if layer_ids is not None else range(self.cfg.num_layers)
        f = jax.checkpoint(body) if remat else body
        for i in ids:
            x, a = f(params[f"layer_{i}"], x)
            aux += a
        return x, aux

    def _fwd_dense_moe(self, params, x, positions, remat):
        cfg = self.cfg
        aux = jnp.float32(0.0)
        if cfg.family == "moe" and self.stacked:
            # leading dense ('D') layers as a python loop (deepseek layer 0),
            # then the uniform MoE stack scanned.
            for i, kind in enumerate(self.kinds):
                if kind == "D":
                    x = tf.attn_sublayer(params[f"layer_{i}"], x, positions, cfg)
                    x, a = tf.ffn_sublayer(params[f"layer_{i}"], x, cfg)
                    aux += a
            def body(p, h):
                h = tf.attn_sublayer(p, h, positions, cfg)
                return tf.ffn_sublayer(p, h, cfg, self.moe_impl, self.mesh)
            x, a = tf.scan_layers(params["blocks"], x, body, remat=remat)
            return x, aux + a
        if self.stacked:  # uniform dense
            def body(p, h):
                h = tf.attn_sublayer(p, h, positions, cfg)
                return tf.ffn_sublayer(p, h, cfg, self.moe_impl, self.mesh)
            return tf.scan_layers(params["blocks"], x, body, remat=remat)
        # python loop: heterogeneous dense (gemma3) or unrolled layouts
        for i, kind in enumerate(self.kinds):
            window, theta = tf.local_params(cfg, kind)
            p = params[f"layer_{i}"]
            def one(p, h, window=window, theta=theta):
                h = tf.attn_sublayer(p, h, positions, cfg, window=window, theta=theta)
                return tf.ffn_sublayer(p, h, cfg, self.moe_impl, self.mesh)
            f = jax.checkpoint(one) if remat else one
            x, a = f(p, x)
            aux += a
        return x, aux

    def _fwd_hybrid(self, params, x, positions, remat):
        cfg = self.cfg
        aux = jnp.float32(0.0)
        for i, kind in enumerate(self.kinds):
            p = params[f"layer_{i}"]
            if kind == "R":
                def one(p, h):
                    h = h + griffin.recurrent_block(p["recurrent"], h, cfg)
                    return tf.ffn_sublayer(p, h, cfg)
            else:
                def one(p, h):
                    h = tf.attn_sublayer(
                        p, h, positions, cfg, window=cfg.sliding_window
                    )
                    return tf.ffn_sublayer(p, h, cfg)
            f = jax.checkpoint(one) if remat else one
            x, a = f(p, x)
            aux += a
        return x, aux

    def _fwd_vlm(self, params, x, positions, images, remat):
        cfg = self.cfg
        aux = jnp.float32(0.0)
        mem = images.astype(x.dtype) @ params["projector"]["w"].astype(x.dtype)
        for i in range(cfg.num_layers):
            p = params[f"layer_{i}"]
            if self._is_cross(i):
                mk, mv = attn.cross_kv(p["cross"], mem)
                def one(p, h, mk=mk, mv=mv):
                    h = tf.cross_sublayer(p, h, mk, mv, cfg)
                    h = tf.attn_sublayer(p, h, positions, cfg)
                    return tf.ffn_sublayer(p, h, cfg)
            else:
                def one(p, h):
                    h = tf.attn_sublayer(p, h, positions, cfg)
                    return tf.ffn_sublayer(p, h, cfg)
            f = jax.checkpoint(one) if remat else one
            x, a = f(p, x)
            aux += a
        return x, aux

    def _encode_audio(self, params, frames):
        cfg = self.cfg
        enc = frames.astype(jnp.dtype(cfg.param_dtype))
        enc = enc + params["enc_pos"]["table"].astype(enc.dtype)[None]
        def body(p, h):
            h = tf.attn_sublayer(p, h, None, cfg, causal=False)
            return tf.ffn_sublayer(p, h, cfg)
        remat = cfg.remat != "none"
        if self.stacked:
            enc, _ = tf.scan_layers(params["encoder"], enc, body, remat=remat)
        else:
            f = jax.checkpoint(body) if remat else body
            for i in range(cfg.encoder_layers):
                enc, _ = f(params[f"enc_layer_{i}"], enc)
        return layers.rms_norm(params["enc_norm"], enc, cfg.rms_norm_eps)

    def _fwd_audio(self, params, x, positions, frames, remat):
        cfg = self.cfg
        enc = self._encode_audio(params, frames)
        def body(p, h):
            h = tf.attn_sublayer(p, h, positions, cfg)
            mk, mv = attn.cross_kv(p["cross"], enc)
            h = tf.cross_sublayer(p, h, mk, mv, cfg)
            return tf.ffn_sublayer(p, h, cfg)
        return self._apply_layers(params, x, body, remat)

    # ----------------------------------------------------------------- cache

    def init_cache(
        self, batch: int, seq_len: int, dtype=None
    ) -> tuple[Params, Params]:
        """Returns (cache, cache_logical_axes)."""
        cfg = self.cfg
        cb = _CacheBuilder(dtype or jnp.dtype(cfg.cache_dtype))
        K, h = cfg.num_kv_heads, cfg.head_dim
        L = cfg.num_layers

        if cfg.family in ("dense", "moe") and self.stacked:
            n = L if cfg.family == "dense" else sum(
                1 for k in self.kinds if k == "M"
            )
            caches, axes = _kv_cache(cb, batch, seq_len, K, h, stacked_layers=n)
            cache = {"blocks": caches}
            cache_axes = {"blocks": axes}
            if cfg.family == "moe" and n != L:
                for i, kind in enumerate(self.kinds):
                    if kind == "D":
                        c, a = _kv_cache(cb, batch, seq_len, K, h)
                        cache[f"layer_{i}"], cache_axes[f"layer_{i}"] = c, a
            return cache, cache_axes

        if cfg.family in ("dense", "moe"):  # looped: gemma3 or unrolled
            cache, cache_axes = {}, {}
            for i, kind in enumerate(self.kinds):
                window, _ = tf.local_params(cfg, kind)
                s = min(window, seq_len) if window else seq_len
                c, a = _kv_cache(cb, batch, s, K, h)
                cache[f"layer_{i}"], cache_axes[f"layer_{i}"] = c, a
            return cache, cache_axes

        if cfg.family == "ssm":
            H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            if self.stacked:
                ssm, sa = cb.zeros(
                    (L, batch, H, P, N),
                    ("layers", "batch", "ssm_heads", None, "ssm_state"),
                    jnp.float32,
                )
                conv, ca = cb.zeros(
                    (L, batch, cfg.conv_width - 1, mamba2.conv_dim(cfg)),
                    ("layers", "batch", None, "ssm_inner"),
                )
                return {"blocks": {"ssm": ssm, "conv": conv}}, {
                    "blocks": {"ssm": sa, "conv": ca}
                }
            cache, cache_axes = {}, {}
            for i in range(L):
                ssm, sa = cb.zeros(
                    (batch, H, P, N),
                    ("batch", "ssm_heads", None, "ssm_state"), jnp.float32,
                )
                conv, ca = cb.zeros(
                    (batch, cfg.conv_width - 1, mamba2.conv_dim(cfg)),
                    ("batch", None, "ssm_inner"),
                )
                cache[f"layer_{i}"] = {"ssm": ssm, "conv": conv}
                cache_axes[f"layer_{i}"] = {"ssm": sa, "conv": ca}
            return cache, cache_axes

        if cfg.family == "hybrid":
            cache, cache_axes = {}, {}
            for i, kind in enumerate(self.kinds):
                if kind == "R":
                    hst, ha = cb.zeros(
                        (batch, cfg.rnn_width), ("batch", "rnn_width"), jnp.float32
                    )
                    conv, ca = cb.zeros(
                        (batch, cfg.rnn_conv_width - 1, cfg.rnn_width),
                        ("batch", None, "rnn_width"),
                    )
                    cache[f"layer_{i}"] = {"h": hst, "conv": conv}
                    cache_axes[f"layer_{i}"] = {"h": ha, "conv": ca}
                else:
                    s = min(cfg.sliding_window, seq_len)
                    c, a = _kv_cache(cb, batch, s, K, h)
                    cache[f"layer_{i}"], cache_axes[f"layer_{i}"] = c, a
            return cache, cache_axes

        if cfg.family == "vlm":
            cache, cache_axes = {}, {}
            for i in range(L):
                c, a = _kv_cache(cb, batch, seq_len, K, h)
                if self._is_cross(i):
                    mk, ma = cb.zeros(
                        (batch, cfg.num_image_tokens, K, h),
                        ("batch", "patches", "act_kv_heads", "head_dim"),
                    )
                    mv, _ = cb.zeros(
                        (batch, cfg.num_image_tokens, K, h),
                        ("batch", "patches", "act_kv_heads", "head_dim"),
                    )
                    c = dict(c, mem_k=mk, mem_v=mv)
                    a = dict(a, mem_k=ma, mem_v=ma)
                cache[f"layer_{i}"], cache_axes[f"layer_{i}"] = c, a
            return cache, cache_axes

        if cfg.family == "audio":
            mem_axes = ("batch", "frames", "act_kv_heads", "head_dim")
            if self.stacked:
                c, a = _kv_cache(cb, batch, seq_len, K, h, stacked_layers=L)
                mk, ma = cb.zeros(
                    (L,) + (batch, cfg.num_audio_frames, K, h),
                    ("layers",) + mem_axes,
                )
                mv, _ = cb.zeros(
                    (L,) + (batch, cfg.num_audio_frames, K, h),
                    ("layers",) + mem_axes,
                )
                return {"blocks": dict(c, mem_k=mk, mem_v=mv)}, {
                    "blocks": dict(a, mem_k=ma, mem_v=ma)
                }
            cache, cache_axes = {}, {}
            for i in range(L):
                c, a = _kv_cache(cb, batch, seq_len, K, h)
                mk, ma = cb.zeros((batch, cfg.num_audio_frames, K, h), mem_axes)
                mv, _ = cb.zeros((batch, cfg.num_audio_frames, K, h), mem_axes)
                cache[f"layer_{i}"] = dict(c, mem_k=mk, mem_v=mv)
                cache_axes[f"layer_{i}"] = dict(a, mem_k=ma, mem_v=ma)
            return cache, cache_axes

        raise ValueError(cfg.family)

    def init_paged_cache(
        self, num_blocks: int, block_size: int, dtype=None
    ) -> tuple[Params, Params]:
        """Paged KV cache for the serving path: each attention layer's
        {"k", "v"} become physical page pools ``(P, bs, K, h)`` shared by
        all rows through a per-request block table (serve/blocks.py).
        Page 0 is reserved as scratch (never mapped to a live request),
        so out-of-range writes land there harmlessly.  Same pytree
        structure as ``init_cache`` — decode_step just threads
        ``block_tables`` through.  dense/moe only (the families whose
        decode path is pure global attention)."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"paged cache supports dense/moe only, not {cfg.family}"
            )
        for kind in self.kinds:
            if tf.local_params(cfg, kind)[0]:
                raise ValueError(
                    "paged cache requires uniform global attention; "
                    "sliding-window layers keep the dense cache"
                )
        cb = _CacheBuilder(dtype or jnp.dtype(cfg.cache_dtype))
        K, h = cfg.num_kv_heads, cfg.head_dim
        axes = ("pages", "page_slot", "act_kv_heads", "head_dim")

        def pool(stacked_layers=0):
            shape = (num_blocks, block_size, K, h)
            a = axes
            if stacked_layers:
                shape, a = (stacked_layers,) + shape, ("layers",) + a
            k, ka = cb.zeros(shape, a)
            v, va = cb.zeros(shape, a)
            return {"k": k, "v": v}, {"k": ka, "v": va}

        if self.stacked:
            n = cfg.num_layers if cfg.family == "dense" else sum(
                1 for k in self.kinds if k == "M"
            )
            c, a = pool(stacked_layers=n)
            cache, cache_axes = {"blocks": c}, {"blocks": a}
            if cfg.family == "moe" and n != cfg.num_layers:
                for i, kind in enumerate(self.kinds):
                    if kind == "D":
                        cache[f"layer_{i}"], cache_axes[f"layer_{i}"] = pool()
            return cache, cache_axes
        cache, cache_axes = {}, {}
        for i in range(cfg.num_layers):
            cache[f"layer_{i}"], cache_axes[f"layer_{i}"] = pool()
        return cache, cache_axes

    # ----------------------------------------------------------- decode step

    def decode_step(
        self, params: Params, cache: Params, tokens: jax.Array, pos: jax.Array,
        block_tables: jax.Array | None = None,
    ) -> tuple[jax.Array, jax.Array, Params]:
        """tokens: (B, 1) -> (logits (B,1,V) f32, values (B,1) f32, cache).

        ``pos`` is a scalar (lockstep batch — the PR 9 path, unchanged) or
        a (B,) int32 vector of per-row positions.  ``block_tables`` (B, nb)
        switches dense/moe attention onto the paged cache from
        ``init_paged_cache``.
        """
        cfg = self.cfg
        if block_tables is not None and cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"block_tables requires a dense/moe model, not {cfg.family}"
            )
        x = self._embed_decode(params, tokens, pos)
        # the named scopes label the decode step's device ops in a profile
        with jax.named_scope("decode_layers"):
            x, new_cache = self._decode_layers(
                params, cache, x, pos, block_tables
            )
        with jax.named_scope("unembed"):
            x = layers.rms_norm(params["final_norm"], x, cfg.rms_norm_eps)
            logits = layers.unembed(params["embedding"], x)
        values = jnp.einsum(
            "btd,dk->btk", x, params["value_head"]["w"].astype(x.dtype)
        )[..., 0].astype(jnp.float32)
        return logits, values, new_cache

    def _decode_layers(
        self, params: Params, cache: Params, x: jax.Array, pos: jax.Array,
        block_tables: jax.Array | None,
    ) -> tuple[jax.Array, Params]:
        """Every layer of one decode step -> (hidden, new cache)."""
        cfg = self.cfg
        new_cache = {}
        if cfg.family in ("dense", "moe") and self.stacked:
            def step(p, c, h):
                h, c2 = tf.attn_sublayer_decode(
                    p, c, h, pos, cfg, block_tables=block_tables
                )
                h, _ = tf.ffn_sublayer(p, h, cfg, self.moe_impl, self.mesh)
                return h, c2
            if cfg.family == "moe" and "layer_0" in params:
                for i, kind in enumerate(self.kinds):
                    if kind == "D":
                        x, c2 = tf.attn_sublayer_decode(
                            params[f"layer_{i}"], cache[f"layer_{i}"], x, pos,
                            cfg, block_tables=block_tables,
                        )
                        x, _ = tf.ffn_sublayer(params[f"layer_{i}"], x, cfg)
                        new_cache[f"layer_{i}"] = c2
            x, blocks_cache = tf.scan_decode_layers(
                params["blocks"], cache["blocks"], x, step
            )
            new_cache["blocks"] = blocks_cache
        elif cfg.family in ("dense", "moe"):
            for i, kind in enumerate(self.kinds):
                window, theta = tf.local_params(cfg, kind)
                x, c2 = tf.attn_sublayer_decode(
                    params[f"layer_{i}"], cache[f"layer_{i}"], x, pos, cfg,
                    window=window, theta=theta, block_tables=block_tables,
                )
                x, _ = tf.ffn_sublayer(params[f"layer_{i}"], x, cfg, self.moe_impl, self.mesh)
                new_cache[f"layer_{i}"] = c2
        elif cfg.family == "ssm":
            def step(p, c, h):
                out, c2 = mamba2.mamba2_decode_step(p["mixer"], c, h, cfg)
                return h + out, c2
            if self.stacked:
                x, blocks_cache = tf.scan_decode_layers(
                    params["blocks"], cache["blocks"], x, step
                )
                new_cache["blocks"] = blocks_cache
            else:
                for i in range(cfg.num_layers):
                    x, c2 = step(params[f"layer_{i}"], cache[f"layer_{i}"], x)
                    new_cache[f"layer_{i}"] = c2
        elif cfg.family == "hybrid":
            for i, kind in enumerate(self.kinds):
                p = params[f"layer_{i}"]
                c = cache[f"layer_{i}"]
                if kind == "R":
                    out, c2 = griffin.recurrent_decode_step(p["recurrent"], c, x, cfg)
                    x = x + out
                else:
                    x, c2 = tf.attn_sublayer_decode(
                        p, c, x, pos, cfg, window=cfg.sliding_window
                    )
                x, _ = tf.ffn_sublayer(p, x, cfg)
                new_cache[f"layer_{i}"] = c2
        elif cfg.family == "vlm":
            for i in range(cfg.num_layers):
                p = params[f"layer_{i}"]
                c = cache[f"layer_{i}"]
                if self._is_cross(i):
                    x = self._cross_decode(p, c, x)
                x, c2 = tf.attn_sublayer_decode(p, {"k": c["k"], "v": c["v"]}, x,
                                                pos, cfg)
                if self._is_cross(i):
                    c2 = dict(c2, mem_k=c["mem_k"], mem_v=c["mem_v"])
                x, _ = tf.ffn_sublayer(p, x, cfg)
                new_cache[f"layer_{i}"] = c2
        elif cfg.family == "audio":
            def step(p, c, h):
                h, c2 = tf.attn_sublayer_decode(p, {"k": c["k"], "v": c["v"]}, h,
                                                pos, cfg)
                h = self._cross_decode(p, c, h)
                h, _ = tf.ffn_sublayer(p, h, cfg)
                return h, dict(c2, mem_k=c["mem_k"], mem_v=c["mem_v"])
            if self.stacked:
                x, blocks_cache = tf.scan_decode_layers(
                    params["blocks"], cache["blocks"], x, step
                )
                new_cache["blocks"] = blocks_cache
            else:
                for i in range(cfg.num_layers):
                    x, c2 = step(params[f"layer_{i}"], cache[f"layer_{i}"], x)
                    new_cache[f"layer_{i}"] = c2
        else:
            raise ValueError(cfg.family)
        return x, new_cache

    # ---------------------------------------------------------- prefill step

    def prefill_step(
        self, params: Params, cache: Params, tokens: jax.Array, pos: jax.Array,
        block_tables: jax.Array | None = None,
    ) -> tuple[jax.Array, jax.Array, Params]:
        """Chunked prefill: process a (B, C) token chunk whose row-b tokens
        sit at positions pos[b]..pos[b]+C-1, writing K/V into the cache and
        returning per-position logits — the fused-forward equivalent of C
        sequential ``decode_step`` calls (bit-exact with them; the parity
        pin in test_models covers it).  tokens: (B, C); pos: scalar or (B,)
        -> (logits (B,C,V) f32, values (B,C) f32, cache).  dense/moe with
        global attention only (the serving path)."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"prefill_step supports dense/moe only, not {cfg.family}; "
                "other families decode token-by-token"
            )
        C = tokens.shape[1]
        pos = jnp.asarray(pos)
        x = layers.embed(params["embedding"], tokens, jnp.dtype(cfg.param_dtype))
        if "gemma" in cfg.name:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if cfg.pos_embed == "learned":
            positions = tf._rope_positions(pos, C)
            x = x + layers.learned_pos(params["pos"], positions).astype(x.dtype)
        new_cache = {}
        if self.stacked:
            def step(p, c, h):
                h, c2 = tf.attn_sublayer_prefill(
                    p, c, h, pos, cfg, block_tables=block_tables
                )
                h, _ = tf.ffn_sublayer(p, h, cfg, self.moe_impl, self.mesh)
                return h, c2
            if cfg.family == "moe" and "layer_0" in params:
                for i, kind in enumerate(self.kinds):
                    if kind == "D":
                        x, c2 = tf.attn_sublayer_prefill(
                            params[f"layer_{i}"], cache[f"layer_{i}"], x, pos,
                            cfg, block_tables=block_tables,
                        )
                        x, _ = tf.ffn_sublayer(params[f"layer_{i}"], x, cfg)
                        new_cache[f"layer_{i}"] = c2
            x, blocks_cache = tf.scan_decode_layers(
                params["blocks"], cache["blocks"], x, step
            )
            new_cache["blocks"] = blocks_cache
        else:
            for i, kind in enumerate(self.kinds):
                if tf.local_params(cfg, kind)[0]:
                    raise ValueError(
                        "prefill_step requires global attention layers; "
                        "sliding-window layers decode token-by-token"
                    )
                x, c2 = tf.attn_sublayer_prefill(
                    params[f"layer_{i}"], cache[f"layer_{i}"], x, pos, cfg,
                    block_tables=block_tables,
                )
                x, _ = tf.ffn_sublayer(
                    params[f"layer_{i}"], x, cfg, self.moe_impl, self.mesh
                )
                new_cache[f"layer_{i}"] = c2
        x = layers.rms_norm(params["final_norm"], x, cfg.rms_norm_eps)
        logits = layers.unembed(params["embedding"], x)
        values = jnp.einsum(
            "btd,dk->btk", x, params["value_head"]["w"].astype(x.dtype)
        )[..., 0].astype(jnp.float32)
        return logits, values, new_cache

    def _embed_decode(self, params, tokens, pos):
        cfg = self.cfg
        x = layers.embed(params["embedding"], tokens, jnp.dtype(cfg.param_dtype))
        if "gemma" in cfg.name:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if cfg.pos_embed == "learned":
            pos = jnp.asarray(pos)
            if pos.ndim == 0:
                pe = layers.learned_pos(params["pos"], pos[None])[None]
            else:  # per-row positions: (B,) -> (B, 1, D)
                pe = layers.learned_pos(params["pos"], pos[:, None])
            x = x + pe.astype(x.dtype)
        return x

    def _cross_decode(self, p, c, x):
        """Cross-attention during decode (memory K/V precomputed in cache)."""
        cfg = self.cfg
        h = layers.rms_norm(p["cross_norm"], x, cfg.rms_norm_eps)
        q = jnp.einsum("btd,dhk->bthk", h, p["cross"]["wq"].astype(h.dtype))
        out = attn.decode_attention(q, c["mem_k"], c["mem_v"], jnp.int32(10**9))
        x = x + attn.output_project(p["cross"], out)
        h = layers.rms_norm(p["cross_ffn_norm"], x, cfg.rms_norm_eps)
        return x + layers.mlp(p["cross_mlp"], h)


def make_model(cfg: ArchConfig, moe_impl: str = "sort",
               unroll: bool = False, mesh=None) -> Model:
    return Model(cfg, moe_impl=moe_impl, unroll=unroll, mesh=mesh)
