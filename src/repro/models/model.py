"""Top-level model API: build_model(cfg) -> Model with init / forward /
loss / prefill / decode_step, covering all assigned families:

  dense|moe|vlm  : uniform decoder stack (token or stub-embedding input)
  ssm            : mamba2 stack
  hybrid         : jamba block stack
  audio          : whisper enc-dec (stub audio-frame embeddings)

Decode caches are stacked along the layer axis and threaded through
``lax.scan`` so serve_step HLO is depth-independent.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro import util
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers, moe, ssm, transformer


def _dtype(cfg) -> Any:
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------- params
    def init(self, rng) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        ks = jax.random.split(rng, 6)
        p: dict[str, Any] = {
            "embed": layers.init_embedding(ks[0], cfg.vocab_size,
                                           cfg.d_model, dt),
            "final_ln": layers.init_norm(cfg.norm, cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = jax.random.normal(
                ks[1], (cfg.d_model, cfg.vocab_size), dt) / math.sqrt(cfg.d_model)
        if cfg.family == "ssm":
            p["layers"] = transformer.init_ssm_stack(ks[2], cfg, dt)
        elif cfg.family == "hybrid":
            p["layers"] = transformer.init_hybrid_block_stack(ks[2], cfg, dt)
        elif cfg.enc_dec:
            p["enc_pos"] = layers.init_embedding(ks[3], 1 << 16, cfg.d_model, dt)
            p["dec_pos"] = layers.init_embedding(ks[4], 1 << 16, cfg.d_model, dt)
            p["encoder"] = transformer.init_uniform_stack(
                ks[2], cfg, dt, cfg.num_enc_layers)
            p["enc_ln"] = layers.init_norm(cfg.norm, cfg.d_model, dt)
            p["layers"] = transformer.init_uniform_stack(
                ks[5], cfg, dt, cfg.num_layers, cross=True)
        else:
            if cfg.pos_emb == "absolute":
                p["dec_pos"] = layers.init_embedding(ks[3], 1 << 16,
                                                     cfg.d_model, dt)
            p["layers"] = transformer.init_uniform_stack(
                ks[2], cfg, dt, cfg.num_layers)
        return p

    def abstract_params(self):
        """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------ forward
    def _embed_in(self, p, batch, which: str = "tokens"):
        cfg = self.cfg
        if which == "tokens" and "tokens" in batch:
            x = layers.embed(batch["tokens"], p["embed"])
            if cfg.family == "dense" and cfg.tie_embeddings:
                x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        else:
            x = batch["embeds"].astype(_dtype(cfg))
        if cfg.pos_emb == "absolute" and "dec_pos" in p:
            n = x.shape[-2]
            x = x + p["dec_pos"][:n][None]
        return x

    def hidden(self, p, batch) -> jax.Array:
        """Final hidden states (B, S, D) before the LM head."""
        cfg = self.cfg
        if cfg.enc_dec:
            enc_x = batch["enc_embeds"].astype(_dtype(cfg))
            ne = enc_x.shape[-2]
            enc_x = enc_x + p["enc_pos"][:ne][None]
            enc_pos = jnp.arange(ne)
            enc_h = transformer.uniform_stack(
                p["encoder"], enc_x, cfg, positions=enc_pos, mask_kind="none")
            enc_h = layers.norm(enc_h, p["enc_ln"], cfg.norm)
            x = layers.embed(batch["tokens"], p["embed"])
            nd = x.shape[-2]
            x = x + p["dec_pos"][:nd][None]
            h = transformer.uniform_stack(
                p["layers"], x, cfg, positions=jnp.arange(nd),
                mask_kind="causal", enc_out=enc_h, enc_positions=enc_pos)
        else:
            x = self._embed_in(p, batch)
            n = x.shape[-2]
            positions = jnp.arange(n)
            if cfg.family == "ssm":
                h = transformer.ssm_stack(p["layers"], x, cfg)
            elif cfg.family == "hybrid":
                h = transformer.hybrid_stack(p["layers"], x, cfg,
                                             positions=positions)
            else:
                h = transformer.uniform_stack(p["layers"], x, cfg,
                                              positions=positions)
        return layers.norm(h, p["final_ln"], cfg.norm)

    def loss(self, p, batch) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        h = self.hidden(p, batch)
        head = p["embed"] if cfg.tie_embeddings else p["lm_head"]
        nll, denom = layers.cross_entropy_chunked(
            h, head, batch["labels"], cfg.tie_embeddings,
            mask=batch.get("loss_mask"))
        return nll, {"loss": nll, "tokens": denom}

    def logits(self, p, batch) -> jax.Array:
        h = self.hidden(p, batch)
        head = p["embed"] if self.cfg.tie_embeddings else p["lm_head"]
        return layers.unembed(h, head, self.cfg.tie_embeddings)

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        dt = _dtype(cfg)
        if cfg.family == "ssm":
            one = lambda: ssm.init_ssm_state(cfg.d_model, cfg.ssm, batch, dt)
            return {"ssm": _stack_pytrees([one() for _ in range(cfg.num_layers)])}
        if cfg.family == "hybrid":
            nb = cfg.num_layers // cfg.attn_every
            a = _stack_pytrees([attn.init_kv_cache(cfg, batch, max_len, dt)
                                for _ in range(nb)])
            s = _stack_pytrees([
                _stack_pytrees([ssm.init_ssm_state(cfg.d_model, cfg.ssm,
                                                   batch, dt)
                                for _ in range(cfg.attn_every - 1)])
                for _ in range(nb)])
            return {"attn": a, "ssm": s}
        n = cfg.num_layers
        cache = {"attn": _stack_pytrees(
            [attn.init_kv_cache(cfg, batch, max_len, dt) for _ in range(n)])}
        if cfg.enc_dec:
            # cross-attn K/V per layer ("kv") or shared enc_out X-cache
            cache["enc_len"] = jnp.zeros((batch,), jnp.int32)
            if attn.cache_mode_for(cfg) == "kv":
                Hkv, dh = cfg.num_kv_heads, cfg.head_dim
                cache["cross_k"] = jnp.zeros((n, batch, max_len, Hkv, dh), dt)
                cache["cross_v"] = jnp.zeros((n, batch, max_len, Hkv, dh), dt)
            else:
                cache["enc_out"] = jnp.zeros((batch, max_len, cfg.d_model), dt)
                cache["cross_v"] = jnp.zeros(
                    (n, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dt)
        return cache

    def prefill(self, p, batch, max_len: int):
        """Process a full prompt; return (last-token logits, cache).

        Implemented as full-sequence forward + cache fill (the compiled
        prefill graph). tokens (B, S) with true lengths (B,).
        """
        cfg = self.cfg
        B = (batch["tokens"] if "tokens" in batch else batch["embeds"]).shape[0]
        lengths = batch.get("lengths")
        cache = self.init_cache(B, max_len)
        cache, h = self._prefill_fill(p, batch, cache)
        head = p["embed"] if cfg.tie_embeddings else p["lm_head"]
        if lengths is None:
            h_last = h[:, -1]
        else:
            h_last = jnp.take_along_axis(
                h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = layers.unembed(h_last, head, cfg.tie_embeddings)
        return logits, cache

    def _prefill_fill(self, p, batch, cache):
        """Run the stack while capturing per-layer K/V (or X) into cache."""
        cfg = self.cfg
        dt = _dtype(cfg)
        mode = attn.cache_mode_for(cfg)

        if cfg.enc_dec:
            enc_x = batch["enc_embeds"].astype(dt)
            ne = enc_x.shape[-2]
            enc_x = enc_x + p["enc_pos"][:ne][None]
            enc_h = transformer.uniform_stack(
                p["encoder"], enc_x, cfg, positions=jnp.arange(ne),
                mask_kind="none")
            enc_h = layers.norm(enc_h, p["enc_ln"], cfg.norm)
            cache["enc_len"] = jnp.full((enc_h.shape[0],), ne, jnp.int32)
            if "enc_out" in cache:
                cache["enc_out"] = _fill_seq(cache["enc_out"], enc_h)
            # decoder prompt = BOS only in serving; fill self cache for it
            x = layers.embed(batch["tokens"], p["embed"])
            nd = x.shape[-2]
            x = x + p["dec_pos"][:nd][None]
            h, new_attn, cross = _capture_uniform(
                p["layers"], x, cfg, jnp.arange(nd), cache["attn"], mode,
                enc_out=enc_h)
            cache["attn"] = new_attn
            if "cross_k" in cache:
                cache["cross_k"] = _fill_seq(cache["cross_k"], cross[0],
                                             layer_axis=True)
                cache["cross_v"] = _fill_seq(cache["cross_v"], cross[1],
                                             layer_axis=True)
            elif "cross_v" in cache:
                cache["cross_v"] = _fill_seq(cache["cross_v"], cross[1],
                                             layer_axis=True)
            return cache, layers.norm(h, p["final_ln"], cfg.norm)

        x = self._embed_in(p, batch)
        n = x.shape[-2]
        positions = jnp.arange(n)
        if cfg.family == "ssm":
            h, states = _capture_ssm(p["layers"], x, cfg)
            cache["ssm"] = states
        elif cfg.family == "hybrid":
            h, a, s = _capture_hybrid(p["layers"], x, cfg, positions,
                                      cache["attn"], mode)
            cache["attn"], cache["ssm"] = a, s
        else:
            h, new_attn, _ = _capture_uniform(p["layers"], x, cfg, positions,
                                              cache["attn"], mode)
            cache["attn"] = new_attn
        return cache, layers.norm(h, p["final_ln"], cfg.norm)

    def decode_step(self, p, cache, token, pos):
        """One token for every sequence in the batch.
        token (B,) int32 (or embeds (B, D)); pos (B,) int32 positions."""
        cfg = self.cfg
        dt = _dtype(cfg)
        if token.ndim == 1:
            x = layers.embed(token, p["embed"])[:, None, :]
            if cfg.family == "dense" and cfg.tie_embeddings:
                x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        else:
            x = token.astype(dt)[:, None, :]
        if cfg.pos_emb == "absolute" and "dec_pos" in p:
            x = x + jnp.take(p["dec_pos"], pos, axis=0)[:, None, :]

        n_layers = cfg.num_layers
        window, theta = transformer._layer_windows(cfg, n_layers)

        if cfg.family == "ssm":
            def body(h, xs):
                pl, st = xs
                hn = layers.norm(h, pl["ln"], cfg.norm)
                o, st2 = ssm.mamba_decode_step(pl["mamba"], hn, st,
                                               cfg.d_model, cfg.ssm)
                return h + o, st2
            h, states = jax.lax.scan(body, x, (p["layers"], cache["ssm"]),
                                     unroll=util.scan_unroll())
            cache = dict(cache, ssm=states)
        elif cfg.family == "hybrid":
            h, cache = self._decode_hybrid(p, x, cache, pos)
        elif cfg.enc_dec:
            h, cache = self._decode_encdec(p, x, cache, pos)
        else:
            def body(h, xs):
                pl, kv, win, th = xs
                hn = layers.norm(h, pl["ln1"], cfg.norm)
                a, kv2 = attn.attention_decode(
                    pl["attn"], hn, kv, pos, transformer._with_theta(cfg, th),
                    window=win)
                h = h + a
                hn2 = layers.norm(h, pl["ln2"], cfg.norm)
                if "moe" in pl:
                    f, _ = moe.moe_ffn(pl["moe"], hn2, cfg.moe, cfg.act)
                else:
                    f = layers.mlp(hn2, pl["mlp"], cfg.act)
                return h + f, kv2
            h, new_kv = jax.lax.scan(body, x,
                                     (p["layers"], cache["attn"], window, theta),
                                     unroll=util.scan_unroll())
            cache = dict(cache, attn=new_kv)

        h = layers.norm(h, p["final_ln"], cfg.norm)
        head = p["embed"] if cfg.tie_embeddings else p["lm_head"]
        return layers.unembed(h[:, 0], head, cfg.tie_embeddings), cache

    # ----------------------------------------------------- paged serving
    def supports_paged(self) -> bool:
        """Paged decode covers the uniform decoder families (dense / moe
        / vlm). SSM state is O(1)/token (nothing to page), hybrid and
        enc-dec carry extra non-token-indexed cache tensors — they stay
        on the dense pool until a later PR."""
        cfg = self.cfg
        return bool(cfg.num_heads) and cfg.family not in ("ssm", "hybrid") \
            and not cfg.enc_dec

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         mesh=None):
        """Block-pool decode cache: the per-layer KVCache with the batch
        axis as physical block id and the seq axis as in-block offset —
        leaves (L, NB, BS, ...). Layout (kv/xv/x, int8) is identical to
        the dense cache, so paging is layout-agnostic.

        mesh: optional serving mesh — the pool is laid out head-sharded
        over the "model" axis (sharding/specs.paged_pool_shardings) so
        each device holds only its head-slice of every block. None (the
        default) keeps the single-device layout bit-for-bit."""
        if not self.supports_paged():
            raise ValueError(
                f"paged cache unsupported for family {self.cfg.family!r}")
        cfg = self.cfg
        dt = _dtype(cfg)

        def build():
            return {"attn": _stack_pytrees(
                [attn.init_kv_cache(cfg, num_blocks, block_size, dt)
                 for _ in range(cfg.num_layers)])}
        if mesh is None:
            return jax.jit(build)()
        # allocate each shard on its own device: a whole pool built on
        # one device first would not fit there beside the weights
        from repro.sharding import specs
        return jax.jit(build, out_shardings=specs.paged_pool_shardings(
            jax.eval_shape(build), mesh))()

    def decode_paged(self, p, cache, tables, tokens, pos,
                     blocks_used=None):
        """n tokens per sequence through the paged cache — the single
        static-shape graph serving both chunked prefill (n = chunk) and
        decode ticks (n = 1).

        tokens (B, n) int32; pos (B,) position of the first new token;
        tables (B, nbk) block tables. Returns (logits (B, n, V), cache);
        the caller indexes the logits row of the last real token
        (trailing rows of a padded final chunk are discarded).

        blocks_used (B,) int32 (optional): live blocks per sequence,
        covering every written position (ceil((pos + n)/block_size)).
        When given — and the planned backend supports the streamed
        schedule — attention streams physical blocks with a used-length
        early exit instead of gathering the full logical view, so tick
        cost scales with actual sequence length instead of max_len.
        """
        cfg = self.cfg
        x = layers.embed(tokens, p["embed"])
        if cfg.family == "dense" and cfg.tie_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        n = tokens.shape[1]
        if cfg.pos_emb == "absolute" and "dec_pos" in p:
            qpos = pos[:, None] + jnp.arange(n)[None, :]
            x = x + jnp.take(p["dec_pos"], qpos, axis=0)

        window, theta = transformer._layer_windows(cfg, cfg.num_layers)

        def body(h, xs):
            pl, kv, win, th = xs
            hn = layers.norm(h, pl["ln1"], cfg.norm)
            a, kv2 = attn.attention_decode_paged(
                pl["attn"], hn, kv, tables, pos,
                transformer._with_theta(cfg, th), window=win,
                blocks_used=blocks_used)
            h = h + a
            hn2 = layers.norm(h, pl["ln2"], cfg.norm)
            if "moe" in pl:
                f, _ = moe.moe_ffn(pl["moe"], hn2, cfg.moe, cfg.act)
            else:
                f = layers.mlp(hn2, pl["mlp"], cfg.act)
            return h + f, kv2

        h, new_kv = jax.lax.scan(body, x,
                                 (p["layers"], cache["attn"], window, theta),
                                 unroll=util.scan_unroll())
        cache = dict(cache, attn=new_kv)
        h = layers.norm(h, p["final_ln"], cfg.norm)
        head = p["embed"] if cfg.tie_embeddings else p["lm_head"]
        return layers.unembed(h, head, cfg.tie_embeddings), cache

    def _decode_hybrid(self, p, x, cache, pos):
        cfg = self.cfg
        per = cfg.attn_every
        take = lambda t, i: jax.tree_util.tree_map(lambda a: a[i], t)

        def body(h, xs):
            blk, kv, sstates = xs
            new_s = []
            for i in range(per):
                if i == 0:
                    hn = layers.norm(h, blk["attn_ln"], cfg.norm)
                    a, kv = attn.attention_decode(blk["attn"], hn, kv, pos, cfg)
                    h = h + a
                else:
                    pl = take(blk["mamba_ln"], i - 1)
                    pm = take(blk["mamba"], i - 1)
                    hn = layers.norm(h, pl, cfg.norm)
                    o, st = ssm.mamba_decode_step(pm, hn, take(sstates, i - 1),
                                                  cfg.d_model, cfg.ssm)
                    h = h + o
                    new_s.append(st)
                pfl = take(blk["ffn_ln"], i)
                hn2 = layers.norm(h, pfl, cfg.norm)
                if i % 2 == 1:
                    f, _ = moe.moe_ffn(take(blk["moe"], i // 2), hn2,
                                       cfg.moe, cfg.act)
                else:
                    f = layers.mlp(hn2, take(blk["mlp"], i // 2), cfg.act)
                h = h + f
            return h, (kv, _stack_pytrees(new_s))

        h, (new_kv, new_ssm) = jax.lax.scan(
            body, x, (p["layers"], cache["attn"], cache["ssm"]),
            unroll=util.scan_unroll())
        return h, dict(cache, attn=new_kv, ssm=new_ssm)

    def _decode_encdec(self, p, x, cache, pos):
        cfg = self.cfg
        mode = attn.cache_mode_for(cfg)

        def body(h, xs):
            pl, kv, cross = xs
            hn = layers.norm(h, pl["ln1"], cfg.norm)
            a, kv2 = attn.attention_decode(pl["attn"], hn, kv, pos, cfg)
            h = h + a
            hx = layers.norm(h, pl["lnx"], cfg.norm)
            xa = _cross_decode(pl["xattn"], hx, cross, cfg, cache, mode)
            h = h + xa
            hn2 = layers.norm(h, pl["ln2"], cfg.norm)
            h = h + layers.mlp(hn2, pl["mlp"], cfg.act)
            return h, kv2

        if mode == "kv":
            cross_xs = (cache["cross_k"], cache["cross_v"])
        else:
            cross_xs = (cache["cross_v"],)
        h, new_kv = jax.lax.scan(body, x, (p["layers"], cache["attn"], cross_xs),
                                 unroll=util.scan_unroll())
        return h, dict(cache, attn=new_kv)


# --------------------------------------------------------------- internals

def _stack_pytrees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _fill_seq(buf, val, layer_axis: bool = False):
    """Write val into the leading positions of a max_len buffer (origin
    update-slice; val may be shorter than buf along the seq axis)."""
    return jax.lax.dynamic_update_slice(
        buf, val.astype(buf.dtype),
        (jnp.zeros((), jnp.int32),) * buf.ndim)


def _capture_uniform(params, x, cfg, positions, cache_stack, mode,
                     enc_out=None):
    """uniform_stack + fill per-layer decode caches (prefill path)."""
    n_layers = jax.tree_util.tree_leaves(params)[0].shape[0]
    window, theta = transformer._layer_windows(cfg, n_layers)
    dt = x.dtype

    def body(h, xs):
        pl, kv, win, th = xs
        hn = layers.norm(h, pl["ln1"], cfg.norm)
        # capture cache entries from the pre-attention normed input
        if mode == "kv":
            k = jnp.einsum("bnd,dhe->bnhe", hn, pl["attn"]["wk"].astype(dt))
            if "bk" in pl["attn"]:
                k = k + pl["attn"]["bk"][None, None].astype(dt)
            if cfg.pos_emb == "rope":
                k = layers.apply_rope(k.swapaxes(1, 2), positions,
                                      th).swapaxes(1, 2)
            kv = attn.write_kv(kv, k, None, cfg)
        else:
            kv = attn.write_x(kv, hn, cfg)
        if kv.v is not None:
            v = jnp.einsum("bnd,dhe->bnhe", hn, pl["attn"]["wv"].astype(dt))
            if "bv" in pl["attn"]:
                v = v + pl["attn"]["bv"][None, None].astype(dt)
            kv = attn.write_kv(kv, None, v, cfg)
        a = attn.attention_full(pl["attn"], hn, hn,
                                transformer._with_theta(cfg, th),
                                positions_q=positions, positions_kv=positions,
                                mask_kind="causal", window=win)
        h = h + a
        cross_k = cross_v = jnp.zeros((0,), dt)
        if enc_out is not None and "xattn" in pl:
            hx = layers.norm(h, pl["lnx"], cfg.norm)
            xa = attn.attention_full(pl["xattn"], hx, enc_out, cfg,
                                     positions_q=positions,
                                     positions_kv=jnp.arange(enc_out.shape[-2]),
                                     mask_kind="none")
            h = h + xa
            cross_k = jnp.einsum("bnd,dhe->bnhe", enc_out,
                                 pl["xattn"]["wk"].astype(dt))
            cross_v = jnp.einsum("bnd,dhe->bnhe", enc_out,
                                 pl["xattn"]["wv"].astype(dt))
        hn2 = layers.norm(h, pl["ln2"], cfg.norm)
        if "moe" in pl:
            f, _ = moe.moe_ffn(pl["moe"], hn2, cfg.moe, cfg.act)
        else:
            f = layers.mlp(hn2, pl["mlp"], cfg.act)
        return h + f, (kv, cross_k, cross_v)

    h, (new_kv, ck, cv) = jax.lax.scan(body, x, (params, cache_stack,
                                                 window, theta),
                                       unroll=util.scan_unroll())
    return h, new_kv, (ck, cv)


def _capture_ssm(params, x, cfg):
    def body(h, pl):
        hn = layers.norm(h, pl["ln"], cfg.norm)
        o, st = ssm.mamba_block(pl["mamba"], hn, cfg.d_model, cfg.ssm,
                                return_state=True)
        return h + o, st
    return jax.lax.scan(body, x, params, unroll=util.scan_unroll())


def _capture_hybrid(params, x, cfg, positions, attn_cache, mode):
    per = cfg.attn_every
    take = lambda t, i: jax.tree_util.tree_map(lambda a: a[i], t)
    dt = x.dtype

    def body(h, xs):
        blk, kv = xs
        states = []
        for i in range(per):
            if i == 0:
                hn = layers.norm(h, blk["attn_ln"], cfg.norm)
                if mode == "kv":
                    k = jnp.einsum("bnd,dhe->bnhe", hn,
                                   blk["attn"]["wk"].astype(dt))
                    kv = attn.write_kv(kv, k, None, cfg)
                else:
                    kv = attn.write_x(kv, hn, cfg)
                if kv.v is not None:
                    v = jnp.einsum("bnd,dhe->bnhe", hn,
                                   blk["attn"]["wv"].astype(dt))
                    kv = attn.write_kv(kv, None, v, cfg)
                h = h + attn.attention_full(blk["attn"], hn, hn, cfg,
                                            positions_q=positions,
                                            positions_kv=positions,
                                            mask_kind="causal")
            else:
                pl = take(blk["mamba_ln"], i - 1)
                pm = take(blk["mamba"], i - 1)
                hn = layers.norm(h, pl, cfg.norm)
                o, st = ssm.mamba_block(pm, hn, cfg.d_model, cfg.ssm,
                                        return_state=True)
                h = h + o
                states.append(st)
            pfl = take(blk["ffn_ln"], i)
            hn2 = layers.norm(h, pfl, cfg.norm)
            if i % 2 == 1:
                f, _ = moe.moe_ffn(take(blk["moe"], i // 2), hn2,
                                   cfg.moe, cfg.act)
            else:
                f = layers.mlp(hn2, take(blk["mlp"], i // 2), cfg.act)
            h = h + f
        return h, (kv, _stack_pytrees(states))

    h, (new_kv, new_ssm) = jax.lax.scan(body, x, (params, attn_cache),
                                        unroll=util.scan_unroll())
    return h, new_kv, new_ssm


def _cross_decode(p, x_new, cross, cfg, cache, mode):
    """Cross-attention during decode. x_new (B,1,D). Scores beyond the
    true encoder length (zero-padded buffer region) are masked."""
    import math as _m
    dt = x_new.dtype
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / _m.sqrt(dh)
    enc_len = cache["enc_len"]                           # (B,)
    if mode == "kv":
        ck, cv = cross
        q = jnp.einsum("bnd,dhe->bhne", x_new, p["wq"].astype(dt))
        B = q.shape[0]
        S = ck.shape[1]
        qg = q.reshape(B, Hkv, H // Hkv, dh)
        s = jnp.einsum("bgre,bsge->bgrs", qg.astype(jnp.float32),
                       ck.astype(jnp.float32)).reshape(B, H, 1, S) * scale
    else:
        (cv,) = cross
        from repro.core import score_backend as sb
        be = sb.plan(cfg).backend
        s = be.scores(x_new, cache["enc_out"], attn.score_weights(p),
                      scale=scale)
        B, S = s.shape[0], s.shape[-1]
    valid = jnp.arange(S)[None, :] < enc_len[:, None]    # (B, S)
    s = s + jnp.where(valid, 0.0, attn.NEG_INF)[:, None, None, :]
    a = jax.nn.softmax(s, axis=-1).astype(dt)
    ag = a.reshape(B, Hkv, H // Hkv, S)
    o = jnp.einsum("bgrs,bsge->bgre", ag, cv.astype(dt)).reshape(B, H, 1, dh)
    return jnp.einsum("bhne,hed->bnd", o, p["wo"].astype(dt))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
