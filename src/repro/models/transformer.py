"""Model assembly for all assigned architecture families.

Layers are stacked with ``lax.scan`` over grouped parameter pytrees (bounded HLO size
— essential for compiling 62-layer models against a 512-device mesh).  Heterogeneous
layer patterns (gemma2 local/global, xlstm 3×mLSTM+sLSTM, zamba2 6×mamba+shared-attn)
scan over *pattern groups*.

Interface (per built model):
  init(key) -> params
  forward(params, batch)                        -> logits               (train)
  prefill(params, batch, cache_len)             -> (logits, caches)
  decode_step(params, tokens, caches, pos)      -> (logits, caches)
  loss(params, batch)                           -> (scalar, metrics)
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import attention as att
from repro.models import mamba as mmb
from repro.models import moe as moe_lib
from repro.models import xlstm as xl
from repro.models.layers import (embed_init, embed_lookup, fenced_rows,
                                 mlp, mlp_init, rmsnorm, rmsnorm_init,
                                 sinusoidal_pos, softcap, unembed,
                                 use_compute_dtype)
from repro.utils.sharding import constrain

F32 = jnp.float32


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _block_init(key, cfg, kind: str, dtype=jnp.float32):
    """One residual block. kind: dense|local|mla|moe|mamba|mlstm|slstm|enc|dec."""
    ks = jax.random.split(key, 4)
    p = {}
    if kind == "mamba":
        p["norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["mixer"] = mmb.mamba_init(ks[0], cfg, dtype)
        return p
    if kind == "mlstm":
        p["norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["mixer"] = xl.mlstm_block_init(ks[0], cfg, dtype)
        return p
    if kind == "slstm":
        p["norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["mixer"] = xl.slstm_block_init(ks[0], cfg, dtype)
        return p
    p["norm1"] = rmsnorm_init(cfg.d_model, dtype)
    p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
    if kind == "mla":
        p["attn"] = att.mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = att.attn_init(ks[0], cfg, dtype=dtype)
    if kind == "dec":                               # whisper decoder: + cross attn
        p["norm_x"] = rmsnorm_init(cfg.d_model, dtype)
        p["xattn"] = att.attn_init(ks[2], cfg, cross=True, dtype=dtype)
    if kind == "moe":
        p["moe"] = moe_lib.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                            gated=cfg.act != "gelu_nogate", dtype=dtype)
    if cfg.name.startswith("gemma2"):               # sandwich norms
        p["post_norm1"] = rmsnorm_init(cfg.d_model, dtype)
        p["post_norm2"] = rmsnorm_init(cfg.d_model, dtype)
    return p


def _maybe_post(p, name, x, cfg):
    return rmsnorm(p[name], x, cfg.norm_eps) if name in p else x


def _block_apply(p, h, cfg, kind: str, *, positions=None, mode="train",
                 cache=None, pos=None, prefix_len=None, enc_out=None,
                 cache_len=None, causal=True):
    """Returns (h, new_cache, aux)."""
    aux = jnp.zeros((), F32)
    window = cfg.local_window if kind == "local" else None
    new_cache = None

    if kind in ("mamba", "mlstm", "slstm"):
        hin = rmsnorm(p["norm"], h, cfg.norm_eps)
        if kind == "mamba":
            if mode == "decode":
                y, new_cache = mmb.mamba_step(p["mixer"], hin, cfg, cache)
            elif mode == "prefill":
                y, new_cache = mmb.mamba_full(p["mixer"], hin, cfg, return_cache=True)
            else:
                y = mmb.mamba_full(p["mixer"], hin, cfg,
                                   use_kernel=cfg.scan_method == "kernel")
        elif kind == "mlstm":
            if mode == "decode":
                y, new_cache = xl.mlstm_block_step(p["mixer"], hin, cfg, cache)
            elif mode == "prefill":
                y, new_cache = xl.mlstm_block(p["mixer"], hin, cfg, return_cache=True)
            else:
                y = xl.mlstm_block(p["mixer"], hin, cfg)
        else:
            if mode == "decode":
                y, new_cache = xl.slstm_block_step(p["mixer"], hin, cfg, cache)
            elif mode == "prefill":
                y, new_cache = xl.slstm_block(p["mixer"], hin, cfg, return_cache=True)
            else:
                y = xl.slstm_block(p["mixer"], hin, cfg)
        return h + y, new_cache, aux

    # ---- attention sub-block ----
    with jax.named_scope("attention"):
        hin = rmsnorm(p["norm1"], h, cfg.norm_eps)
        full_cache = cache
        if kind == "dec" and cache is not None:
            cache = cache["kv"]          # self-attn part of the enc-dec cache
        if kind == "mla":
            if mode == "decode":
                y, new_cache = att.mla_decode(p["attn"], hin, cfg, cache, pos)
            elif mode == "prefill":
                y, new_cache = att.mla_full(p["attn"], hin, cfg, positions=positions,
                                            return_cache=True, cache_len=cache_len)
            else:
                y = att.mla_full(p["attn"], hin, cfg, positions=positions)
        else:
            if mode == "decode":
                # a "pages" leaf marks the paged KV layout (continuous batching);
                # plain {"k","v"} caches stay on the dense kv_layout baseline
                dec = (att.attn_decode_paged if (cache is not None
                                                 and "pages" in cache)
                       else att.attn_decode)
                y, new_cache = dec(p["attn"], hin, cfg, cache, pos,
                                   window=window)
            elif mode == "prefill":
                y, new_cache = att.attn_full(p["attn"], hin, cfg, positions=positions,
                                             causal=causal, window=window,
                                             prefix_len=prefix_len, return_cache=True,
                                             cache_len=cache_len)
            else:
                y = att.attn_full(p["attn"], hin, cfg, positions=positions,
                                  causal=causal, window=window, prefix_len=prefix_len)
        y = _maybe_post(p, "post_norm1", y, cfg)
        h = h + y

    # ---- cross attention (whisper decoder) ----
    if kind == "dec":
        hin = rmsnorm(p["norm_x"], h, cfg.norm_eps)
        if mode == "decode":
            y = att.attn_cross_decode(p["xattn"], hin, cfg, full_cache["xkv"])
        else:
            y = att.attn_full(p["xattn"], hin, cfg, positions=None, kv_x=enc_out,
                              use_rope=False)
            if mode == "prefill":
                new_cache = {"kv": new_cache,
                             "xkv": att.cross_kv(p["xattn"], enc_out, cfg)}
        h = h + y
        if mode == "decode":
            new_cache = {"kv": new_cache, "xkv": full_cache["xkv"]}
    elif mode in ("prefill", "decode") and new_cache is not None:
        pass

    # ---- mlp / moe sub-block ----
    with jax.named_scope("mlp"):
        hin = rmsnorm(p["norm2"], h, cfg.norm_eps)
        if kind == "moe":
            y, aux = moe_lib.moe_apply(p["moe"], hin, cfg, no_drop=mode == "decode")
        else:
            y = mlp(p["mlp"], hin, act=cfg.act)
        y = _maybe_post(p, "post_norm2", y, cfg)
        return h + y, new_cache, aux


def _decode_cache_for(kind, cfg, h, cache_len, block_params=None, enc_out=None):
    """Empty caches for pure-decode dry-runs (shape/dtype only)."""
    b = h.shape[0]
    dt = h.dtype
    hd = cfg.head_dim_
    if kind in ("dense", "local", "global", "moe", "enc", "mla_naive"):
        return {"k": jnp.zeros((b, cache_len, cfg.n_kv_heads, hd), dt),
                "v": jnp.zeros((b, cache_len, cfg.n_kv_heads, hd), dt)}
    if kind == "dec":
        return {"kv": {"k": jnp.zeros((b, cache_len, cfg.n_kv_heads, hd), dt),
                       "v": jnp.zeros((b, cache_len, cfg.n_kv_heads, hd), dt)},
                "xkv": {"k": jnp.zeros((b, cfg.enc_len, cfg.n_kv_heads, hd), dt),
                        "v": jnp.zeros((b, cfg.enc_len, cfg.n_kv_heads, hd), dt)}}
    if kind == "mla":
        m = cfg.mla
        return {"latent": jnp.zeros((b, cache_len, m.kv_lora_rank), dt),
                "k_rope": jnp.zeros((b, cache_len, m.qk_rope_head_dim), dt)}
    if kind == "mamba":
        s = cfg.ssm
        conv_dim = s.expand * cfg.d_model + 2 * s.n_groups * s.d_state
        return {"conv": jnp.zeros((b, s.conv_kernel - 1, conv_dim), dt),
                "ssm": jnp.zeros((b, s.n_heads, s.d_state, s.head_dim), F32)}
    if kind == "mlstm":
        x = cfg.xlstm
        d_inner = int(x.proj_factor * cfg.d_model)
        hdx = d_inner // x.n_heads
        return {"conv": jnp.zeros((b, x.conv_kernel - 1, d_inner), dt),
                "c": jnp.zeros((b, x.n_heads, hdx, hdx), F32),
                "n": jnp.zeros((b, x.n_heads, hdx), F32),
                "m": jnp.full((b, x.n_heads), -1e30, F32)}
    if kind == "slstm":
        x = cfg.xlstm
        hdx = cfg.d_model // x.n_heads
        z = jnp.zeros((b, x.n_heads, hdx), F32)
        return {"conv": jnp.zeros((b, x.conv_kernel - 1, cfg.d_model), dt),
                "rec": (z, z, jnp.full((b, x.n_heads, hdx), -1e30, F32), z)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# the LM
# ---------------------------------------------------------------------------


class TransformerLM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.pattern = self._pattern()
        self.group = len(self.pattern)
        assert cfg.n_layers % self.group == 0 or cfg.family == "hybrid", \
            (cfg.name, cfg.n_layers, self.pattern)

    # ---- architecture pattern ----
    def _pattern(self):
        cfg = self.cfg
        if cfg.layer_pattern:
            return tuple(cfg.layer_pattern)
        if cfg.family == "xlstm":
            k = cfg.xlstm.slstm_every
            return tuple(["mlstm"] * (k - 1) + ["slstm"])
        if cfg.family == "moe":
            return ("moe",)
        if cfg.family == "encdec":
            return ("dec",)
        if cfg.mla is not None:
            return ("mla",)
        return ("dense",)

    # ---- init ----
    def init(self, key, dtype=None):
        cfg = self.cfg
        dtype = dtype or jnp.float32
        keys = jax.random.split(key, 8)
        p = {"embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype,
                                 scale=cfg.d_model ** -0.5),
             "final_norm": rmsnorm_init(cfg.d_model, dtype)}

        def stack_init(key, n, kinds):
            def one(k):
                ks = jax.random.split(k, len(kinds))
                return {f"sub{i}": _block_init(ks[i], cfg, kind, dtype)
                        for i, kind in enumerate(kinds)}
            return jax.vmap(one)(jax.random.split(key, n))

        if cfg.family == "hybrid":
            iv = cfg.shared_attn_interval
            n_groups = cfg.n_layers // iv
            trailing = cfg.n_layers - n_groups * iv
            p["stack"] = stack_init(keys[1], n_groups, ("mamba",) * iv)
            p["shared"] = _block_init(keys[2], cfg, "dense", dtype)
            if trailing:
                p["tail"] = stack_init(keys[3], trailing, ("mamba",))
        elif cfg.family == "encdec":
            p["enc_stack"] = stack_init(keys[1], cfg.n_enc_layers, ("enc",))
            p["stack"] = stack_init(keys[2], cfg.n_layers, ("dec",))
            p["enc_norm"] = rmsnorm_init(cfg.d_model, dtype)
        else:
            moe = self.cfg.moe
            pre = moe.first_k_dense if moe else 0
            if pre:
                p["pre"] = stack_init(keys[3], pre, ("dense",))
            p["stack"] = stack_init(
                keys[1], (cfg.n_layers - pre) // self.group, self.pattern)
        return p

    # ---- layer-stack scan helper ----
    def _scan_stack(self, params, h, kinds, *, mode, positions=None, caches=None,
                    pos=None, prefix_len=None, enc_out=None, cache_len=None,
                    causal=True):
        cfg = self.cfg

        def group_body(h, p_group, cache_group):
            new_caches = []
            aux = jnp.zeros((), F32)
            for i, kind in enumerate(kinds):
                c_i = None if cache_group is None else cache_group[f"sub{i}"]
                h, nc, a = _block_apply(
                    p_group[f"sub{i}"], h, cfg, kind, positions=positions,
                    mode=mode, cache=c_i, pos=pos, prefix_len=prefix_len,
                    enc_out=enc_out, cache_len=cache_len, causal=causal)
                aux = aux + a
                new_caches.append(nc)
            out_cache = ({f"sub{i}": c for i, c in enumerate(new_caches)}
                         if new_caches[0] is not None else None)
            return h, out_cache, aux

        if not cfg.scan_layers:
            # Unrolled layers: bigger HLO, but cost_analysis counts every layer
            # (XLA counts while-loop bodies ONCE — see DESIGN.md §6) — used by the
            # dry-run so the roofline terms are exact.
            gb = (jax.checkpoint(group_body) if (cfg.remat and mode == "train")
                  else group_body)
            n = jax.tree.leaves(params)[0].shape[0]
            auxs, ncs = jnp.zeros((), F32), []
            for i in range(n):
                p_g = jax.tree.map(lambda a: a[i], params)
                c_g = None if caches is None else jax.tree.map(
                    lambda a: a[i], caches)
                h, nc, aux = gb(h, p_g, c_g)
                auxs = auxs + aux
                ncs.append(nc)
            new_caches = (None if ncs[0] is None
                          else jax.tree.map(lambda *a: jnp.stack(a), *ncs))
            return h, new_caches, auxs

        def f(carry, xs):
            h = carry
            p_g, c_g = (xs, None) if caches is None else xs
            h, nc, aux = group_body(h, p_g, c_g)
            return h, (nc, aux)      # nc=None is an empty pytree — fine for scan ys

        body = jax.checkpoint(f) if (cfg.remat and mode == "train") else f
        xs = params if caches is None else (params, caches)
        h, (new_caches, aux) = jax.lax.scan(body, h, xs)
        return h, new_caches, jnp.sum(aux)

    # ---- embedding helpers ----
    def _embed(self, params, tokens):
        cfg = self.cfg
        h = embed_lookup(params["embed"], tokens)
        if cfg.scale_embed:
            h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
        return h

    @jax.named_scope("head")
    def _logits(self, params, h):
        cfg = self.cfg
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = unembed(params["embed"], h)
        logits = softcap(logits, cfg.final_softcap)
        if cfg.padded_vocab != cfg.vocab_size:      # mask padded vocab rows
            iota = jnp.arange(cfg.padded_vocab, dtype=jnp.int32)
            logits = jnp.where(iota < cfg.vocab_size, logits, -1e30)
        return logits

    def _encode(self, params, enc_embed):
        """Whisper encoder over (stub) frame embeddings."""
        cfg = self.cfg
        h = enc_embed.astype(jnp.dtype(cfg.dtype))
        h = h + sinusoidal_pos(h.shape[1], cfg.d_model, h.dtype)[None]
        h, _, _ = self._scan_stack(params["enc_stack"], h, ("enc",),
                                   mode="train", positions=None, causal=False)
        return rmsnorm(params["enc_norm"], h, cfg.norm_eps)

    # ---- forward paths ----
    def _run(self, params, batch, *, mode, cache_len=None, caches=None, pos=None):
        with use_compute_dtype(jnp.dtype(self.cfg.dtype)):
            return self._run_inner(params, batch, mode=mode, cache_len=cache_len,
                                   caches=caches, pos=pos)

    def _run_inner(self, params, batch, *, mode, cache_len=None, caches=None,
                   pos=None):
        cfg = self.cfg
        tokens = batch["tokens"]
        h = self._embed(params, tokens)
        h = constrain(h, "dp", None, None)
        prefix_len = None
        enc_out = None

        if cfg.family == "vlm" and mode != "decode":
            img = batch["img_embed"].astype(h.dtype)
            h = jnp.concatenate([img, h], axis=1)
            prefix_len = cfg.n_img_tokens
        if cfg.family == "encdec" and mode != "decode":
            enc_out = self._encode(params, batch["enc_embed"])
        if cfg.family == "encdec":
            # whisper-style absolute decoder positions (sinusoidal stand-in)
            if mode == "decode":
                d = cfg.d_model
                inv = jnp.exp(jnp.arange(0, d, 2, dtype=F32)
                              * (-jnp.log(10000.0) / d))
                ang = pos.astype(F32) * inv
                pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])
                pe = pe.reshape(2, -1).T.reshape(-1)          # interleave sin/cos
                h = h + pe.astype(h.dtype)[None, None, :]
            else:
                h = h + sinusoidal_pos(h.shape[1], cfg.d_model, h.dtype)[None]

        s = h.shape[1]
        if mode == "decode":
            positions = None
        else:
            positions = jnp.arange(s, dtype=jnp.int32)[None, :]

        aux_total = jnp.zeros((), F32)
        new_caches = {}

        if cfg.family == "hybrid":
            iv = cfg.shared_attn_interval
            # shared attention block applied after each group of `iv` mamba layers
            def with_shared(h, stack_caches, shared_caches):
                def group_body(h, xs):
                    if stack_caches is None:
                        p_g, sc, shc = xs, None, None
                    else:
                        p_g, (sc, shc) = xs
                    new_g = []
                    for i in range(iv):
                        c_i = None if sc is None else jax.tree.map(
                            lambda a: a[i], sc)
                        h, nc, _ = _block_apply(
                            p_g[f"sub{i}"], h, cfg, "mamba", positions=positions,
                            mode=mode, cache=c_i, pos=pos, cache_len=cache_len)
                        new_g.append(nc)
                    # shared block (weights shared across invocations)
                    h, nc_sh, _ = _block_apply(
                        params["shared"], h, cfg, "dense", positions=positions,
                        mode=mode, cache=shc, pos=pos, cache_len=cache_len)
                    ys = None
                    if new_g[0] is not None:
                        stacked = jax.tree.map(lambda *a: jnp.stack(a), *new_g)
                        ys = (stacked, nc_sh)
                    return h, ys
                if not cfg.scan_layers:
                    gb = (jax.checkpoint(group_body)
                          if (cfg.remat and mode == "train") else group_body)
                    yss = []
                    n = jax.tree.leaves(params["stack"])[0].shape[0]
                    for gi in range(n):
                        p_g = jax.tree.map(lambda a: a[gi], params["stack"])
                        if stack_caches is None:
                            xs_i = p_g
                        else:
                            xs_i = (p_g, (jax.tree.map(lambda a: a[gi],
                                                       stack_caches),
                                          jax.tree.map(lambda a: a[gi],
                                                       shared_caches)))
                        h, ys_i = gb(h, xs_i)
                        yss.append(ys_i)
                    ys = (None if yss[0] is None
                          else jax.tree.map(lambda *a: jnp.stack(a), *yss))
                    return h, ys
                xs = (params["stack"] if stack_caches is None
                      else (params["stack"], (stack_caches, shared_caches)))
                h, ys = jax.lax.scan(group_body, h, xs)
                return h, ys
            stack_c = None if caches is None else caches["stack"]
            shared_c = None if caches is None else caches["shared"]
            h, ys = with_shared(h, stack_c, shared_c)
            if ys is not None:
                new_caches["stack"], new_caches["shared"] = ys
            if "tail" in params:
                tc = None if caches is None else caches["tail"]
                h, ntc, _ = self._scan_stack(
                    params["tail"], h, ("mamba",), mode=mode, positions=positions,
                    caches=tc, pos=pos, cache_len=cache_len)
                if ntc is not None:
                    new_caches["tail"] = ntc
        else:
            if "pre" in params:
                pc = None if caches is None else caches["pre"]
                h, npc, _ = self._scan_stack(
                    params["pre"], h, ("dense",), mode=mode, positions=positions,
                    caches=pc, pos=pos, cache_len=cache_len)
                if npc is not None:
                    new_caches["pre"] = npc
            sc = None if caches is None else caches["stack"]
            h, nsc, aux = self._scan_stack(
                params["stack"], h, self.pattern, mode=mode, positions=positions,
                caches=sc, pos=pos, prefix_len=prefix_len, enc_out=enc_out,
                cache_len=cache_len)
            aux_total = aux_total + aux
            if nsc is not None:
                new_caches["stack"] = nsc

        logits = self._logits(params, h)
        if mode == "train":
            return logits, aux_total
        return logits, new_caches

    # ---- public API ----
    def forward(self, params, batch):
        logits, _ = self._run(params, batch, mode="train")
        return logits

    def loss(self, params, batch):
        cfg = self.cfg
        logits, aux = self._run(params, batch, mode="train")
        tokens = batch["tokens"]
        if cfg.family == "vlm":        # predictions for text positions only
            logits = logits[:, cfg.n_img_tokens:]
        targets = tokens[:, 1:]
        lg = logits[:, :-1].astype(F32)
        # vocab-parallel-friendly CE: logsumexp + masked correct-logit sum — no
        # cross-shard gather when the vocab axis is model-sharded.
        logz = jax.nn.logsumexp(lg, axis=-1)
        iota = jnp.arange(lg.shape[-1], dtype=jnp.int32)
        correct = jnp.sum(jnp.where(iota[None, None, :] == targets[..., None],
                                    lg, 0.0), axis=-1)
        nll = logz - correct
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:].astype(F32)
            ce = jnp.sum(nll * mask) / jnp.clip(jnp.sum(mask), 1.0)
        else:
            ce = jnp.mean(nll)
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    def prefill(self, params, batch, *, cache_len=None):
        logits, caches = self._run(params, batch, mode="prefill",
                                   cache_len=cache_len)
        return logits[:, -1], caches

    def decode_step(self, params, tokens, caches, pos):
        """tokens: (B,1) int32; pos: scalar int32 write position, or per-row
        (B,) int32 for attention-only models (continuous batching)."""
        with fenced_rows():       # a row's bits must not depend on the batch
            logits, caches = self._run(params, {"tokens": tokens},
                                       mode="decode", caches=caches, pos=pos)
        return logits[:, -1], caches

    # ---- decode-cache specs for dry-runs ----
    def empty_caches(self, batch_size: int, cache_len: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        h = jnp.zeros((batch_size, 1, cfg.d_model), dt)

        def stack_cache(n, kinds):
            def one(_):
                return {f"sub{i}": _decode_cache_for(k, cfg, h, cache_len)
                        for i, k in enumerate(kinds)}
            return jax.vmap(one)(jnp.arange(n))

        c = {}
        if cfg.family == "hybrid":
            iv = cfg.shared_attn_interval
            n_groups = cfg.n_layers // iv
            trailing = cfg.n_layers - n_groups * iv

            def one_group(_):
                sc = jax.vmap(lambda _: _decode_cache_for("mamba", cfg, h,
                                                          cache_len))(jnp.arange(iv))
                return (sc, _decode_cache_for("dense", cfg, h, cache_len))
            grouped = jax.vmap(one_group)(jnp.arange(n_groups))
            c["stack"], c["shared"] = grouped
            if trailing:
                c["tail"] = stack_cache(trailing, ("mamba",))
            return c
        moe = cfg.moe
        pre = moe.first_k_dense if moe else 0
        if pre:
            c["pre"] = stack_cache(pre, ("dense",))
        kinds = self.pattern
        c["stack"] = stack_cache((cfg.n_layers - pre) // self.group, kinds)
        return c
