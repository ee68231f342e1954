"""Model assembly for all assigned architectures.

Layer stacks are ``lax.scan`` over parameter pytrees stacked on a leading
axis (HLO size O(1) in depth — see DESIGN.md SS7). Heterogeneous patterns use
*grouped* scans whose body unrolls the group members:

  gemma3-4b   : scan over 5 groups of [5 local + 1 global] + tail of 4 local
  llama-vision: scan over 20 groups of [4 self + 1 cross]
  zamba2-7b   : scan over 13 groups of [6 mamba] + shared attn block (single
                weight copy, closure) + tail of 3 mamba
  others      : one homogeneous scanned stack

Decode states are pytrees stacked the same way as their stacks. The
homogeneous stack's scan reads each layer's KV cache in place (a scan
input only) and emits the token's K/V rows, which are written into the
cache once, after the scan; the grouped stacks thread each layer's cache
through their scans as input and output.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (KVCache, cross_attention, decode_attend,
                        decode_self_attention, init_attention, ring_slot,
                        self_attention, write_token_rows)
from .layers import (embed, init_embedding, init_mlp, init_rmsnorm, mlp,
                     rmsnorm, _dense_init)
from .mamba import init_mamba_block, init_mamba_state, mamba_block
from .moe import init_moe, moe_block
from .rwkv import RWKVState, init_rwkv_block, rwkv_block

Params = Dict[str, Any]


def _split_init(fn, key, n):
    """Stack n inits on a leading axis (vmap keeps this eval_shape-able)."""
    return jax.vmap(fn)(jax.random.split(key, n))


def _add_aux(a: Dict, b: Dict) -> Dict:
    out = dict(a)
    for k, v in b.items():
        out[k] = out.get(k, 0.0) + v
    return out


ZERO_AUX = {"moe_balance": 0.0, "moe_zloss": 0.0, "moe_drop_frac": 0.0}


# ---------------------------------------------------------------------------
# Transformer block (attention + FFN/MoE)
# ---------------------------------------------------------------------------

def init_tblock(key, cfg: ModelConfig, kind: str = "dense",
                cross: bool = False) -> Params:
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "ln1": init_rmsnorm(cfg.d_model, dt),
        "attn": init_attention(k1, cfg, cross=cross),
        "ln2": init_rmsnorm(cfg.d_model, dt),
    }
    if kind == "moe":
        p["ffn"] = init_moe(k2, cfg)
    else:
        p["ffn"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dt)
    return p


def tblock_fwd(p: Params, x, cfg, *, kind="dense", window=0):
    with jax.named_scope("attention"):
        h = self_attention(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                           cfg, window=window)
    x = x + h
    with jax.named_scope("mlp"):
        y = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            f, aux = moe_block(p["ffn"], y, cfg)
        else:
            f, aux = mlp(p["ffn"], y, cfg.act), ZERO_AUX
    return x + f, aux


def tblock_decode(p: Params, x, cache: KVCache, pos, cfg, *, kind="dense",
                  window=0, attend=decode_self_attention):
    """One block of single-token decode. Returns (x, what `attend` returns
    beside its output): the updated cache, or the token's (k, v) rows with
    `attend=decode_attend`."""
    with jax.named_scope("attention"):
        h, cache = attend(
            p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps), cache, pos, cfg,
            window=window)
    x = x + h
    with jax.named_scope("mlp"):
        y = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            f, _ = moe_block(p["ffn"], y, cfg)
        else:
            f = mlp(p["ffn"], y, cfg.act)
    return x + f, cache


def cross_block_fwd(p: Params, x, img, cfg):
    x = x + cross_attention(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                            img, cfg)
    return x + mlp(p["ffn"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg.act)


# ---------------------------------------------------------------------------
# Family plans
# ---------------------------------------------------------------------------

def _gemma_plan(cfg):
    """(n_groups, locals_per_group, tail_locals)."""
    r = cfg.local_global_ratio                       # 5 locals : 1 global
    group = r + 1
    n_groups = cfg.n_layers // group
    tail = cfg.n_layers - n_groups * group
    return n_groups, r, tail


def _vlm_plan(cfg):
    group = cfg.cross_attn_every                     # 4 self + 1 cross
    n_groups = cfg.n_layers // group
    assert n_groups * group == cfg.n_layers, "vlm layers must divide evenly"
    return n_groups, group - 1


def _hybrid_plan(cfg):
    group = cfg.shared_attn_every
    n_groups = cfg.n_layers // group
    tail = cfg.n_layers - n_groups * group
    return n_groups, group, tail


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    """Functional model wrapper for one ModelConfig."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- init ---------------------------------------------------------------

    def init(self, key) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        ks = jax.random.split(key, 8)
        p: Params = {}
        if cfg.n_codebooks:
            p["embed"] = {"table": _dense_init(
                ks[0], (cfg.n_codebooks, cfg.vocab, cfg.d_model), dt,
                scale=1.0)}
        else:
            p["embed"] = init_embedding(ks[0], cfg.vocab, cfg.d_model, dt)
        p["final_norm"] = init_rmsnorm(cfg.d_model, dt)
        if not cfg.tie_embeddings:
            if cfg.n_codebooks:
                p["lm_head"] = _dense_init(
                    ks[1], (cfg.n_codebooks, cfg.vocab, cfg.d_model), dt)
            else:
                p["lm_head"] = _dense_init(
                    ks[1], (cfg.vocab, cfg.d_model), dt)

        fam = cfg.family
        if fam in ("dense", "audio") and not cfg.local_global_ratio:
            p["blocks"] = _split_init(
                lambda k: init_tblock(k, cfg), ks[2], cfg.n_layers)
        elif cfg.local_global_ratio:                  # gemma3
            g, r, tail = _gemma_plan(cfg)
            p["local_groups"] = _split_init(
                lambda k: _split_init(lambda k2: init_tblock(k2, cfg), k, r),
                ks[2], g)
            p["global_groups"] = _split_init(
                lambda k: init_tblock(k, cfg), ks[3], g)
            if tail:
                p["local_tail"] = _split_init(
                    lambda k: init_tblock(k, cfg), ks[4], tail)
        elif fam == "vlm":
            g, n_self = _vlm_plan(cfg)
            p["self_groups"] = _split_init(
                lambda k: _split_init(lambda k2: init_tblock(k2, cfg), k,
                                      n_self), ks[2], g)
            p["cross_groups"] = _split_init(
                lambda k: init_tblock(k, cfg, cross=True), ks[3], g)
        elif fam == "moe":
            p["blocks"] = _split_init(
                lambda k: init_tblock(k, cfg, kind="moe"), ks[2],
                cfg.n_layers)
        elif fam == "ssm":
            p["blocks"] = _split_init(
                lambda k: init_rwkv_block(k, cfg), ks[2], cfg.n_layers)
        elif fam == "hybrid":
            g, per, tail = _hybrid_plan(cfg)
            p["mamba_groups"] = _split_init(
                lambda k: _split_init(lambda k2: init_mamba_block(k2, cfg),
                                      k, per), ks[2], g)
            p["shared_attn"] = init_tblock(ks[3], cfg)   # ONE weight copy
            if tail:
                p["mamba_tail"] = _split_init(
                    lambda k: init_mamba_block(k, cfg), ks[4], tail)
        else:
            raise ValueError(f"unknown family {fam}")
        return p

    # -- embedding / head ----------------------------------------------------

    def embed_tokens(self, p: Params, tokens):
        cfg = self.cfg
        if cfg.n_codebooks:
            # tokens (B, S, n_codebooks) -> sum of per-codebook embeddings
            tabs = p["embed"]["table"]                    # (C, V, d)
            outs = [jnp.take(tabs[c], tokens[..., c], axis=0)
                    for c in range(cfg.n_codebooks)]
            return sum(outs)
        return embed(p["embed"], tokens)

    def head_matrix(self, p: Params):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return p["embed"]["table"]
        return p["lm_head"]

    def logits(self, p: Params, hidden):
        """Full logits — small-vocab path / tests only (O(T V) memory)."""
        w = self.head_matrix(p)
        if self.cfg.n_codebooks:
            return jnp.einsum("...d,cvd->...cv", hidden, w)
        return hidden @ w.T

    # -- full-sequence forward (train / prefill) -----------------------------

    @jax.named_scope("trunk")
    def forward(self, p: Params, tokens, *, img=None) -> Tuple[Any, Dict]:
        """tokens (B, S[, C]) -> (hidden (B, S, d), aux)."""
        cfg = self.cfg
        x = self.embed_tokens(p, tokens)
        remat = cfg.remat != "none"

        def ck(f):
            return jax.checkpoint(f) if remat else f

        aux = ZERO_AUX
        fam = cfg.family
        if fam in ("dense", "audio") and not cfg.local_global_ratio:
            body = ck(lambda px, x_: tblock_fwd(
                px, x_, cfg, window=cfg.sliding_window))

            def f(carry, px):
                x_, a_ = carry
                x2, a2 = body(px, x_)
                return (x2, _add_aux(a_, a2)), None
            (x, aux), _ = jax.lax.scan(f, (x, aux), p["blocks"])
        elif cfg.local_global_ratio:
            g, r, tail = _gemma_plan(cfg)

            def group_fn(pg):
                loc, glob = pg

                def f(x_):
                    for i in range(r):
                        x_, _ = tblock_fwd(
                            jax.tree.map(lambda t: t[i], loc), x_, cfg,
                            window=cfg.sliding_window)
                    x_, _ = tblock_fwd(glob, x_, cfg, window=0)
                    return x_
                return f

            def f(x_, pg):
                return ck(group_fn(pg))(x_), None
            x, _ = jax.lax.scan(
                f, x, (p["local_groups"], p["global_groups"]))
            if tail:
                def ft(x_, px):
                    return ck(lambda x2: tblock_fwd(
                        px, x2, cfg, window=cfg.sliding_window)[0])(x_), None
                x, _ = jax.lax.scan(ft, x, p["local_tail"])
        elif fam == "vlm":
            g, n_self = _vlm_plan(cfg)

            def f(x_, pg):
                selfs, crossp = pg

                def body_(x2):
                    for i in range(n_self):
                        x2, _ = tblock_fwd(
                            jax.tree.map(lambda t: t[i], selfs), x2, cfg)
                    return cross_block_fwd(crossp, x2, img, cfg)
                return ck(body_)(x_), None
            x, _ = jax.lax.scan(f, x, (p["self_groups"], p["cross_groups"]))
        elif fam == "moe":
            body = ck(lambda px, x_: tblock_fwd(px, x_, cfg, kind="moe"))

            def f(carry, px):
                x_, a_ = carry
                x2, a2 = body(px, x_)
                return (x2, _add_aux(a_, a2)), None
            (x, aux), _ = jax.lax.scan(f, (x, aux), p["blocks"])
        elif fam == "ssm":
            body = ck(lambda px, x_: rwkv_block(px, x_, cfg)[0])

            def f(x_, px):
                return body(px, x_), None
            x, _ = jax.lax.scan(f, x, p["blocks"])
        elif fam == "hybrid":
            g, per, tail = _hybrid_plan(cfg)
            shared = p["shared_attn"]

            def f(x_, pg):
                def body_(x2):
                    for i in range(per):
                        x2, _ = mamba_block(
                            jax.tree.map(lambda t: t[i], pg), x2, cfg)
                    x2, _ = tblock_fwd(shared, x2, cfg)
                    return x2
                return ck(body_)(x_), None
            x, _ = jax.lax.scan(f, x, p["mamba_groups"])
            if tail:
                def ft(x_, px):
                    return ck(lambda x2: mamba_block(px, x2, cfg)[0])(x_), None
                x, _ = jax.lax.scan(ft, x, p["mamba_tail"])
        else:
            raise ValueError(fam)

        return rmsnorm(p["final_norm"], x, cfg.norm_eps), aux

    # -- decode --------------------------------------------------------------

    def init_decode_state(self, batch: int, max_len: int) -> Any:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads

        def kv(n, length):
            shape = (n, batch, length, nkv, hd)
            return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

        fam = cfg.family
        if fam in ("dense", "audio") and not cfg.local_global_ratio:
            length = min(max_len, cfg.sliding_window) if cfg.sliding_window \
                else max_len
            return {"kv": kv(cfg.n_layers, length)}
        if cfg.local_global_ratio:
            g, r, tail = _gemma_plan(cfg)
            w = min(max_len, cfg.sliding_window)
            st = {"local": {"k": jnp.zeros((g, r, batch, w, nkv, hd), dt),
                            "v": jnp.zeros((g, r, batch, w, nkv, hd), dt)},
                  "global": kv(g, max_len)}
            if tail:
                st["tail"] = kv(tail, w)
            return st
        if fam == "vlm":
            g, n_self = _vlm_plan(cfg)
            return {"self": {"k": jnp.zeros((g, n_self, batch, max_len, nkv,
                                             hd), dt),
                             "v": jnp.zeros((g, n_self, batch, max_len, nkv,
                                             hd), dt)}}
        if fam == "moe":
            return {"kv": kv(cfg.n_layers, max_len)}
        if fam == "ssm":
            s0 = RWKVState.init(batch, cfg, dt)
            return {"rwkv": jax.tree.map(
                lambda t: jnp.broadcast_to(
                    t[None], (cfg.n_layers,) + t.shape), s0)}
        if fam == "hybrid":
            g, per, tail = _hybrid_plan(cfg)
            m0 = init_mamba_state(batch, cfg, dt)
            st = {"mamba": jax.tree.map(
                lambda t: jnp.broadcast_to(t[None, None],
                                           (g, per) + t.shape), m0),
                  "shared_kv": kv(g, max_len)}
            if tail:
                st["mamba_tail"] = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None],
                                               (tail,) + t.shape), m0)
            return st
        raise ValueError(fam)

    @jax.named_scope("trunk")
    def decode_step(self, p: Params, state, token, pos, *, img=None):
        """token (B,) or (B, C) -> (hidden_last (B, d), new_state)."""
        cfg = self.cfg
        tok = token[:, None] if not cfg.n_codebooks else token[:, None, :]
        x = self.embed_tokens(p, tok)                      # (B, 1, d)
        fam = cfg.family

        def as_cache(st):
            return KVCache(k=st["k"], v=st["v"])

        if fam in ("dense", "audio", "moe") and not cfg.local_global_ratio:
            kind = "moe" if fam == "moe" else "dense"
            win = cfg.sliding_window

            # the scan only reads the cache and emits the token's rows
            # (L, B, Kv, Dh); a cache carried through it, or stacked as its
            # output, is sliced out and written back whole every step
            def f(x_, xs):
                px, st = xs
                x2, (k, v) = tblock_decode(px, x_, as_cache(st), pos, cfg,
                                           kind=kind, window=win,
                                           attend=decode_attend)
                return x2, (k[:, 0], v[:, 0])
            x, (k_rows, v_rows) = jax.lax.scan(
                f, x, (p["blocks"], state["kv"]))
            slot = ring_slot(pos, win)
            with jax.named_scope("kv_cache"):
                new_state = {"kv": {
                    "k": write_token_rows(state["kv"]["k"], k_rows, slot),
                    "v": write_token_rows(state["kv"]["v"], v_rows, slot)}}
        elif cfg.local_global_ratio:
            g, r, tail = _gemma_plan(cfg)
            w = cfg.sliding_window

            def f(x_, xs):
                loc, glob, lst, gst = xs
                new_l = []
                for i in range(r):
                    x_, c = tblock_decode(
                        jax.tree.map(lambda t: t[i], loc), x_,
                        as_cache(jax.tree.map(lambda t: t[i], lst)), pos,
                        cfg, window=w)
                    new_l.append({"k": c.k, "v": c.v})
                x_, cg = tblock_decode(glob, x_, as_cache(gst), pos, cfg,
                                       window=0)
                stack = jax.tree.map(lambda *ts: jnp.stack(ts), *new_l)
                return x_, (stack, {"k": cg.k, "v": cg.v})
            x, (new_local, new_global) = jax.lax.scan(
                f, x, (p["local_groups"], p["global_groups"],
                       state["local"], state["global"]))
            new_state = {"local": new_local, "global": new_global}
            if tail:
                def ft(x_, xs):
                    px, st = xs
                    x2, c = tblock_decode(px, x_, as_cache(st), pos, cfg,
                                          window=w)
                    return x2, {"k": c.k, "v": c.v}
                x, new_tail = jax.lax.scan(
                    ft, x, (p["local_tail"], state["tail"]))
                new_state["tail"] = new_tail
        elif fam == "vlm":
            g, n_self = _vlm_plan(cfg)

            def f(x_, xs):
                selfs, crossp, st = xs
                new_s = []
                for i in range(n_self):
                    x_, c = tblock_decode(
                        jax.tree.map(lambda t: t[i], selfs), x_,
                        as_cache(jax.tree.map(lambda t: t[i], st)), pos, cfg)
                    new_s.append({"k": c.k, "v": c.v})
                x_ = cross_block_fwd(crossp, x_, img, cfg)
                return x_, jax.tree.map(lambda *ts: jnp.stack(ts), *new_s)
            x, new_self = jax.lax.scan(
                f, x, (p["self_groups"], p["cross_groups"], state["self"]))
            new_state = {"self": new_self}
        elif fam == "ssm":
            def f(x_, xs):
                px, st = xs
                x2, st2 = rwkv_block(px, x_, cfg, st)
                return x2, st2
            x, new_rwkv = jax.lax.scan(f, x, (p["blocks"], state["rwkv"]))
            new_state = {"rwkv": new_rwkv}
        elif fam == "hybrid":
            g, per, tail = _hybrid_plan(cfg)
            shared = p["shared_attn"]

            def f(x_, xs):
                pg, mst, kst = xs
                new_m = []
                for i in range(per):
                    x_, s2 = mamba_block(
                        jax.tree.map(lambda t: t[i], pg), x_, cfg,
                        jax.tree.map(lambda t: t[i], mst))
                    new_m.append(s2)
                x_, c = tblock_decode(shared, x_, as_cache(kst), pos, cfg)
                return x_, (jax.tree.map(lambda *ts: jnp.stack(ts), *new_m),
                            {"k": c.k, "v": c.v})
            x, (new_mamba, new_shared) = jax.lax.scan(
                f, x, (p["mamba_groups"], state["mamba"],
                       state["shared_kv"]))
            new_state = {"mamba": new_mamba, "shared_kv": new_shared}
            if tail:
                def ft(x_, xs):
                    px, st = xs
                    x2, s2 = mamba_block(px, x_, cfg, st)
                    return x2, s2
                x, new_tail = jax.lax.scan(
                    ft, x, (p["mamba_tail"], state["mamba_tail"]))
                new_state["mamba_tail"] = new_tail
        else:
            raise ValueError(fam)

        h = rmsnorm(p["final_norm"], x, cfg.norm_eps)
        return h[:, 0], new_state
