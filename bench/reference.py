"""The plain reference: a dense decoder in float32 ``jax.numpy``, written
from the published description (Qwen2 / Mistral: pre-RMSNorm blocks,
rotary positions on half-split pairs, grouped-query attention, SiLU-gated
MLP, untied head). It imports nothing of the program.

It runs teacher-forced over whole sequences, one layer at a time: each
layer's bf16 weights are upcast inside that layer's call, so a float32 copy
of the model never exists. Every matrix product runs at
``Precision.HIGHEST``.

``mode="int8"`` is the control: the same reference with every linear layer
(q, k, v, o, gate, up, down and the head) computed in int8 -- weights
quantized per output channel, activations per token, int32 accumulation --
the step below the configuration's bf16 that a later change could be
tempted by. A comparison that cannot tell the control from the reference
cannot tell a lower-precision program from a sound one.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
MODES = ("f32", "int8")


def _q8(x, axis):
    """Symmetric int8 quantization along ``axis`` (the reduced one)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def linear(x, w, mode: str):
    """x (..., n) @ w (n, m) in float32, or in int8 for the control."""
    w = w.astype(jnp.float32)
    if mode == "f32":
        return jnp.matmul(x, w, precision=HI)
    xq, sx = _q8(x, -1)
    wq, sw = _q8(w, 0)
    y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * sx * sw


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (B, L, H, hd), rotate_half convention: pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, :, None].astype(jnp.float32) * inv          # (B, L, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("arch", "mode"))
def layer(x, blocks, i, *, arch, mode: str):
    """One decoder layer over x (B, L, d) float32; ``blocks`` is the whole
    stacked tree and ``i`` picks the layer."""
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(jnp.float32), blocks)
    b, n, _ = x.shape
    nh, nkv, hd = arch.n_heads, arch.n_kv, arch.head_dim
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    h = rmsnorm(x, p["ln1"]["scale"], arch.norm_eps)
    a = p["attn"]
    q, k, v = (linear(h, a["wq"], mode), linear(h, a["wk"], mode),
               linear(h, a["wv"], mode))
    if arch.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(b, n, nh, hd), pos, arch.rope_theta)
    k = rope(k.reshape(b, n, nkv, hd), pos, arch.rope_theta)
    v = v.reshape(b, n, nkv, hd)
    rep = nh // nkv                      # query head j reads kv head j // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(b, n, nh * hd)
    x = x + linear(o, a["wo"], mode)
    h = rmsnorm(x, p["ln2"]["scale"], arch.norm_eps)
    f = p["ffn"]
    g = jax.nn.silu(linear(h, f["gate"], mode)) * linear(h, f["up"], mode)
    return x + linear(g, f["down"], mode)


class Scores(NamedTuple):
    """Per served position, in sequence-then-position order."""
    tok: np.ndarray      # logit of the target token
    lse: np.ndarray      # exact log Z over the whole vocabulary
    best: np.ndarray     # the best logit
    first: np.ndarray    # the token with the best logit
    above: np.ndarray    # rows scored strictly above the target (0: best)


@partial(jax.jit, static_argnames=("mode",))
def _head_rows(hidden, head, tokens, *, mode: str):
    """For rows (R, d) and ``tokens`` (R,): the ``Scores`` fields."""
    logits = linear(hidden, head.T, mode)                      # (R, V)
    tok = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
    return (tok, jax.nn.logsumexp(logits, axis=-1), jnp.max(logits, -1),
            jnp.argmax(logits, -1), jnp.sum(logits > tok[:, None], -1))


def score(params: Dict, arch, seqs: List[Tuple[np.ndarray, int]],
          length: int, mode: str = "f32", rows_per_call: int = 256,
          targets: Optional[np.ndarray] = None) -> Scores:
    """Teacher-force every sequence and read the served positions.

    ``seqs`` holds (tokens, n_prompt) pairs: tokens = prompt + served
    answer. The answer's token j was emitted from position n_prompt-1+j.
    Sequences are padded to ``length`` so that one compiled layer serves
    every run of the cell. The target at each position is the served
    token, or ``targets`` (one per served position, in the same order)
    where given.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}")
    b = len(seqs)
    toks = np.zeros((b, length), np.int32)
    rows, served = [], []
    for i, (t, n_prompt) in enumerate(seqs):
        if len(t) - 1 > length:
            raise ValueError("sequence longer than the compiled length")
        toks[i, :len(t) - 1] = t[:-1]
        for j in range(len(t) - n_prompt):
            rows.append(i * length + n_prompt - 1 + j)
            served.append(t[n_prompt + j])
    x = jnp.take(params["embed"]["table"], jnp.asarray(toks),
                 axis=0).astype(jnp.float32)
    for i in range(arch.n_layers):
        x = layer(x, params["blocks"], jnp.int32(i), arch=arch, mode=mode)
    x = rmsnorm(x, params["final_norm"]["scale"], arch.norm_eps)
    x = x.reshape(b * length, -1)
    rows = np.asarray(rows, np.int32)
    if targets is None:
        targets = served
    targets = np.asarray(targets, np.int32)
    if targets.shape != rows.shape:
        raise ValueError("one target per served position")
    out = [[] for _ in Scores._fields]
    for s in range(0, len(rows), rows_per_call):
        r = rows[s:s + rows_per_call]
        t = targets[s:s + rows_per_call]
        pad = rows_per_call - len(r)            # one compiled shape
        r = np.pad(r, (0, pad))
        t = np.pad(t, (0, pad))
        res = _head_rows(jnp.take(x, jnp.asarray(r), axis=0),
                         params["lm_head"], jnp.asarray(t), mode=mode)
        for acc, v in zip(out, res):
            acc.append(np.asarray(v)[:len(r) - pad])
    return Scores(*(np.concatenate(a) for a in out))
