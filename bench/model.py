"""A configuration file, read two ways: as the program's ``ModelConfig``
(the system under test) and as the plain shapes the reference and the
FLOP counts use; and the weights, made by the benchmark from the seed.

The weights are made here, not by the program, so that the reference can
use the same numbers without taking anything the program made. They are
made on the device, in one jitted call, in the dtype they are served in,
and laid out as the program's parameter tree (layers stacked on a leading
axis), which the reference reads layer by layer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Arch:
    """The shapes of a dense decoder, in the configuration file's terms."""
    name: str
    d: int
    d_ff: int
    n_layers: int
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    act: str
    dtype: str

    @classmethod
    def from_file(cls, c: Dict) -> "Arch":
        if c.get("tie_word_embeddings"):
            raise ValueError("tied embeddings are not defined here")
        if c["hidden_act"] != "silu":
            raise ValueError(f"unknown activation {c['hidden_act']!r}")
        return cls(name=c["name"], d=c["hidden_size"],
                   d_ff=c["intermediate_size"],
                   n_layers=c["num_hidden_layers"],
                   n_heads=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"],
                   head_dim=c.get("head_dim")
                   or c["hidden_size"] // c["num_attention_heads"],
                   vocab=c["vocab_size"], qkv_bias=bool(c["attention_bias"]),
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]), act=c["hidden_act"],
                   dtype=c["torch_dtype"])

    def trunk_params(self) -> int:
        """Matrix parameters of the layers (no embedding, no head)."""
        attn = self.d * self.head_dim * (self.n_heads + 2 * self.n_kv) \
            + self.n_heads * self.head_dim * self.d
        return self.n_layers * (attn + 3 * self.d * self.d_ff)


def program_config(c: Dict, arch: Arch):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, PartitionConfig
    s = c["serving"]
    part = PartitionConfig(method=s["method"], k=s["k"], l=s["l"],
                           n_probe=s["n_probe"], block_rows=s["block_rows"],
                           n_clusters=s["n_clusters"],
                           sample_k=s["sample_k"])
    return ModelConfig(
        name=arch.name, family="dense", n_layers=arch.n_layers,
        d_model=arch.d, n_heads=arch.n_heads, n_kv_heads=arch.n_kv,
        head_dim=arch.head_dim, d_ff=arch.d_ff, vocab=arch.vocab,
        max_seq_len=c["max_position_embeddings"], act=arch.act,
        qkv_bias=arch.qkv_bias, rope_theta=arch.rope_theta,
        norm_eps=arch.norm_eps, tie_embeddings=False, partition=part,
        dtype=arch.dtype)


def param_specs(a: Arch) -> Dict[str, Any]:
    """(shape, std, mean) of every leaf, in the program's tree layout."""
    L, d, f = a.n_layers, a.d, a.d_ff
    hq, hkv = a.n_heads * a.head_dim, a.n_kv * a.head_dim
    attn = {"wq": ((L, d, hq), d ** -0.5, 0.0),
            "wk": ((L, d, hkv), d ** -0.5, 0.0),
            "wv": ((L, d, hkv), d ** -0.5, 0.0),
            "wo": ((L, hq, d), hq ** -0.5, 0.0)}
    if a.qkv_bias:
        attn.update(bq=((L, hq), 0.5, 0.0), bk=((L, hkv), 0.5, 0.0),
                    bv=((L, hkv), 0.5, 0.0))
    return {
        "embed": {"table": ((a.vocab, d), 1.0, 0.0)},
        "final_norm": {"scale": ((d,), 0.1, 1.0)},
        "lm_head": ((a.vocab, d), d ** -0.5, 0.0),
        "blocks": {
            "ln1": {"scale": ((L, d), 0.1, 1.0)},
            "attn": attn,
            "ln2": {"scale": ((L, d), 0.1, 1.0)},
            "ffn": {"gate": ((L, d, f), d ** -0.5, 0.0),
                    "up": ((L, d, f), d ** -0.5, 0.0),
                    "down": ((L, f, d), f ** -0.5, 0.0)},
        },
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_params(a: Arch, key: jax.Array):
    """All weights from ``key`` in one jitted call, in ``a.dtype``."""
    specs = param_specs(a)
    leaves, tree = jax.tree.flatten(specs, is_leaf=_is_spec)
    dt = jnp.dtype(a.dtype)

    def build(k):
        ks = jax.random.split(k, len(leaves))
        out = [(jax.random.normal(ki, shape, dt) * jnp.asarray(std, dt)
                + jnp.asarray(mean, dt)) for ki, (shape, std, mean)
               in zip(ks, leaves)]
        return jax.tree.unflatten(tree, out)

    return jax.jit(build)(key)
