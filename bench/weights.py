"""Random weights from the seed, made on the device in one jitted call.

The dense GQA decoder's weights (``bench/reference/dense_gqa.py`` hands
them to the harness).  The benchmark makes the weights, not the program,
so that the plain reference can make the same ones again without taking
anything the program made.  The pytree is the layout the serving engine reads
(``embed.tok``, ``embed.unembed``, a layer stack scanned on axis 0 with
``ln1``/``ln2``, ``attn.{wq,wk,wv,wo}``, ``ffn.{w_gate,w_up,w_down}``,
and ``final_norm``).  Matrices are normal with std ``1/sqrt(fan_in)`` and
norm weights are 1, in float32.  Layers and the vocabulary matrices are
made block by block inside the one program, so its peak stays near the
size of the weights.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

VOCAB_BLOCKS = 16


def sizes(cfg: Dict) -> Dict[str, int]:
    """The shape numbers of a configuration file, by the names used here."""
    return {"L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]),
            "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"])}


def seed_key(seed: int) -> np.ndarray:
    """Raw threefry key data for any seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} out of range [0, 2**64)")
    return np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * np.float32(
        1.0 / np.sqrt(fan_in))


def _vocab_matrix(key, rows, cols, fan_in, axis):
    """(rows, cols) made in VOCAB_BLOCKS pieces along ``axis``, the
    vocabulary's, each written in place into the one output buffer."""
    shape = [rows, cols]
    blk = -(-shape[axis] // VOCAB_BLOCKS)
    padded = list(shape)
    padded[axis] = blk * VOCAB_BLOCKS
    piece = list(shape)
    piece[axis] = blk
    keys = jax.random.split(key, VOCAB_BLOCKS)

    def body(i, out):
        start = [0, 0]
        start[axis] = i * blk
        return lax.dynamic_update_slice(
            out, _normal(keys[i], tuple(piece), fan_in), tuple(start))

    out = lax.fori_loop(0, VOCAB_BLOCKS, body,
                        jnp.zeros(tuple(padded), jnp.float32))
    return out if padded == shape else lax.slice(out, (0, 0), tuple(shape))


def _layer(key, s):
    ks = jax.random.split(key, 7)
    d, H, KV, hd, F = s["d"], s["H"], s["KV"], s["hd"], s["F"]
    return {"ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "attn": {"wq": _normal(ks[0], (d, H * hd), d),
                     "wk": _normal(ks[1], (d, KV * hd), d),
                     "wv": _normal(ks[2], (d, KV * hd), d),
                     "wo": _normal(ks[3], (H * hd, d), H * hd)},
            "ffn": {"w_gate": _normal(ks[4], (d, F), d),
                    "w_up": _normal(ks[5], (d, F), d),
                    "w_down": _normal(ks[6], (F, d), F)}}


@functools.lru_cache(maxsize=None)
def _weight_program(L, d, H, KV, hd, F, V):
    s = dict(L=L, d=d, H=H, KV=KV, hd=hd, F=F, V=V)

    def build(raw):
        key = jax.random.wrap_key_data(raw, impl="threefry2x32")
        k_tok, k_un, k_layers = jax.random.split(key, 3)
        layers = lax.map(lambda k: _layer(k, s),
                         jax.random.split(k_layers, L))
        return {"embed": {"tok": _vocab_matrix(k_tok, V, d, V, 0),
                          "unembed": _vocab_matrix(k_un, d, V, d, 1)},
                "layers": layers,
                "final_norm": jnp.ones((d,), jnp.float32)}
    return jax.jit(build)


def make_weights(cfg: Dict, seed: int):
    """The weights of configuration ``cfg`` for ``seed``, on the device."""
    s = sizes(cfg)
    return _weight_program(s["L"], s["d"], s["H"], s["KV"], s["hd"],
                           s["F"], s["V"])(jnp.asarray(seed_key(seed)))
