"""Weights and token batches made from ``--seed``, on the device.

Both the program under test and the plain reference take their inputs
from here, so the reference never reads anything the program made. The
weights come in one canonical tree (every layer leaf carries a leading
layer axis) built by one jitted call; ``to_program`` lays it out the
way the program's parameter tree is shaped.

Initial values follow the usual dense-LM recipe: linear weights
N(0, 1/d_in), embedding and output head N(0, 0.02^2), norm gains 1,
biases 0.
"""
from __future__ import annotations

import functools
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int):
    """``--seed`` (any non-negative integer below 2**62) as two uint32
    words, so seeds above 2**31 reach the PRNG whole."""
    if seed < 0 or seed >= 1 << 62:
        raise ValueError(f"seed {seed} out of range [0, 2**62)")
    return (np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32))


def _key(lo, hi, stream: int):
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(lo), hi), stream)


def canonical_shapes(c: dict) -> dict:
    """Leaf shapes of the canonical tree for config file ``c``."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff, rows = c["head_dim"], c["intermediate_size"], c["logit_rows"]

    def lin(d_in, d_out, bias=False):
        out = {"w": (L, d_in, d_out)}
        if bias:
            out["b"] = (L, d_out)
        return out

    bias = c["attention_bias"]
    tree = {
        "embed": {"w": (rows, d)},
        "final_norm": {"g": (d,)},
        "layers": {
            "norm1": {"g": (L, d)}, "norm2": {"g": (L, d)},
            "attn": {"wq": lin(d, H * hd, bias), "wk": lin(d, Hkv * hd, bias),
                     "wv": lin(d, Hkv * hd, bias), "wo": lin(H * hd, d)},
            "mlp": {"gate": lin(d, ff), "up": lin(d, ff), "down": lin(ff, d)},
        },
    }
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = {"w": (d, rows)}
    return tree


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _init_leaf(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    if last == "g":
        return jnp.ones(shape, dtype)
    if last == "b":
        return jnp.zeros(shape, dtype)
    if name.startswith(("embed", "lm_head")):
        scale = 0.02
    else:
        scale = 1.0 / np.sqrt(shape[-2])
    key = jax.random.fold_in(key, zlib.crc32(name.encode()))
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _weights_fn(config_json: str):
    c = json.loads(config_json)
    shapes = canonical_shapes(c)

    @jax.jit
    def make(lo, hi):
        key = _key(lo, hi, 1)
        return jax.tree_util.tree_map_with_path(
            lambda p, s: _init_leaf(key, _path(p), s, c["param_dtype"]),
            shapes, is_leaf=lambda x: isinstance(x, tuple))
    return make


def canonical_weights(c: dict, seed: int):
    """The canonical weight tree for ``c`` and ``seed``, in the
    configuration's parameter dtype, made in one jitted call."""
    return _weights_fn(json.dumps(c, sort_keys=True))(*seed_words(seed))


@functools.lru_cache(maxsize=None)
def _batches_fn(n: int, batch: int, seq: int, vocab: int):
    @jax.jit
    def make(lo, hi):
        toks = jax.random.randint(_key(lo, hi, 2), (n, batch, seq + 1), 0,
                                  vocab, jnp.int32)
        return tuple({"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
                     for i in range(n))
    return make


def token_batches(seed: int, n: int, batch: int, seq: int, vocab: int):
    """``n`` batches of ``batch`` rows of ``seq`` next-token pairs,
    uniform over the vocabulary; every row differs. One jitted call."""
    return _batches_fn(n, batch, seq, vocab)(*seed_words(seed))


# ------------------------------------------------ the program's layout


def _program_names(prog_tree):
    """``{program leaf path: (canonical path, layer or None)}``; a
    stacked group that holds every layer maps onto the canonical layer
    leaves whole, an unrolled layer list onto one layer's slice."""
    out = {}
    for path, _ in jax.tree_util.tree_flatten_with_path(prog_tree)[0]:
        p = _path(path)
        parts = p.split("/")
        if parts[0] == "groups":
            if len(prog_tree["groups"]) != 1:
                raise ValueError("only a single stacked layer group is "
                                 "mapped")
            out[p] = ("layers/" + "/".join(parts[2:]), None)
        elif parts[0] == "layers":
            out[p] = ("layers/" + "/".join(parts[2:]), int(parts[1]))
        else:
            out[p] = (p, None)
    return out


def leaf_names(prog_tree):
    """Readable names of the program's leaves, in flatten order: the
    names readings and checks use (``layers.3.attn.wq.w`` for an
    unrolled layer, ``layers.attn.wq.w`` for a stacked one)."""
    names = []
    for canon, layer in _program_names(prog_tree).values():
        if layer is not None:
            canon = canon.replace("layers/", f"layers/{layer}/", 1)
        names.append(canon.replace("/", "."))
    return names


def _get(tree, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def to_program(canon, prog_sds):
    """The canonical tree laid out as the program's parameter tree
    ``prog_sds`` (ShapeDtypeStructs). Raises where a leaf's shape or
    dtype differs: the program would not run the stated configuration."""
    names = _program_names(prog_sds)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(prog_sds)
    out = []
    for path, sds in leaves:
        canon_path, layer = names[_path(path)]
        x = _get(canon, canon_path)
        if layer is not None:
            x = x[layer]
        if tuple(x.shape) != tuple(sds.shape) or x.dtype != sds.dtype:
            raise ValueError(
                f"program leaf {_path(path)} is {sds.shape} {sds.dtype}; "
                f"the configuration states {x.shape} {x.dtype}")
        out.append(x)
    return jax.tree.unflatten(treedef, out)
