"""Random weights of a dense decoder, drawn from the run's seed.

Jitted calls make the tree on the device, in the dtype it is served in:
one call for the embedding, the final norm and the LM head, and one call
per layer (the same compiled program for every layer, the layer's index an
argument).  A whole model in bfloat16 does not fit beside its packed copy
on one chip, so the program packs, and the reference runs, one layer at a
time.  The reference calls the same functions, so the program and the
reference start from the same numbers while neither takes anything the
other made.

Each leaf has its own stream (``fold_in`` of the leaf's index), each layer
of a per-layer leaf its own sub-stream.  Matrices are N(0, 1/fan_in), the
embedding N(0, 1), norm gains 1 + N(0, 0.1^2), so a norm applied to the
wrong tensor shows in the logits.  Rows of the served table past the
published vocabulary are zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def leaves(conf: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], str]]:
    """(path, shape of one layer's leaf, kind) of every leaf, in the
    order that numbers their random streams."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hd = conf["head_dim"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    v = conf["serving"]["vocab_rows"]
    return [
        (("embed",), (v, d), "embed"),
        (("layers", "attn", "wq"), (d, q), "matrix"),
        (("layers", "attn", "wk"), (d, kv), "matrix"),
        (("layers", "attn", "wv"), (d, kv), "matrix"),
        (("layers", "attn", "wo"), (q, d), "matrix"),
        (("layers", "ln1"), (d,), "gain"),
        (("layers", "ln2"), (d,), "gain"),
        (("layers", "mlp", "w_gate"), (d, f), "matrix"),
        (("layers", "mlp", "w_up"), (d, f), "matrix"),
        (("layers", "mlp", "w_down"), (f, d), "matrix"),
        (("final_norm",), (d,), "gain"),
        (("lm_head",), (d, v), "head"),
    ]


def _draw(key, shape, kind: str, vocab: int):
    if kind == "gain":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        return jnp.where(jnp.arange(shape[0])[:, None] < vocab, x, 0.0)
    x = x / np.sqrt(np.float32(shape[0]))
    if kind == "head":
        return jnp.where(jnp.arange(shape[1])[None, :] < vocab, x, 0.0)
    return x


_SHAPE_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
               "num_key_value_heads", "head_dim", "vocab_size")


def _put(tree: dict, path: tuple[str, ...], val) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = val


@functools.lru_cache(maxsize=None)
def _makers(shape_items: tuple, dtype: str, vocab_rows: int):
    conf = dict(shape_items)
    conf["serving"] = {"vocab_rows": vocab_rows}
    vocab = conf["vocab_size"]

    def base(seed_lo, seed_hi):
        return jax.random.fold_in(jax.random.key(seed_lo), seed_hi)

    def top(seed_lo, seed_hi):
        tree: dict = {}
        for index, (path, shape, kind) in enumerate(leaves(conf)):
            if path[0] != "layers":
                key = jax.random.fold_in(base(seed_lo, seed_hi), index)
                _put(tree, path, _draw(key, shape, kind, vocab).astype(dtype))
        return tree

    def layer(seed_lo, seed_hi, i):
        tree: dict = {}
        for index, (path, shape, kind) in enumerate(leaves(conf)):
            if path[0] == "layers":
                key = jax.random.fold_in(
                    jax.random.fold_in(base(seed_lo, seed_hi), index), i)
                _put(tree, path[1:],
                     _draw(key, shape, kind, vocab).astype(dtype))
        return tree

    return jax.jit(top), jax.jit(layer)


def _fns(conf: dict):
    serving = conf["serving"]
    return _makers(tuple((k, conf[k]) for k in _SHAPE_KEYS), serving["dtype"],
                   serving["vocab_rows"])


def _words(seed: int):
    """``seed`` may exceed 32 bits: its two words seed the stream."""
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def top(conf: dict, seed: int) -> dict:
    """``{"embed", "final_norm", "lm_head"}`` on the default device."""
    return _fns(conf)[0](*_words(seed))


def layer(conf: dict, seed: int, i: int) -> dict:
    """Layer ``i``: ``{"attn": {wq, wk, wv, wo}, "ln1", "ln2", "mlp":
    {w_gate, w_up, w_down}}`` on the default device."""
    return _fns(conf)[1](*_words(seed), np.int32(i))


def stacked_shapes(conf: dict) -> dict:
    """Shapes and dtypes of the whole tree in the stacked layout (layers
    along a leading axis), as the program's layer registry reads it."""
    n, dtype = conf["num_hidden_layers"], jnp.dtype(conf["serving"]["dtype"])
    tree: dict = {}
    for path, shape, _ in leaves(conf):
        full = (n, *shape) if path[0] == "layers" else shape
        _put(tree, path, jax.ShapeDtypeStruct(full, dtype))
    return tree
