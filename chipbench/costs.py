"""Operations and bytes the serving path needs, computed from shapes.

Every roofline share the benchmark reports divides one of these counts by
a device time taken from the profiler trace.  They count what the
algorithm must do for a call, at the call's own shapes: the packed weight
bytes as stored (sub-byte levels packed into int8 containers, one f32
scale per output channel), activations in and out at the served dtype,
and the quantized KV cache at the live lengths only.  Nothing here comes
from the program under test.
"""
from __future__ import annotations

import dataclasses
import math

#: values per int8 container byte for each weight/KV bitwidth (6 and 8 bit
#: levels take a whole byte)
LANES = {2: 4, 4: 2, 6: 1, 8: 1}


@dataclasses.dataclass(frozen=True)
class Dims:
    """A dense decoder's serving shapes, as a configuration file gives them."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_rows: int          # rows of the embedding / LM-head table as served
    layer_bits: tuple        # per layer, the bitwidth of its four matrices
    head_bits: int           # LM head
    embed_bits: int
    kv_bits: tuple = (4, 4)  # (K, V)
    kv_block: int = 16
    act_bytes: int = 2       # bf16 activations

    @classmethod
    def from_config(cls, conf: dict) -> "Dims":
        s = conf["serving"]
        return cls(n_layers=conf["num_hidden_layers"],
                   d_model=conf["hidden_size"],
                   n_heads=conf["num_attention_heads"],
                   n_kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["head_dim"],
                   d_ff=conf["intermediate_size"],
                   vocab_rows=s["vocab_rows"],
                   layer_bits=tuple(s["weight_bits"]["layers"]),
                   head_bits=s["weight_bits"]["lm_head"],
                   embed_bits=s["weight_bits"]["embed"],
                   kv_bits=tuple(s["kv_bits"]),
                   kv_block=s["kv_block"])

    def layer_matrices(self) -> list[tuple[int, int]]:
        """(K, N) of each packed matrix of one layer as served: fused
        Q/K/V, output projection, fused gate/up, down projection."""
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        d, f = self.d_model, self.d_ff
        return [(d, q + 2 * kv), (q, d), (d, 2 * f), (f, d)]

    def params_per_token(self) -> int:
        """Weights one token multiplies through: every layer and the head."""
        per_layer = sum(k * n for k, n in self.layer_matrices())
        return self.n_layers * per_layer + self.d_model * self.vocab_rows


def packed_bytes(k: int, n: int, bits: int) -> int:
    """HBM bytes of one packed (K, N) matrix: levels plus f32 scales."""
    return n * math.ceil(k / LANES[bits]) + 4 * n


def weight_bytes(dims: Dims) -> int:
    """Every packed matrix a decode step reads: each layer's four and the
    LM head (embedding rows are gathered, not read whole)."""
    return sum(packed_bytes(k, n, bits) for bits in dims.layer_bits
               for k, n in dims.layer_matrices()) + packed_bytes(
        dims.d_model, dims.vocab_rows, dims.head_bits)


def kv_attend_cost(dims: Dims, live: list[int]):
    """(flops, bytes) of one layer's fused decode step over the quantized
    cache: append one row per slot and attend over ``live`` positions
    (each slot's length after the append).  Bytes are the packed K and V
    at the live lengths with their block scales, one requantized block
    written back per side, and the query in and output out."""
    kb, vb = dims.kv_bits
    h, hd, blk = dims.n_kv_heads, dims.head_dim, dims.kv_block
    flops = sum(4 * n * dims.n_heads * hd for n in live)
    read = sum(n * h * hd * (kb + vb) / 8 + 2 * 4 * h * math.ceil(n / blk)
               for n in live)
    write = len(live) * (h * blk * hd * (kb + vb) / 8 + 2 * 4 * h)
    qo = len(live) * 2 * dims.n_heads * hd * dims.act_bytes
    return flops, read + write + qo


def decode_step_cost(dims: Dims, live: list[int]):
    """(model flops, required bytes) of one decode step that advances the
    active slots whose lengths after the step are ``live``: every packed
    weight read once, each active token multiplied through all of them,
    and the KV work of every layer."""
    b = len(live)
    flops = 2 * b * dims.params_per_token()
    nbytes = weight_bytes(dims) + b * (
        math.ceil(dims.d_model / LANES[dims.embed_bits]) + 4)
    f_kv, b_kv = kv_attend_cost(dims, live)
    return flops + dims.n_layers * f_kv, nbytes + dims.n_layers * b_kv


def prefill_flops(dims: Dims, tokens: int) -> int:
    """Model FLOPs of one prompt prefill of ``tokens`` valid positions.

    The prefill fills the KV cache and nothing else: the first token's
    logits come from the decode step that replays the prompt's last token.
    So it needs every layer's Q/K/V projection, and the attention, output
    projection and MLP of every layer but the last, whose outputs nothing
    reads (the compiled prefill leaves them out too)."""
    (kq, nq), (ko, no), (kg, ng), (kd, nd) = dims.layer_matrices()
    attn = 2 * 2 * dims.n_heads * dims.head_dim * tokens * (tokens + 1) // 2
    rest = 2 * tokens * (ko * no + kg * ng + kd * nd) + attn
    return dims.n_layers * 2 * tokens * kq * nq + (dims.n_layers - 1) * rest
