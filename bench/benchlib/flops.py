"""Operations and bytes of the served work, computed from shapes.

Useful work only: no padded rows, heads or keys, and no recomputation.
A multiply-add counts as two operations.  Causal attention counts each
query against the keys it may see: its chunk's history, the chunk's
earlier tokens and itself.  Weights, activations and KV are bf16 (2
bytes), log-sum-exp rows float32.

``m`` is a configuration's ``model`` dict (published key names, as the
reference reads them).
"""

from __future__ import annotations

from typing import Iterable

BF16 = 2
F32 = 4


def dims(m: dict) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    return {"L": m["num_hidden_layers"], "d": d, "H": H,
            "KVH": m["num_key_value_heads"],
            "Dh": m.get("head_dim") or d // H,
            "F": m["intermediate_size"], "V": m["vocab_size"]}


def linear_flops_per_token(m: dict) -> float:
    """Projections and MLP of every layer, per token (no unembedding)."""
    g = dims(m)
    d, H, KVH, Dh, F = g["d"], g["H"], g["KVH"], g["Dh"], g["F"]
    per_layer = d * (H + 2 * KVH) * Dh + H * Dh * d + 3 * d * F
    return 2.0 * g["L"] * per_layer


def unembed_flops(m: dict, rows: int) -> float:
    g = dims(m)
    return 2.0 * g["d"] * g["V"] * rows


def attention_flops(m: dict, keys: float) -> float:
    """QK^T and PV over ``keys`` (query, key) pairs, all layers."""
    g = dims(m)
    return 4.0 * g["L"] * g["H"] * g["Dh"] * keys


def causal_pairs(n: int, hist: int) -> float:
    """Visible (query, key) pairs of an ``n``-token chunk after ``hist``
    tokens of history."""
    return n * hist + n * (n + 1) / 2.0


def chunk_flops(m: dict, n: int, hist: int) -> float:
    """One prefill chunk: ``n`` tokens after ``hist``; the program takes
    logits at the chunk's last position only."""
    return (linear_flops_per_token(m) * n
            + attention_flops(m, causal_pairs(n, hist))
            + unembed_flops(m, 1))


def tick_flops(m: dict, ctx_lens: Iterable[int]) -> float:
    """One decode tick: one new token per live row, each attending over
    its ``ctx`` cached tokens and itself."""
    ctx_lens = list(ctx_lens)
    rows = len(ctx_lens)
    keys = sum(c + 1 for c in ctx_lens)
    return (linear_flops_per_token(m) * rows + attention_flops(m, keys)
            + unembed_flops(m, rows))


# ---------------------------------------------------- kernels, per layer
def flash_attention_cost(m: dict, n: int) -> tuple:
    """Causal self-attention of an ``n``-token chunk over its own KV:
    (operations, bytes) of one layer's call."""
    g = dims(m)
    H, KVH, Dh = g["H"], g["KVH"], g["Dh"]
    flops = 4.0 * H * Dh * n * (n + 1) / 2.0
    nbytes = (2 * n * H * Dh * BF16          # q in, o out
              + 2 * n * KVH * Dh * BF16      # k, v in
              + n * H * F32)                 # lse out
    return flops, float(nbytes)


def paged_flash_prefill_cost(m: dict, n: int, hist: int) -> tuple:
    """An ``n``-token chunk's queries over ``hist`` tokens of paged
    history: (operations, bytes) of one layer's call."""
    g = dims(m)
    H, KVH, Dh = g["H"], g["KVH"], g["Dh"]
    flops = 4.0 * H * Dh * n * hist
    nbytes = (2 * n * H * Dh * BF16 + 2 * hist * KVH * Dh * BF16
              + n * H * F32)
    return flops, float(nbytes)


def paged_flash_decode_cost(m: dict, ctx_lens: Iterable[int]) -> tuple:
    """A decode tick's fused append-and-attend over the live pages:
    (operations, bytes) of one layer's call.  Bytes are the live tokens'
    K and V read, the new token's K and V written, q read and o written."""
    g = dims(m)
    H, KVH, Dh = g["H"], g["KVH"], g["Dh"]
    ctx_lens = list(ctx_lens)
    rows = len(ctx_lens)
    keys = sum(c + 1 for c in ctx_lens)
    flops = 4.0 * H * Dh * keys
    nbytes = (2 * keys * KVH * Dh * BF16 + 2 * rows * KVH * Dh * BF16
              + 2 * rows * H * Dh * BF16)
    return flops, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bw"])
