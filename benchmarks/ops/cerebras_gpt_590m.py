"""Operations and bytes of the GPT block stack, from shapes.

Per token: the products of the blocks (Wq, Wk, Wv, Wo, and the two
feed-forward kernels) and of the output head count 2 operations per
parameter; the embedding is a lookup and counts nothing (the program
spends a [vocab x d_model] product on it: that is its waste, not work the
algorithm needs). Attention counts 4 x context x d_model a layer (scores
and the weighted sum, 2 each).
"""

from __future__ import annotations


def block_matmul_params(sizes: dict) -> int:
    d, f = sizes["d_model"], sizes["ffn"]
    return sizes["layers"] * (4 * d * d + 2 * d * f)


def matmul_params_per_token(sizes: dict) -> int:
    """Parameters every token multiplies: blocks + output head."""
    return block_matmul_params(sizes) + sizes["d_model"] * sizes["vocab"]


def parameter_count(sizes: dict) -> int:
    d, f, v = sizes["d_model"], sizes["ffn"], sizes["vocab"]
    per_block = 4 * d * d + d + 2 * d * f + f + d + 4 * d   # kernels, biases, 2 LN
    return (v * d + d) + sizes["layers"] * per_block + 2 * d + (d * v + v)


def token_flops(sizes: dict, context: float) -> float:
    """Operations to produce one token's output with ``context`` earlier
    tokens (itself included) in the cache."""
    return (2.0 * matmul_params_per_token(sizes)
            + 4.0 * context * sizes["d_model"] * sizes["layers"])


def kv_bytes_per_token(sizes: dict, bytes_per_value: int = 4) -> int:
    return 2 * sizes["layers"] * sizes["d_model"] * bytes_per_value


def requests_flops(sizes: dict, spans) -> float:
    """Operations for spans of tokens: each span is (first context,
    count): ``count`` consecutive tokens whose contexts run from ``first
    context`` upward by one. A prompt of n tokens is (1, n)."""
    total = 0.0
    lin = 2.0 * matmul_params_per_token(sizes)
    att = 4.0 * sizes["d_model"] * sizes["layers"]
    for first, count in spans:
        if count <= 0:
            continue
        ctx_sum = count * first + count * (count - 1) / 2.0
        total += count * lin + att * ctx_sum
    return total


def paged_read(sizes: dict, spans, bytes_per_value: int = 4) -> dict:
    """What the paged read has to do for those spans, all layers: the
    operations (4 x context x d_model a token a layer) and the bytes (the
    live keys and values of the context, read once per decoded token; a
    prefilled chunk's tokens share one read of their common context, so a
    prompt span counts its final context once per ``chunk`` tokens)."""
    att = 4.0 * sizes["d_model"] * sizes["layers"]
    kv = kv_bytes_per_token(sizes, bytes_per_value)
    ops = 0.0
    nbytes = 0.0
    for first, count, chunk in spans:
        if count <= 0:
            continue
        ops += att * (count * first + count * (count - 1) / 2.0)
        if chunk <= 1:
            nbytes += kv * (count * first + count * (count - 1) / 2.0)
        else:
            done = 0
            while done < count:
                n = min(chunk, count - done)
                nbytes += kv * (first + done + n - 1)
                done += n
    return {"ops": ops, "bytes": nbytes}
