"""Operations and bytes of one pipeline stage of Falcon-H1-34B-Instruct
(six blocks, the whole vocabulary), from shapes (``sizes`` of
``configs/falcon_h1_34b_instruct.json``).

Per token: every product counts 2 operations per parameter it multiplies.
A block: the Mamba-2 mixer's two projections, its depthwise convolution
(2 x d_conv a channel) and its recurrence (2 multiply-adds a state element:
the decayed update and the read through C); the attention mixer's four
projections (heads x head_dim wide, not d_model), and 4 x context x heads x
head_dim for scores and the weighted sum (the rotation, 6 operations a
rotated element, is left out as the norms are); the gated feed-forward's
three products. The head counts; the embedding is a lookup and counts
nothing (the program spends a [vocab x d_model] product on it: that is its
waste, not work the algorithm needs).
"""

from __future__ import annotations


def mamba_matmul_params(sizes: dict) -> int:
    d = sizes["d_model"]
    di = sizes["mamba_heads"] * sizes["mamba_head_dim"]
    width = 2 * di + 2 * sizes["n_groups"] * sizes["d_state"] \
        + sizes["mamba_heads"]
    return d * width + di * d


def attention_matmul_params(sizes: dict) -> int:
    d, hd = sizes["d_model"], sizes["head_dim"]
    return 2 * d * sizes["heads"] * hd + 2 * d * sizes["kv_heads"] * hd


def mlp_params(sizes: dict) -> int:
    """Gate, up and down."""
    return 3 * sizes["d_model"] * sizes["mlp_width"]


def block_matmul_params(sizes: dict) -> int:
    return mamba_matmul_params(sizes) + attention_matmul_params(sizes) \
        + mlp_params(sizes)


def matmul_params_per_token(sizes: dict) -> int:
    """Parameters a token multiplies: the blocks and the output head."""
    return sizes["layers"] * block_matmul_params(sizes) \
        + sizes["d_model"] * sizes["vocab"]


def scan_flops_per_token(sizes: dict) -> float:
    """Convolution and recurrence of every block's Mamba-2 mixer."""
    di = sizes["mamba_heads"] * sizes["mamba_head_dim"]
    cd = di + 2 * sizes["n_groups"] * sizes["d_state"]
    return sizes["layers"] * (2.0 * sizes["d_conv"] * cd
                              + 4.0 * di * sizes["d_state"])


def parameter_count(sizes: dict) -> int:
    """Every parameter held here (norms, biases and scalars included)."""
    d, v = sizes["d_model"], sizes["vocab"]
    H = sizes["mamba_heads"]
    di = H * sizes["mamba_head_dim"]
    cd = di + 2 * sizes["n_groups"] * sizes["d_state"]
    mamba_small = cd * sizes["d_conv"] + cd + 3 * H + di
    return (sizes["layers"] * (block_matmul_params(sizes) + mamba_small
                               + 2 * d)
            + d + (v * d + d) + (d * v + v))


def _attention_flops_per_context(sizes: dict) -> float:
    return 4.0 * sizes["heads"] * sizes["head_dim"] * sizes["layers"]


def token_flops(sizes: dict, context: float) -> float:
    """Operations to produce one token's output with ``context`` earlier
    tokens (itself included) in the attention mixers' cache."""
    return (2.0 * matmul_params_per_token(sizes)
            + scan_flops_per_token(sizes)
            + _attention_flops_per_context(sizes) * context)


def kv_bytes_per_token(sizes: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of every block's attention mixer: kv_heads, not
    heads, of head_dim."""
    return 2 * sizes["layers"] * sizes["kv_heads"] * sizes["head_dim"] \
        * bytes_per_value


def requests_flops(sizes: dict, spans) -> float:
    """Operations for spans of tokens: each span is (first context,
    count): ``count`` consecutive tokens whose contexts run from ``first
    context`` upward by one. A prompt of n tokens is (1, n)."""
    total = 0.0
    lin = 2.0 * matmul_params_per_token(sizes) + scan_flops_per_token(sizes)
    att = _attention_flops_per_context(sizes)
    for first, count in spans:
        if count <= 0:
            continue
        total += count * lin \
            + att * (count * first + count * (count - 1) / 2.0)
    return total


def paged_read(sizes: dict, spans, bytes_per_value: int = 2) -> dict:
    """What the paged read has to do for those spans, over the six
    attention mixers: the operations (4 x context x heads x head_dim a
    token a layer) and the bytes (the live keys and values of the context,
    kv_heads of them, read once per decoded token; a prefilled chunk's
    tokens share one read of their common context, so a prompt span counts
    its final context once per ``chunk`` tokens)."""
    att = _attention_flops_per_context(sizes)
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


def decode_step_min_bytes(sizes: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights that EVERY decode micro-step has to read whatever
    the batch: the six blocks' products and the head (7.84 GB at the
    published widths). A floor: it leaves out the embedding's rows (and the
    whole table that the program's one-hot product reads), the scan state
    read and written, the KV and every activation, and every prefill round,
    so the step's true traffic is larger and a share computed from this can
    never pass 100%."""
    return matmul_params_per_token(sizes) * bytes_per_value
