"""Operations and bytes of one chip's share of granite-4.0-h-small, from
shapes (``sizes`` of ``configs/granite_4.0_h_small.json``).

Per token: every product counts 2 operations per parameter it multiplies.
A Mamba-2 layer: the two projections, the depthwise convolution (2 x
d_conv a channel) and the recurrence (2 multiply-adds a state element: the
decayed update and the read through C). An attention layer: the four
projections, and 4 x context x heads x head_dim for scores and the
weighted sum. An expert layer: the router over ALL experts, the shared
expert, and the routed experts at their EXPECTATION for this share: a
token chooses ``top_k`` of ``experts`` and ``experts_held[1]`` of them
live here, so it runs top_k x held / experts of them (10 x 36 / 72 = 5 at
the published sizes), whatever the routing of one run was. The head
counts; the embedding is a lookup and counts nothing (the program spends
a [vocab x d_model] product on it: that is its waste, not work the
algorithm needs).
"""

from __future__ import annotations


def _kinds(sizes: dict):
    mamba = sum(1 for k in sizes["layer_types"] if k == "mamba")
    return mamba, len(sizes["layer_types"]) - mamba


def mamba_matmul_params(sizes: dict) -> int:
    d = sizes["d_model"]
    di = sizes["mamba_heads"] * sizes["mamba_head_dim"]
    width = 2 * di + 2 * sizes["n_groups"] * sizes["d_state"] \
        + sizes["mamba_heads"]
    return d * width + di * d


def attention_matmul_params(sizes: dict) -> int:
    d, hd = sizes["d_model"], sizes["head_dim"]
    return 2 * d * sizes["heads"] * hd + 2 * d * sizes["kv_heads"] * hd


def expert_params(sizes: dict) -> int:
    """One routed expert: gated in, then out."""
    return 3 * sizes["d_model"] * sizes["expert_width"]


def shared_expert_params(sizes: dict) -> int:
    return 3 * sizes["d_model"] * sizes["shared_width"]


def router_params(sizes: dict) -> int:
    return sizes["d_model"] * sizes["experts"]


def experts_per_token_here(sizes: dict) -> float:
    return sizes["top_k"] * sizes["experts_held"][1] / sizes["experts"]


def matmul_params_per_token(sizes: dict) -> float:
    """Parameters a token multiplies, the routed experts at their
    expectation for this share: layers + output head."""
    mamba, attn = _kinds(sizes)
    layers = mamba + attn
    return (mamba * mamba_matmul_params(sizes)
            + attn * attention_matmul_params(sizes)
            + layers * (router_params(sizes) + shared_expert_params(sizes)
                        + experts_per_token_here(sizes)
                        * expert_params(sizes))
            + sizes["d_model"] * sizes["vocab"])


def scan_flops_per_token(sizes: dict) -> float:
    """Convolution and recurrence of every Mamba-2 layer, one token."""
    mamba, _ = _kinds(sizes)
    di = sizes["mamba_heads"] * sizes["mamba_head_dim"]
    cd = di + 2 * sizes["n_groups"] * sizes["d_state"]
    return mamba * (2.0 * sizes["d_conv"] * cd + 4.0 * di * sizes["d_state"])


def parameter_count(sizes: dict) -> int:
    """Every parameter held here (norms, biases and scalars included)."""
    mamba, attn = _kinds(sizes)
    layers = mamba + attn
    d, v = sizes["d_model"], sizes["vocab"]
    H = sizes["mamba_heads"]
    di = H * sizes["mamba_head_dim"]
    cd = di + 2 * sizes["n_groups"] * sizes["d_state"]
    mamba_small = cd * sizes["d_conv"] + cd + 3 * H + di
    return (mamba * (mamba_matmul_params(sizes) + mamba_small)
            + attn * attention_matmul_params(sizes)
            + layers * (2 * d + router_params(sizes)
                        + shared_expert_params(sizes)
                        + sizes["experts_held"][1] * expert_params(sizes))
            + d + (v * d + d) + (d * v + v))


def token_flops(sizes: dict, context: float) -> float:
    """Operations to produce one token's output with ``context`` earlier
    tokens (itself included) in the attention layers' cache."""
    _, attn = _kinds(sizes)
    return (2.0 * matmul_params_per_token(sizes)
            + scan_flops_per_token(sizes)
            + 4.0 * context * sizes["heads"] * sizes["head_dim"] * attn)


def kv_bytes_per_token(sizes: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of the attention layers: kv_heads, not heads."""
    _, attn = _kinds(sizes)
    return 2 * attn * sizes["kv_heads"] * sizes["head_dim"] * bytes_per_value


def requests_flops(sizes: dict, spans) -> float:
    """Operations for spans of tokens: each span is (first context,
    count): ``count`` consecutive tokens whose contexts run from ``first
    context`` upward by one. A prompt of n tokens is (1, n)."""
    _, attn = _kinds(sizes)
    total = 0.0
    lin = 2.0 * matmul_params_per_token(sizes) + scan_flops_per_token(sizes)
    att = 4.0 * sizes["heads"] * sizes["head_dim"] * attn
    for first, count in spans:
        if count <= 0:
            continue
        ctx_sum = count * first + count * (count - 1) / 2.0
        total += count * lin + att * ctx_sum
    return total


def paged_read(sizes: dict, spans, bytes_per_value: int = 2) -> dict:
    """What the paged read has to do for those spans, over the attention
    layers (one in ten): the operations (4 x context x heads x head_dim a
    token a layer) and the bytes (the live keys and values of the context,
    kv_heads of them, read once per decoded token; a prefilled chunk's
    tokens share one read of their common context, so a prompt span counts
    its final context once per ``chunk`` tokens)."""
    _, attn = _kinds(sizes)
    att = 4.0 * sizes["heads"] * sizes["head_dim"] * attn
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
    """Bytes of weights that EVERY decode micro-step has to read, whatever
    the batch and however the tokens are routed: the mixers, the routers,
    the shared experts and the head. A floor: it leaves out the routed
    experts (which of them a step touches is the routing's), the scan
    state, the KV and every activation, so the step's true traffic is
    larger and a share computed from this can never pass 100%."""
    mamba, attn = _kinds(sizes)
    layers = mamba + attn
    params = (mamba * mamba_matmul_params(sizes)
              + attn * attention_matmul_params(sizes)
              + layers * (router_params(sizes) + shared_expert_params(sizes))
              + sizes["d_model"] * sizes["vocab"])
    return params * bytes_per_value
