"""Operations and bytes of one chip's share of DeepSeek-V2, from shapes
(``sizes`` of ``configs/deepseek_v2.json``).

Per token: every product counts 2 operations per parameter it multiplies.
A latent-attention layer: the four projections (W_DQ, W_UQ, W_DKV, W_O) and
the two per-head factors W_UK and W_UV once a token (the absorbed query and
the un-absorbed output; the plain form's rebuilt key and value of the new
token cost the same), and the ABSORBED read of the cache: 2 x heads x
((kv_rank + rope_dim) + kv_rank) operations a cached position a layer
(scores against the whole row, values from its first kv_rank columns). The
plain form, which rebuilds 2 x kv_rank x heads x (nope_dim + v_dim)
operations of keys and values for every cached position, is not what the
algorithm needs once the cache holds latents. Block 0: the dense gated
feed-forward. Later blocks: the router over ALL experts, the shared
expert, and the routed experts at their EXPECTATION for this share: a
token chooses ``top_k`` of ``experts`` and ``experts_held[1]`` of them live
here, so it runs top_k x held / experts of them (6 x 40 / 160 = 1.5 at the
published sizes), whatever the routing of one run was. The head counts;
the embedding is a lookup and counts nothing (the program spends a [vocab
x d_model] product on it: that is its waste, not work the algorithm
needs).

``cached_prefix_tokens``: the configuration's one cell shares a document
of that many tokens, which a request admitted inside the window takes
from cached pages: a prompt longer than it is counted as its own part
alone, prefilled at the contexts that follow the document. A prompt that
found no cached page (the cold wave before the window, a cache that
failed) did more than is counted: the share of the peak reads low then,
never high.
"""

from __future__ import annotations


def attention_matmul_params(sizes: dict) -> int:
    d, H = sizes["d_model"], sizes["heads"]
    n, r, v = sizes["nope_dim"], sizes["rope_dim"], sizes["v_dim"]
    rq, c = sizes["q_rank"], sizes["kv_rank"]
    return (d * rq + rq * H * (n + r) + d * (c + r) + c * H * (n + v)
            + H * v * d)


def mlp_params(sizes: dict) -> int:
    return 3 * sizes["d_model"] * sizes["mlp_width"]


def expert_params(sizes: dict) -> int:
    """One routed expert: gated in, then out."""
    return 3 * sizes["d_model"] * sizes["expert_width"]


def shared_expert_params(sizes: dict) -> int:
    return 3 * sizes["d_model"] * sizes["shared_width"]


def router_params(sizes: dict) -> int:
    return sizes["d_model"] * sizes["experts"]


def experts_per_token_here(sizes: dict) -> float:
    return sizes["top_k"] * sizes["experts_held"][1] / sizes["experts"]


def _layers(sizes: dict):
    """(dense blocks, expert blocks)."""
    return sizes["dense_layers"], sizes["layers"] - sizes["dense_layers"]


def matmul_params_per_token(sizes: dict) -> float:
    """Parameters a token multiplies, the routed experts at their
    expectation for this share: blocks + output head."""
    dense, moe = _layers(sizes)
    return (sizes["layers"] * attention_matmul_params(sizes)
            + dense * mlp_params(sizes)
            + moe * (router_params(sizes) + shared_expert_params(sizes)
                     + experts_per_token_here(sizes) * expert_params(sizes))
            + sizes["d_model"] * sizes["vocab"])


def read_ops_per_position(sizes: dict) -> int:
    """The absorbed read of ONE cached position, all layers."""
    c, r = sizes["kv_rank"], sizes["rope_dim"]
    return 2 * sizes["heads"] * ((c + r) + c) * sizes["layers"]


def parameter_count(sizes: dict) -> int:
    """Every parameter held here (norms and zero biases included)."""
    dense, moe = _layers(sizes)
    d, v = sizes["d_model"], sizes["vocab"]
    return (sizes["layers"] * (attention_matmul_params(sizes)
                               + sizes["q_rank"] + sizes["kv_rank"] + 2 * d)
            + dense * mlp_params(sizes)
            + moe * (router_params(sizes) + shared_expert_params(sizes)
                     + sizes["experts_held"][1] * expert_params(sizes))
            + d + (v * d + d) + (d * v + v))


def token_flops(sizes: dict, context: float) -> float:
    """Operations to produce one token's output with ``context`` earlier
    tokens (itself included) in the latent cache."""
    return 2.0 * matmul_params_per_token(sizes) \
        + read_ops_per_position(sizes) * context


def kv_bytes_per_token(sizes: dict, bytes_per_value: int = 2) -> int:
    """One latent row a layer: no head count in it."""
    return sizes["layers"] * (sizes["kv_rank"] + sizes["rope_dim"]) \
        * bytes_per_value


def _own_part(sizes: dict, first: int, count: int):
    """A prompt span without the document it took from cached pages."""
    cached = int(sizes.get("cached_prefix_tokens", 0))
    if first == 1 and cached and count > cached:
        return cached + 1, count - cached
    return first, count


def requests_flops(sizes: dict, spans) -> float:
    """Operations for spans of tokens: each span is (first context,
    count): ``count`` consecutive tokens whose contexts run from ``first
    context`` upward by one. A prompt of n tokens is (1, n)."""
    total = 0.0
    lin = 2.0 * matmul_params_per_token(sizes)
    att = float(read_ops_per_position(sizes))
    for first, count in spans:
        if count <= 0:
            continue
        first, count = _own_part(sizes, first, count)
        total += count * lin \
            + att * (count * first + count * (count - 1) / 2.0)
    return total


def paged_read(sizes: dict, spans, bytes_per_value: int = 2) -> dict:
    """What the latent read has to do for those spans, over all layers:
    the operations, and the bytes (the live rows of the context, read once
    per decoded token; a prefilled chunk's tokens share one read of their
    common context, so a prompt span counts its final context once per
    ``chunk`` tokens)."""
    att = float(read_ops_per_position(sizes))
    kv = kv_bytes_per_token(sizes, bytes_per_value)
    ops = 0.0
    nbytes = 0.0
    for first, count, chunk in spans:
        if count <= 0:
            continue
        first, count = _own_part(sizes, first, count)
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
    the batch and however the tokens are routed: attention, the dense
    block's feed-forward, the routers, the shared experts and the head. A
    floor: it leaves out the routed experts (which of them a step touches
    is the routing's), the latent rows and every activation, so the step's
    true traffic is larger and a share computed from this can never pass
    100%."""
    dense, moe = _layers(sizes)
    params = (sizes["layers"] * attention_matmul_params(sizes)
              + dense * mlp_params(sizes)
              + moe * (router_params(sizes) + shared_expert_params(sizes))
              + sizes["d_model"] * sizes["vocab"])
    return params * bytes_per_value
