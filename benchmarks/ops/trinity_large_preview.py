"""Operations and bytes of one chip's share of Trinity-Large-Preview, from
shapes (``sizes`` of ``configs/trinity_large_preview.json``).

Per token: every product counts 2 operations per parameter it multiplies.
An attention layer: the five projections (Wq, Wk, Wv, Wg, Wo) once a
token, and the read of the cache: 4 x heads x head_dim operations a visible
key (scores and the weighted sum of values). A ``full`` layer sees every
earlier token and itself; a ``sliding`` layer sees ``min(context,
window)`` of them, whatever the program gathers to find them. The first
``dense_layers`` blocks: the dense gated feed-forward. Later blocks: the
router over ALL experts, the shared expert, and the routed experts at
their EXPECTATION for this share: a token chooses ``top_k`` of ``experts``
and ``experts_held[1]`` of them live here, so it runs top_k x held /
experts of them (4 x 32 / 256 = 0.5 at the published sizes), whatever the
routing of one run was (``select_bias`` moves it seed by seed). The head
counts; the embedding is a lookup and counts nothing (the program spends a
[vocab x d_model] product on it: that is its waste, not work the algorithm
needs).
"""

from __future__ import annotations


def attention_matmul_params(sizes: dict) -> int:
    d, H, G, dh = (sizes["d_model"], sizes["heads"], sizes["kv_heads"],
                   sizes["head_dim"])
    return d * H * dh * 3 + d * G * dh * 2       # Wq, Wg, Wo; Wk, Wv


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


def _kinds(sizes: dict):
    """(sliding layers, full layers)."""
    sliding = sum(1 for k in sizes["layer_types"] if k == "sliding")
    return sliding, sizes["layers"] - sliding


def matmul_params_per_token(sizes: dict) -> float:
    """Parameters a token multiplies, the routed experts at their
    expectation for this share: blocks + output head."""
    dense, moe = _layers(sizes)
    return (sizes["layers"] * attention_matmul_params(sizes)
            + dense * mlp_params(sizes)
            + moe * (router_params(sizes) + shared_expert_params(sizes)
                     + experts_per_token_here(sizes) * expert_params(sizes))
            + sizes["d_model"] * sizes["vocab"])


def read_ops_per_key(sizes: dict) -> int:
    """Scores against one visible key and its share of the weighted sum,
    all query heads of ONE layer."""
    return 4 * sizes["heads"] * sizes["head_dim"]


def parameter_count(sizes: dict) -> int:
    """Every parameter held here (norms, the selection bias and zero
    biases included)."""
    dense, moe = _layers(sizes)
    d, v = sizes["d_model"], sizes["vocab"]
    return (sizes["layers"] * (attention_matmul_params(sizes)
                               + 2 * sizes["head_dim"] + 4 * d)
            + dense * mlp_params(sizes)
            + moe * (router_params(sizes) + sizes["experts"]
                     + shared_expert_params(sizes)
                     + sizes["experts_held"][1] * expert_params(sizes))
            + d + (v * d + d) + (d * v + v))


def visible_keys(first: int, count: int, window=None) -> float:
    """Keys visible to ``count`` consecutive tokens whose contexts run from
    ``first`` upward by one, summed: each sees its context, or at most
    ``window`` of it."""
    total = count * first + count * (count - 1) / 2.0
    if window is None or first + count - 1 <= window:
        return total
    # tokens whose context passes the window see the window alone
    over = min(count, first + count - 1 - window)
    start = first + count - over                    # the first such context
    return total - (over * start + over * (over - 1) / 2.0) + over * window


def token_flops(sizes: dict, context: float) -> float:
    """Operations to produce one token's output with ``context`` earlier
    tokens (itself included) in the cache."""
    sliding, full = _kinds(sizes)
    return 2.0 * matmul_params_per_token(sizes) + read_ops_per_key(sizes) * (
        full * context + sliding * min(context, sizes["window"]))


def kv_bytes_per_token(sizes: dict, bytes_per_value: int = 2) -> int:
    """A key and a value per key/value head, ONE layer."""
    return 2 * sizes["kv_heads"] * sizes["head_dim"] * bytes_per_value


def requests_flops(sizes: dict, spans) -> float:
    """Operations for spans of tokens: each span is (first context,
    count): ``count`` consecutive tokens whose contexts run from ``first
    context`` upward by one. A prompt of n tokens is (1, n)."""
    total = 0.0
    lin = 2.0 * matmul_params_per_token(sizes)
    att = float(read_ops_per_key(sizes))
    sliding, full = _kinds(sizes)
    for first, count in spans:
        if count <= 0:
            continue
        total += count * lin + att * (
            full * visible_keys(first, count)
            + sliding * visible_keys(first, count, sizes["window"]))
    return total


def decode_step_min_bytes(sizes: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights that EVERY decode micro-step has to read, whatever
    the batch and however the tokens are routed: attention, the dense
    block's feed-forward, the routers, the shared experts and the head. A
    floor: it leaves out the routed experts (which of them a step touches
    is the routing's), the keys and values and every activation, so the
    step's true traffic is larger and a share computed from this can never
    pass 100%."""
    dense, moe = _layers(sizes)
    params = (sizes["layers"] * attention_matmul_params(sizes)
              + dense * mlp_params(sizes)
              + moe * (router_params(sizes) + shared_expert_params(sizes))
              + sizes["d_model"] * sizes["vocab"])
    return params * bytes_per_value
