"""Operations and bytes of one chip's share of Olmo-Hybrid-7B, from shapes
(``sizes`` of ``configs/olmo_hybrid_7b.json``).

Per token: every product counts 2 operations per parameter it multiplies
(the depthwise convolution's weights among them). A full layer: its four
projections once a token, and the read of the cache: 4 x heads x head_dim
operations a visible key (scores and the weighted sum of values), every
earlier token and itself visible. A linear layer: its two projections and
the convolution once a token, and the delta rule's own products on the
``[dv, dk]`` state of each head: what the decayed state reads along the
key (``S k``), the rank-one write (``u k^T``) and the output (``S q``),
2 x dk x dv operations each, whatever form (chunked or stepped) the
program takes them in. The feed-forward and the head count; the embedding
is a lookup and counts nothing (the program spends a [vocab x d_model]
product on it: that is its waste, not work the algorithm needs).
"""

from __future__ import annotations


def _kinds(sizes: dict):
    """(linear layers, full layers)."""
    linear = sum(1 for k in sizes["layer_types"] if k == "linear")
    return linear, sizes["layers"] - linear


def full_mixer_params(sizes: dict) -> int:
    """Wq, Wk, Wv, Wo: as many key/value heads as query heads."""
    return 4 * sizes["d_model"] * sizes["heads"] * sizes["head_dim"]


def conv_dim(sizes: dict) -> int:
    H = sizes["linear_heads"]
    return H * (2 * sizes["linear_key_dim"] + sizes["linear_value_dim"])


def linear_mixer_params(sizes: dict) -> int:
    """W_in ([q | k | v | z | a | b]), W_out and the convolution; the
    per-head scalars and the head norm are not products."""
    d, H = sizes["d_model"], sizes["linear_heads"]
    Vw = H * sizes["linear_value_dim"]
    return (d * (conv_dim(sizes) + Vw + 2 * H) + Vw * d
            + conv_dim(sizes) * sizes["linear_conv"])


def mlp_params(sizes: dict) -> int:
    return 3 * sizes["d_model"] * sizes["mlp_width"]


def matmul_params_per_token(sizes: dict) -> int:
    """Parameters a token multiplies: the blocks and the head."""
    linear, full = _kinds(sizes)
    return (linear * linear_mixer_params(sizes)
            + full * full_mixer_params(sizes)
            + sizes["layers"] * mlp_params(sizes)
            + sizes["d_model"] * sizes["vocab"])


def delta_rule_ops_per_token(sizes: dict) -> int:
    """The recurrence's three products on each head's state, all linear
    layers: ``S k``, ``u k^T``, ``S q``."""
    linear, _ = _kinds(sizes)
    return linear * 3 * 2 * sizes["linear_heads"] \
        * sizes["linear_key_dim"] * sizes["linear_value_dim"]


def read_ops_per_key(sizes: dict) -> int:
    """Scores against one visible key and its share of the weighted sum,
    all heads of ONE full layer."""
    return 4 * sizes["heads"] * sizes["head_dim"]


def parameter_count(sizes: dict) -> int:
    """Every parameter held here (norms, the per-head scalars and the
    zero biases of embedding and head included)."""
    linear, full = _kinds(sizes)
    d, v, H = sizes["d_model"], sizes["vocab"], sizes["linear_heads"]
    A = sizes["heads"] * sizes["head_dim"]
    return (linear * (linear_mixer_params(sizes) + 2 * H
                      + sizes["linear_value_dim"])
            + full * (full_mixer_params(sizes) + 2 * A)
            + sizes["layers"] * (mlp_params(sizes) + 2 * d)
            + d + (v * d + d) + (d * v + v))


def visible_keys(first: int, count: int) -> float:
    """Keys visible to ``count`` consecutive tokens whose contexts run from
    ``first`` upward by one, summed."""
    return count * first + count * (count - 1) / 2.0


def token_flops(sizes: dict, context: float) -> float:
    """Operations to produce one token's output with ``context`` earlier
    tokens (itself included) in the cache."""
    _, full = _kinds(sizes)
    return (2.0 * matmul_params_per_token(sizes)
            + delta_rule_ops_per_token(sizes)
            + read_ops_per_key(sizes) * full * context)


def requests_flops(sizes: dict, spans) -> float:
    """Operations for spans of tokens: each span is (first context,
    count): ``count`` consecutive tokens whose contexts run from ``first
    context`` upward by one. A prompt of n tokens is (1, n)."""
    _, full = _kinds(sizes)
    per_token = 2.0 * matmul_params_per_token(sizes) \
        + delta_rule_ops_per_token(sizes)
    att = float(read_ops_per_key(sizes)) * full
    total = 0.0
    for first, count in spans:
        if count > 0:
            total += count * per_token + att * visible_keys(first, count)
    return total


def kv_bytes_per_token(sizes: dict, bytes_per_value: int = 2) -> int:
    """A key and a value for every head, ALL full layers."""
    _, full = _kinds(sizes)
    return full * 2 * sizes["heads"] * sizes["head_dim"] * bytes_per_value


def paged_read(sizes: dict, spans, bytes_per_value: int = 2) -> dict:
    """What the paged read has to do for ``(first, count, chunk)`` spans,
    the full layers together: the operations (``read_ops_per_key`` a
    visible key a layer) and the bytes (the live keys and values of the
    context in bfloat16, read once per decoded token; a prefilled chunk's
    tokens share one read of their common context, so a prompt span counts
    its final context once per ``chunk`` tokens)."""
    _, full = _kinds(sizes)
    att = float(read_ops_per_key(sizes)) * full
    kv = kv_bytes_per_token(sizes, bytes_per_value)
    ops = 0.0
    nbytes = 0.0
    for first, count, chunk in spans:
        if count <= 0:
            continue
        ops += att * visible_keys(first, count)
        if chunk <= 1:
            nbytes += kv * visible_keys(first, count)
        else:
            done = 0
            while done < count:
                n = min(chunk, count - done)
                nbytes += kv * (first + done + n - 1)
                done += n
    return {"ops": ops, "bytes": nbytes}


def linear_state_bytes(sizes: dict) -> int:
    """One slot's linear-attention state, all linear layers: a float32
    ``[heads, dv, dk]`` state and a bfloat16 convolution tail of ``K - 1``
    inputs."""
    linear, _ = _kinds(sizes)
    H, dk, dv = (sizes["linear_heads"], sizes["linear_key_dim"],
                 sizes["linear_value_dim"])
    return linear * (4 * H * dv * dk
                     + 2 * (sizes["linear_conv"] - 1) * conv_dim(sizes))


def decode_step_min_bytes(sizes: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights that EVERY decode micro-step has to read: the
    blocks' and the head's (the embedding is a lookup). A floor: it leaves
    out the linear layers' state (``linear_state_bytes`` a slot),
    the keys and values and every activation, so the step's true traffic is
    larger and a share computed from this can never pass 100%."""
    linear, full = _kinds(sizes)
    d = sizes["d_model"]
    A = sizes["heads"] * sizes["head_dim"]
    params = (linear * (linear_mixer_params(sizes)
                        + 2 * sizes["linear_heads"]
                        + sizes["linear_value_dim"])
              + full * (full_mixer_params(sizes) + 2 * A)
              + sizes["layers"] * (mlp_params(sizes) + 2 * d)
              + d + d * sizes["vocab"])
    return params * bytes_per_value
