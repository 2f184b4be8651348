"""Operations and bytes of one chip's share of Qwen3-Next-80B-A3B-Instruct,
from shapes (``sizes`` of ``configs/qwen3_next_80b_a3b_instruct.json``).

Per token: every product counts 2 operations per parameter it multiplies
(the depthwise convolution's weights among them). A full layer: its five
projections (Wq, Wg, Wk, Wv, Wo) once a token, and the read of the cache:
4 x heads x head_dim operations a visible key (scores and the weighted
sum of values), every earlier token and itself visible. A linear layer:
its two projections and the convolution once a token, and the delta
rule's own products on the ``[dv, dk]`` state of each VALUE head: what the
decayed state reads along the key (``S k``), the rank-one write (``u
k^T``) and the output (``S q``), 2 x dk x dv operations each, whatever
form the program takes them in. Every block's MoE: the router over ALL
experts, the shared expert and its gate, and the routed experts at their
EXPECTATION for this share: a token chooses ``top_k`` of ``experts`` and
``experts_held[1]`` of them live here, so it runs top_k x held / experts
of them (10 x 128 / 512 = 2.5 at the published sizes). The head counts;
the embedding is a lookup and counts nothing.
"""

from __future__ import annotations


def _kinds(sizes: dict):
    """(linear layers, full layers)."""
    linear = sum(1 for k in sizes["layer_types"] if k == "linear")
    return linear, sizes["layers"] - linear


def full_mixer_params(sizes: dict) -> int:
    """Wq and the gate Wg (``heads`` heads), Wk and Wv (``kv_heads``), Wo."""
    d, A = sizes["d_model"], sizes["heads"] * sizes["head_dim"]
    return 3 * d * A + 2 * d * sizes["kv_heads"] * sizes["head_dim"]


def conv_dim(sizes: dict) -> int:
    return (2 * sizes["linear_key_heads"] * sizes["linear_key_dim"]
            + sizes["linear_heads"] * sizes["linear_value_dim"])


def linear_mixer_params(sizes: dict) -> int:
    """W_in ([q | k | v | z | a | b]), W_out and the convolution; the
    per-head scalars and the head norm are not products."""
    d, H = sizes["d_model"], sizes["linear_heads"]
    Vw = H * sizes["linear_value_dim"]
    return (d * (conv_dim(sizes) + Vw + 2 * H) + Vw * d
            + conv_dim(sizes) * sizes["linear_conv"])


def expert_params(sizes: dict) -> int:
    """One routed expert: gated in, then out."""
    return 3 * sizes["d_model"] * sizes["expert_width"]


def shared_expert_params(sizes: dict) -> int:
    """The shared expert and its gate's one column."""
    return 3 * sizes["d_model"] * sizes["shared_width"] + sizes["d_model"]


def router_params(sizes: dict) -> int:
    return sizes["d_model"] * sizes["experts"]


def experts_per_token_here(sizes: dict) -> float:
    return sizes["top_k"] * sizes["experts_held"][1] / sizes["experts"]


def matmul_params_per_token(sizes: dict) -> float:
    """Parameters a token multiplies, the routed experts at their
    expectation for this share: blocks + output head."""
    linear, full = _kinds(sizes)
    return (linear * linear_mixer_params(sizes)
            + full * full_mixer_params(sizes)
            + sizes["layers"] * (router_params(sizes)
                                 + shared_expert_params(sizes)
                                 + experts_per_token_here(sizes)
                                 * expert_params(sizes))
            + sizes["d_model"] * sizes["vocab"])


def delta_rule_ops_per_token(sizes: dict) -> int:
    """The recurrence's three products on each value head's state, all
    linear layers: ``S k``, ``u k^T``, ``S q``."""
    linear, _ = _kinds(sizes)
    return linear * 3 * 2 * sizes["linear_heads"] \
        * sizes["linear_key_dim"] * sizes["linear_value_dim"]


def read_ops_per_key(sizes: dict) -> int:
    """Scores against one visible key and its share of the weighted sum,
    all query heads of ONE full layer."""
    return 4 * sizes["heads"] * sizes["head_dim"]


def parameter_count(sizes: dict) -> int:
    """Every parameter held here (norms, the per-head scalars and the zero
    biases of embedding and head included)."""
    linear, full = _kinds(sizes)
    d, v, H = sizes["d_model"], sizes["vocab"], sizes["linear_heads"]
    return (linear * (linear_mixer_params(sizes) + 2 * H
                      + sizes["linear_value_dim"])
            + full * (full_mixer_params(sizes) + 2 * sizes["head_dim"])
            + sizes["layers"] * (router_params(sizes)
                                 + shared_expert_params(sizes)
                                 + sizes["experts_held"][1]
                                 * expert_params(sizes) + 2 * d)
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
    """A key and a value for every key/value head, ALL full layers."""
    _, full = _kinds(sizes)
    return full * 2 * sizes["kv_heads"] * sizes["head_dim"] * bytes_per_value


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
    ``[value heads, dv, dk]`` state and a bfloat16 convolution tail of
    ``K - 1`` inputs."""
    linear, _ = _kinds(sizes)
    H, dk, dv = (sizes["linear_heads"], sizes["linear_key_dim"],
                 sizes["linear_value_dim"])
    return linear * (4 * H * dv * dk
                     + 2 * (sizes["linear_conv"] - 1) * conv_dim(sizes))


def held_expert_bytes(sizes: dict, bytes_per_value: int = 2) -> int:
    """One held routed expert's weights: what a grouped product has to read
    for an expert that gets at least one row."""
    return expert_params(sizes) * bytes_per_value


def decode_step_min_bytes(sizes: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights that EVERY decode micro-step has to read, whatever
    the batch and however the tokens are routed: the mixers, the norms, the
    routers, the shared experts and their gates, and the head (the
    embedding is a lookup). A floor: it leaves out the routed experts
    (which of them a step touches is the routing's: ``held_expert_bytes``
    each, read by ``moe_expert_stream_pct``), the linear state
    (``linear_state_bytes`` a slot), the keys and values and every
    activation, so a share computed from this can never pass 100%."""
    linear, full = _kinds(sizes)
    d = sizes["d_model"]
    params = (linear * (linear_mixer_params(sizes)
                        + 2 * sizes["linear_heads"]
                        + sizes["linear_value_dim"])
              + full * (full_mixer_params(sizes) + 2 * sizes["head_dim"])
              + sizes["layers"] * (router_params(sizes)
                                   + shared_expert_params(sizes) + 2 * d)
              + d + d * sizes["vocab"])
    return params * bytes_per_value
