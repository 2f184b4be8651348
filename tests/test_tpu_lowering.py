"""Cross-platform TPU lowering of every Pallas kernel variant the main
path reaches (``chip_smoke.py`` lists them): paged-attention read, f32 and
int8 pools, masked and unmasked, decode (T=1) and prefill-chunk rows, and a
bfloat16 pool of 30 heads and one of 2 heads of 256 read by 16; flash
attention forward + dq + dk/dv, masked and unmasked, f32 and bf16; the
grouped prefill read blocked over its keys (``chunk_attention``) at
trinity's, falcon's and Qwen3-Next's geometry.

No chip and no Mosaic here: ``lower(lowering_platforms=("tpu",))`` with
``interpret=False`` runs the Pallas TPU lowering, which is where a block
shape the TPU refuses (last two dimensions neither (8, 128)-divisible nor
the array's own) raises. Whether Mosaic then compiles the kernel, and what
it computes, is ``chip_smoke.py``'s job on the chip.
"""

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.conf.layers.paged_attention import (
    PallasPagedAttention)
from deeplearning4j_tpu.ops.pallas_attention import (chunk_attention,
                                                     flash_attention)

pytestmark = pytest.mark.pallas


def _lower_for_tpu(fn, *args):
    # the suite runs with x64 on (conftest); the chip runs with it off,
    # and the TPU lowering has no float64
    with jax.enable_x64(False):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("T", [1, 256])
def test_paged_attention_lowers_for_tpu(T, masked, quant):
    # the serving geometry of chip_smoke.py: head_dim 128, 16-token pages,
    # 2048-token capacity
    B, H, d, ps, NP = 2, 8, 128, 16, 128
    P = B * NP + 1
    s = jax.ShapeDtypeStruct
    pool = s((P, ps, H * d), jnp.int8 if quant else jnp.float32)
    scales = s((P, H, ps), jnp.float32) if quant else None
    mask = s((B, T), jnp.float32) if masked else None
    helper = PallasPagedAttention(interpret=False)

    def f(q, kp, vp, bt, pos, mask, ks, vs):
        return helper.attend(q, kp, vp, bt, pos, mask=mask, kscales=ks,
                             vscales=vs)

    _lower_for_tpu(f, s((B, H, T, d), jnp.float32), pool, pool,
                   s((B, NP), jnp.int32), s((B,), jnp.int32), mask, scales,
                   scales)


@pytest.mark.parametrize("T", [1, 1024])
def test_paged_attention_lowers_for_tpu_over_a_bfloat16_pool(T):
    # the Olmo cell's geometry: 30 heads of 128, 16-token pages, a table of
    # 256 pages; a bfloat16 page is one (16, 128) tile a head
    B, H, d, ps, NP = 2, 30, 128, 16, 256
    s = jax.ShapeDtypeStruct
    pool = s((B * NP + 1, ps, H * d), jnp.bfloat16)
    mask = s((B, T), jnp.float32) if T > 1 else None
    helper = PallasPagedAttention(interpret=False)

    def f(q, kp, vp, bt, pos, mask):
        return helper.attend(q, kp, vp, bt, pos, mask=mask)

    _lower_for_tpu(f, s((B, H, T, d), jnp.bfloat16), pool, pool,
                   s((B, NP), jnp.int32), s((B,), jnp.int32), mask)


def test_grouped_paged_read_lowers_for_tpu_at_head_size_256():
    # the Qwen3-Next cell's decode step: 16 query heads over 2 key/value
    # heads of 256 (8 a group), a bfloat16 pool of 16-token pages, a table
    # of 256 pages, the stated scale
    B, H, Hkv, d, ps, NP = 2, 16, 2, 256, 16, 256
    s = jax.ShapeDtypeStruct
    pool = s((B * NP + 1, ps, Hkv * d), jnp.bfloat16)
    helper = PallasPagedAttention(interpret=False)

    def f(q, kp, vp, bt, pos):
        return helper.attend(q, kp, vp, bt, pos, scale=d ** -0.5)

    _lower_for_tpu(f, s((B, H, 1, d), jnp.bfloat16), pool, pool,
                   s((B, NP), jnp.int32), s((B,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("masked", [False, True])
def test_flash_attention_fwd_bwd_lowers_for_tpu(masked, dtype):
    # the LM train shape of chip_smoke.py: T=512, head_dim 32
    B, H, T, d = 2, 8, 512, 32
    x = jax.ShapeDtypeStruct((B, H, T, d), dtype)
    mask = jax.ShapeDtypeStruct((B, T), jnp.float32) if masked else None

    def f(q, k, v, g, mask):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True, mask=mask,
                                            interpret=False), q, k, v)
        return (out,) + vjp(g)

    text = _lower_for_tpu(f, x, x, x, x, mask)
    assert text.count("tpu_custom_call") >= 3    # fwd, dq, dk/dv


@pytest.mark.parametrize("B,H,Hkv,T,S,window,d", [
    (1, 48, 8, 1024, 5136, 4096, 128),   # trinity, a window layer's view
    (1, 48, 8, 1024, 14720, 0, 128),     # trinity, the full layer's
    (2, 20, 4, 256, 2048, 0, 128),       # falcon, two rows of 256
    (1, 48, 8, 384, 4496, 4096, 128),    # a 384-column bucket: tiles of 128
    (1, 16, 2, 1024, 4096, 0, 256),      # Qwen3-Next: heads of 256
], ids=["trinity_window", "trinity_full", "falcon", "window_T384",
        "qwen3next_d256"])
def test_chunk_read_lowers_for_tpu(B, H, Hkv, T, S, window, d):
    s = jax.ShapeDtypeStruct
    view = s((B, Hkv, S, d), jnp.bfloat16)

    def f(q, k, v, q_start, key_valid):
        return chunk_attention(q, k, v, q_start, key_valid, scale=d ** -0.5,
                               window=window, interpret=False)

    text = _lower_for_tpu(f, s((B, H, T, d), jnp.bfloat16), view, view,
                          s((B,), jnp.int32), s((B, S), jnp.float32))
    assert text.count("tpu_custom_call") == 1


def test_grouped_heads_and_latent_reads_never_take_the_chunk_route(
        monkeypatch):
    """The route is the grouped read's alone: a server of plain heads (the
    cgpt cell's kind) and one of latent attention trace their prefill and
    decode programs without calling ``chunk_read``, so this route leaves
    their programs as they were."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo import TransformerLM
    from deeplearning4j_tpu.nn.conf.layers import attention
    from deeplearning4j_tpu.parallel.generation import GenerationServer
    from tests.test_deepseek_v2 import tiny_deepseek

    def refused(*a, **k):
        raise AssertionError("chunk_read reached")

    monkeypatch.setattr(attention, "chunk_read", refused)
    plain = TransformerLM(num_labels=64, max_length=256, d_model=128,
                          n_heads=1, n_blocks=1, seed=5).init()
    latent, _, sizes = tiny_deepseek()
    for net, vocab in ((plain, 64), (latent, sizes["vocab"])):
        srv = GenerationServer(net, vocab, slots=2, page_size=16,
                               prefill_chunk=128)
        try:
            rows, bucket = srv._prefill_rows, min(128, srv._capacity)
            i32, f32 = np.int32, np.float32
            srv._prefill_program(bucket).lower(
                *srv._weights(), srv._pool, srv._bt_arg(),
                np.zeros(rows, i32),
                np.zeros(rows, i32), np.zeros((rows, bucket), i32),
                np.zeros((rows, bucket), f32), np.ones(rows, i32),
                np.zeros(rows, f32), np.zeros(rows, i32),
                np.zeros((rows, 2), np.uint32))
            srv._decode_program().lower(
                *srv._weights(), srv._pool, srv._bt_arg(), srv._pos,
                srv._last,
                srv._active_mask(), srv._temp, srv._topk, srv._keys,
                srv._counts)
        finally:
            srv.close()


# ---- the serving programs compiled for a described chip: the pool's order
#: the compile below takes a few seconds here; past this it is abandoned
#: (a skip, with the reason) so that it can never cost the suite its clock
COMPILE_LIMIT_S = 120


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e: the TPU's compiler is installed
    here and compiles for a chip that is not attached. Described inside
    the fixture, never at import (only one process may load libtpu)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def serving_programs(one_chip):
    """``gen_decode`` and ``gen_prefill`` of a small Pallas-backed server,
    compiled once for the described chip: their texts by name, with the
    element count of a pool plane and the vocabulary."""
    import concurrent.futures

    import numpy as np

    from deeplearning4j_tpu.models.zoo import TransformerLM
    from deeplearning4j_tpu.nn.conf.layers import paged_attention as ppa
    from deeplearning4j_tpu.parallel.generation import GenerationServer

    vocab, slots, pages, bucket = 384, 4, 67, 32
    previous = jax.config.read("jax_disable_most_optimizations")
    with pytest.MonkeyPatch.context() as monkeypatch:
        # off the chip a forced "pallas" is the interpreted kernel; what is
        # compiled for the described chip has to be the Mosaic call
        monkeypatch.setitem(ppa._HELPERS, "pallas",
                            ppa.PallasPagedAttention(interpret=False))
        # XLA's default optimisation level, as on the chip (see conftest)
        jax.config.update("jax_disable_most_optimizations", False)
        net = TransformerLM(num_labels=vocab, max_length=256, d_model=512,
                            n_heads=4, n_blocks=2, seed=5).init()
        srv = GenerationServer(net, vocab, slots=slots, pages=pages,
                               page_size=16, prefill_chunk=bucket,
                               paged_attention="pallas")
        try:
            assert srv._pa == "pallas"

            def on_chip(tree):
                return jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=one_chip), tree)

            weights, pool = on_chip(srv._weights()), on_chip(srv._pool)
            plane = {int(np.prod(a.shape))
                     for a in jax.tree_util.tree_leaves(pool)}
            assert plane == {pages * 16 * 512}
            rows = srv._prefill_rows
            i32, f32 = np.int32, np.float32
            decode = on_chip((srv._bt, srv._pos, srv._last,
                              srv._active_mask(), srv._temp, srv._topk,
                              srv._keys, srv._counts))
            prefill = on_chip((srv._bt, np.zeros(rows, i32),
                               np.zeros(rows, i32),
                               np.zeros((rows, bucket), i32),
                               np.zeros((rows, bucket), f32),
                               np.ones(rows, i32), np.zeros(rows, f32),
                               np.zeros(rows, i32),
                               np.zeros((rows, 2), np.uint32)))
            programs = {"gen_decode": (srv._decode_program(), decode),
                        "gen_prefill": (srv._prefill_program(bucket),
                                        prefill)}

            def compiled_texts():
                # the chip runs with x64 off, and the TPU has no float64;
                # the setting is the thread's own
                with jax.enable_x64(False):
                    return {name: prog.lower(*weights, pool, *args)
                            .compile().as_text()
                            for name, (prog, args) in programs.items()}

            worker = concurrent.futures.ThreadPoolExecutor(1)
            try:
                texts = worker.submit(compiled_texts).result(
                    timeout=COMPILE_LIMIT_S)
            except concurrent.futures.TimeoutError:
                pytest.skip(f"compiling two serving programs for the "
                            f"described v5e took over {COMPILE_LIMIT_S} s "
                            "here")
            finally:
                worker.shutdown(wait=False)
        finally:
            srv.close()
            jax.config.update("jax_disable_most_optimizations", previous)
    return texts, plane, vocab


def test_no_serving_program_copies_the_pool(serving_programs):
    """The page write (XLA's scatter) and the Mosaic read agree on one
    physical order of the pool, ``[pages, page_size, heads * d]``
    row-major, so the compiled decode and prefill programs hold no
    ``copy`` whose result is as large as a pool plane. With heads before
    page rows (``[pages, heads, page_size, d]``) layout assignment gave the
    scatter another order than the kernel's and every program transposed
    each layer's K and V pool around every call: 78% of a busy chip in
    the cgpt cell (PERF.md, PRs 33 and 35)."""
    import re

    import numpy as np

    texts, plane, _ = serving_programs
    copy = re.compile(r" = \w+\[([\d,]+)\]\S* copy\(")
    for name, text in texts.items():
        assert text.count("tpu_custom_call") >= 2, name   # a read a layer
        sizes = [int(np.prod([int(n) for n in m.group(1).split(",")]))
                 for m in copy.finditer(text)]
        assert not [n for n in sizes if n in plane], (
            f"{name} copies a pool plane {sum(n in plane for n in sizes)} "
            "times: the page write and the paged read no longer share one "
            "order of the pool")


def test_no_serving_program_sorts_the_vocabulary(serving_programs):
    """The sampler reads ONE number a row off the vocabulary, the top-k
    cut, and finds it by selection (``zoo.kth_largest``): neither program
    holds a ``sort`` as wide as the vocabulary. One did, over every row at
    every decode micro-step and prefill dispatch: 5.5 of a 26.5 ms step
    at 261,120 entries (PERF.md, PR 37)."""
    import re

    texts, _, vocab = serving_programs
    shape = re.compile(r"\[([\d,]+)\]")
    for name, text in texts.items():
        wide = [line.strip()[:120] for line in text.splitlines()
                if " sort(" in line and any(
                    int(m.group(1).split(",")[-1]) == vocab
                    for m in shape.finditer(line.split(" sort(")[0]))]
        assert not wide, f"{name} sorts the vocabulary: {wide}"


def _compiled_shapes(text):
    """Instruction name -> (opcode, dims) of a compiled HLO text, which
    names its operands without their shapes."""
    import re

    defn = re.compile(r"%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    return {m.group(1): (m.group(3),
                         [int(n) for n in m.group(2).split(",") if n])
            for m in map(defn.search, text.splitlines()) if m}


def _contracted_sizes(text):
    """Each product (``dot``, or the ``convolution`` the TPU compiler makes
    of one) as (size its left operand contracts over, its result's dims)."""
    import re

    shapes = _compiled_shapes(text)
    found = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* (convolution|dot)\(%(\S+?), ",
                      line)
        if not m or m.group(3) not in shapes:
            continue
        lhs = shapes[m.group(3)][1]
        if m.group(2) == "dot":
            axis = int(re.search(r"lhs_contracting_dims=\{(\d+)",
                                 line).group(1))
        else:
            axis = re.search(r"dim_labels=(\w+)_", line).group(1).index("f")
        found.append((lhs[axis],
                      [int(n) for n in m.group(1).split(",") if n]))
    return found


def test_no_serving_program_multiplies_a_one_hot_block(serving_programs):
    """The programs read each token's row of the embedding table by a
    gather: neither holds a product that contracts over the vocabulary nor
    a vocabulary-wide one-hot (an ``iota`` compared against the ids). Such
    a product read the whole table to return a row a token: a 2.67 GB
    table in each of falcon's decode micro-steps (PERF.md, PR 41). The
    head's product, which contracts over ``d_model`` into the vocabulary,
    stays."""
    import re

    texts, _, vocab = serving_programs
    for name, text in texts.items():
        products = _contracted_sizes(text)
        assert products, name
        assert not [p for p in products if p[0] == vocab], (
            f"{name} contracts over the vocabulary: {products}")
        assert [p for p in products if p[0] == 512 and p[1][-1] == vocab], (
            f"{name} lost the head's product")
        shapes = _compiled_shapes(text)
        one_hot = [line.strip()[:120] for line in text.splitlines()
                   if (m := re.search(r" compare\(%(\S+?), %(\S+?)\)", line))
                   and any(shapes.get(o, ("", []))[0] == "iota"
                           and vocab in shapes[o][1] for o in m.groups())]
        assert not one_hot, f"{name} builds a one-hot block: {one_hot}"


def test_grouped_prefill_reads_blocked_in_the_compiled_program(one_chip,
                                                                monkeypatch):
    """A two-layer window-and-full server's ``gen_prefill`` at a 1,024
    bucket, compiled for the described chip as it would run there (the
    route's platform test steered to the TPU): each grouped layer's read is
    one Mosaic call, and no float32 buffer is the shape of a chunk's scores
    over a view (1,024 queries by the view's width), which the materialised
    read wrote, maxed, exponentiated and normalised (PERF.md §5, PR 38)."""
    import concurrent.futures
    import re

    import numpy as np

    from deeplearning4j_tpu.models import TrinityLM
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel.generation import GenerationServer

    vocab, bucket = 64, 1024
    conf = TrinityLM(num_labels=vocab, max_length=2048, d_model=64,
                     layer_types=["sliding", "full"], dense_layers=1,
                     n_heads=6, n_kv_heads=1, head_dim=128, window=256,
                     mlp_width=64, n_experts=4, top_k=2, expert_width=32,
                     shared_width=32, seed=5).conf()
    for v in conf.vertices.values():
        if hasattr(getattr(v, "layer", None), "max_cache"):
            v.layer.max_cache = 2048
    net = ComputationGraph(conf).init()
    srv = GenerationServer(net, vocab, slots=2, page_size=16,
                           prefill_chunk=bucket)
    previous = jax.config.read("jax_disable_most_optimizations")
    try:
        assert srv._prefill_rows == 1 and srv._capacity == 2048
        widths = {srv._capacity,
                  srv._layer_by_name["attn0"].window_pages(bucket, 16) * 16}
        on_chip = (lambda tree: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree))
        i32, f32 = np.int32, np.float32
        args = on_chip((*srv._weights(), srv._pool, srv._bt_arg(),
                        np.zeros(1, i32), np.zeros(1, i32),
                        np.zeros((1, bucket), i32),
                        np.zeros((1, bucket), f32), np.ones(1, i32),
                        np.zeros(1, f32), np.zeros(1, i32),
                        np.zeros((1, 2), np.uint32)))
        prog = srv._prefill_program(bucket)
        jax.config.update("jax_disable_most_optimizations", False)
        # the route and the kernel ask which backend runs them: the chip's
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def compiled():
            with jax.enable_x64(False):
                return prog.lower(*args).compile().as_text()

        worker = concurrent.futures.ThreadPoolExecutor(1)
        try:
            text = worker.submit(compiled).result(timeout=COMPILE_LIMIT_S)
        except concurrent.futures.TimeoutError:
            pytest.skip(f"compiling gen_prefill for the described v5e took "
                        f"over {COMPILE_LIMIT_S} s here")
        finally:
            worker.shutdown(wait=False)
    finally:
        srv.close()
        jax.config.update("jax_disable_most_optimizations", previous)
    assert text.count("tpu_custom_call") >= 2, "a Mosaic call a layer"
    dims = {m.group(0): set(map(int, m.group(1).split(",")))
            for m in re.finditer(r"f32\[([\d,]+)\]", text)}
    scores = [shape for shape, d in dims.items()
              if bucket in d and d & widths]
    assert not scores, f"float32 score planes remain: {sorted(set(scores))}"


# ---- a grouped, scaled or windowed layer's decode step: the Mosaic read
def _grouped_net(layer_types):
    """A tiny trinity-shaped net: six query heads a key/value head of 128
    lanes, a stated scale, a window of 32 where a layer slides."""
    from deeplearning4j_tpu.models import TrinityLM

    return TrinityLM(num_labels=64, max_length=256, d_model=32,
                     layer_types=layer_types, dense_layers=1, n_heads=6,
                     n_kv_heads=1, head_dim=128, window=32, mlp_width=32,
                     n_experts=4, top_k=2, expert_width=16, shared_width=16,
                     dtype="bfloat16", seed=5).init()


def _lowered(srv, program):
    """``gen_decode`` or ``gen_prefill`` (at a bucket of 32) of ``srv``
    lowered for the TPU: its StableHLO text."""
    import numpy as np

    i32, f32 = np.int32, np.float32
    rows = srv._prefill_rows
    if program == "gen_decode":
        prog = srv._decode_program()
        args = (srv._pos, srv._last, srv._active_mask(), srv._temp,
                srv._topk, srv._keys, srv._counts)
    else:
        prog = srv._prefill_program(32)
        args = (np.zeros(rows, i32), np.zeros(rows, i32),
                np.zeros((rows, 32), i32), np.zeros((rows, 32), f32),
                np.ones(rows, i32), np.zeros(rows, f32),
                np.zeros(rows, i32), np.zeros((rows, 2), np.uint32))
    with jax.enable_x64(False):
        return prog.trace(*srv._weights(), srv._pool, srv._bt_arg(),
                          *args).lower(lowering_platforms=("tpu",)).as_text()


@pytest.fixture
def mosaic_helper(monkeypatch):
    """Off the chip a forced "pallas" is the interpreted kernel; what is
    lowered for the TPU has to be the Mosaic call."""
    from deeplearning4j_tpu.nn.conf.layers import paged_attention as ppa

    monkeypatch.setitem(ppa._HELPERS, "pallas",
                        ppa.PallasPagedAttention(interpret=False))
    return ppa


@pytest.mark.parametrize("layer_types", [["full"], ["sliding", "full"]],
                         ids=["grouped", "window_class"])
def test_grouped_decode_reads_pages_in_place(layer_types, mosaic_helper):
    """Under ``pallas`` a grouped server's ``gen_decode`` reads every paged
    layer through the Mosaic kernel and gathers no dense view: no
    ``[slots, pages, page_size, kv_heads x d]`` gather of a whole table
    or a window's pages, no ``[slots, kv_heads, width, d]`` view. Its
    ``gen_prefill`` is the program the ``xla`` resolution lowers: the
    chunk's reads over gathered views did not change."""
    import re

    from deeplearning4j_tpu.parallel.generation import GenerationServer

    net = _grouped_net(layer_types)
    texts = {}
    for backend in ("pallas", "xla"):
        srv = GenerationServer(net, 64, slots=2, page_size=16,
                               prefill_chunk=32, steps_per_dispatch=2,
                               paged_attention=backend)
        try:
            assert srv._pa == backend
            texts[backend] = {p: _lowered(srv, p)
                              for p in ("gen_decode", "gen_prefill")}
            S, NP = srv.slots, srv._np
            widths = {NP} | {srv._layer_by_name[n].window_pages(2, 16)
                             for n in srv._paged_names
                             if srv._layer_by_name[n].window}
        finally:
            srv.close()
    decode = texts["pallas"]["gen_decode"]
    assert "call @_pallas_paged_attention" in decode
    assert decode.count("tpu_custom_call") >= 1
    views = [m.group(0) for m in re.finditer(
        r"tensor<(\d+)x(\d+)x(\d+)x(\d+)x?\w*>", decode)
        if int(m.group(1)) == S and (
            (int(m.group(2)) in widths and int(m.group(3)) == 16
             and int(m.group(4)) == 128)
            or (int(m.group(2)) == 1 and int(m.group(4)) == 128
                and int(m.group(3)) in {16 * w for w in widths}))]
    assert not views, f"gen_decode gathers dense views: {set(views)}"
    # the XLA family's decode does gather them (what the check looks for)
    assert re.search(rf"tensor<{S}x1x{16 * NP}x128x", texts["xla"][
        "gen_decode"])
    assert texts["pallas"]["gen_prefill"] == texts["xla"]["gen_prefill"]


def test_a_plain_servers_decode_is_unchanged_by_the_new_arguments(
        mosaic_helper, monkeypatch):
    """A plain server (one key/value head a query head, the 1/sqrt(d)
    scale, no window) hands the kernel its neutral arguments: its
    ``gen_decode`` traces to the program it traces to when the kernel is
    called without them, as it was before they existed (the kernel's body
    compared as its jaxpr, whose source locations are left out: Mosaic's
    serialised body carries them, and a wrapper adds a frame)."""
    import re

    from deeplearning4j_tpu.models.zoo import TransformerLM
    from deeplearning4j_tpu.parallel.generation import GenerationServer

    ppa = mosaic_helper
    net = TransformerLM(num_labels=64, max_length=256, d_model=256,
                        n_heads=2, n_blocks=1, seed=5).init()
    kernel = ppa._pallas_paged_attention
    texts = []
    for call in (kernel, None):
        if call is None:
            def call(*args, interpret, scale=None, window=0):
                assert scale is None and window == 0
                return kernel(*args, interpret=interpret)

            net._output_cache.clear()
        monkeypatch.setattr(ppa, "_pallas_paged_attention", call)
        srv = GenerationServer(net, 64, slots=2, page_size=16,
                               prefill_chunk=32, paged_attention="pallas")
        try:
            with jax.enable_x64(False):
                jaxpr = srv._decode_program().trace(
                    *srv._weights(), srv._pool, srv._bt, srv._pos,
                    srv._last, srv._active_mask(), srv._temp, srv._topk,
                    srv._keys, srv._counts).jaxpr
        finally:
            srv.close()
        texts.append(re.sub(r"[\w/.-]+\.py:\d+(:\d+)?", "", str(jaxpr)))
    assert "name=_pallas_paged_attention" in texts[0]
    assert "pallas_call" in texts[0]
    assert texts[0] == texts[1]
