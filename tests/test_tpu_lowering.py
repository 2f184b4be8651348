"""Cross-platform TPU lowering of every Pallas kernel variant the main
path reaches (``chip_smoke.py`` lists them): paged-attention read, f32 and
int8 pools, masked and unmasked, decode (T=1) and prefill-chunk rows; flash
attention forward + dq + dk/dv, masked and unmasked, f32 and bf16.

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
from deeplearning4j_tpu.ops.pallas_attention import flash_attention

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
