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
    pool = s((P, H, ps, d), jnp.int8 if quant else jnp.float32)
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
