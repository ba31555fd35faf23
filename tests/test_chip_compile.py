"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler lowers each kernel at ``mla-7b`` widths
(H=32, d_c=512, d_r=64, page 128) for a v5e device that is described, not
present, and each test checks that the compiled program holds the kernel
(``tpu_custom_call``). Interpret mode accepts block shapes and vector ops the
chip's compiler refuses; these compiles catch that without chip time.

The topology is described inside the fixture only (never at import): one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.kvcache import MLACache, PagedMLAPool
from repro.kernels.mla_decode import kernel as K
from repro.kernels.quantize import fetch_dequant as FD
from repro.kernels.quantize import kernel as QK

H, D_C, D_R, PAGE = 32, 512, 64, 128      # mla-7b decode widths
B, P, N_PAGES, SPLITS = 8, 8, 64, 2
F8, F32, BF16, I32 = jnp.float8_e4m3fn, jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"    # no compiler logs on disk
    # a described chip's compile cannot be read back: keep it out of the
    # persistent cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the compiled text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool_shapes(rope_dtype=F32):
    return [((N_PAGES, PAGE, D_C), F8), ((N_PAGES, PAGE, D_R), rope_dtype),
            ((N_PAGES, PAGE), F32), ((B, P), I32), ((B,), I32)]


@pytest.mark.parametrize("rescale", ["fma", "amla"])
@pytest.mark.parametrize("q_len", [1, 4])
def test_paged_splitkv_decode_compiles(one_chip, q_len, rescale):
    """Paged split-KV decode (q_len 1) and the speculative-verify block
    (q_len 4), with the split combine that follows each."""
    q = (B, H) if q_len == 1 else (B, q_len, H)
    c, r, s, pt, sl = _pool_shapes()

    def fn(q_c8, q_r, sigma_q, content, rope, scale, table, lens):
        return K.mla_decode_paged_splitkv_pallas(
            q_c8, q_r, sigma_q, content, rope, scale, table, lens,
            softmax_scale=0.04, num_splits=SPLITS, interpret=False,
            rescale=rescale)

    text = _compile(one_chip, fn, (q + (D_C,), F8), (q + (D_R,), F32),
                    (q, F32), c, r, s, pt, sl)
    assert "tpu_custom_call" in text
    # every pool operand, scales included, is read by the page-table index
    # maps: no XLA gather of the pool runs beside the kernel
    assert "gather" not in text


def _contiguous_shapes(q_len):
    q = (B, H) if q_len == 1 else (B, q_len, H)
    n = P * PAGE
    return [(q + (D_C,), F8), (q + (D_R,), F32), (q, F32),
            ((B, n, D_C), F8), ((B, n, D_R), F32), ((B, n), F32),
            ((B,), I32)]


@pytest.mark.parametrize("rescale", ["fma", "amla"])
@pytest.mark.parametrize("q_len", [1, 4])
def test_contiguous_splitkv_decode_compiles(one_chip, q_len, rescale):
    """Contiguous-cache split-KV decode and its q_len 4 verify block."""
    def fn(q_c8, q_r, sigma_q, content, rope, sigma_k, lens):
        return K.mla_decode_splitkv_pallas(
            q_c8, q_r, sigma_q, content, rope, sigma_k, lens,
            softmax_scale=0.04, num_splits=SPLITS, block_n=PAGE,
            interpret=False, rescale=rescale)

    text = _compile(one_chip, fn, *_contiguous_shapes(q_len))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rescale", ["fma", "amla"])
def test_contiguous_single_pass_decode_compiles(one_chip, rescale):
    """num_splits=1 on a contiguous cache: the serial-block kernel."""
    def fn(q_c8, q_r, sigma_q, content, rope, sigma_k, lens):
        return K.mla_decode_pallas(
            q_c8, q_r, sigma_q, content, rope, sigma_k, lens,
            softmax_scale=0.04, block_n=PAGE, interpret=False,
            rescale=rescale)

    text = _compile(one_chip, fn, *_contiguous_shapes(1))
    assert "tpu_custom_call" in text


def test_paged_single_pass_decode_compiles(one_chip):
    """num_splits=1: the serial-page paged kernel."""
    def fn(q_c8, q_r, sigma_q, content, rope, scale, table, lens):
        return K.mla_decode_paged_pallas(
            q_c8, q_r, sigma_q, content, rope, scale, table, lens,
            softmax_scale=0.04, interpret=False)

    text = _compile(one_chip, fn, ((B, H, D_C), F8), ((B, H, D_R), F32),
                    ((B, H), F32), *_pool_shapes()[:4], ((B,), I32))
    assert "tpu_custom_call" in text
    assert "gather" not in text


@pytest.mark.parametrize("combine", ["lse", "amla"])
def test_split_combine_compiles(one_chip, combine):
    vec = ((B, SPLITS, H), F32)
    if combine == "lse":
        text = _compile(one_chip, lambda o, l: K.lse_combine_pallas(
            o, l, interpret=False), ((B, SPLITS, H, D_C), F32), vec)
    else:
        text = _compile(one_chip, lambda o, l, g: K.amla_combine_pallas(
            o, l, g, interpret=False), ((B, SPLITS, H, D_C), F32), vec, vec)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bounded", [True, False])
def test_paged_fetch_dequant_compiles(one_chip, bounded):
    """The chunked-prefill prefix fetch, bounded by chunk_start (what the
    engine runs) and over the full page-table span."""
    def fn(content, rope, scale, table, lens, chunk_start):
        pool = PagedMLAPool(content=content, rope=rope, scale=scale,
                            page_table=table, seq_lens=lens)
        return FD.paged_fetch_dequant_pallas(
            pool, chunk_start=chunk_start if bounded else None,
            interpret=False)

    text = _compile(one_chip, fn, *_pool_shapes(BF16), ((B,), I32))
    assert "tpu_custom_call" in text
    assert "gather" not in text


def test_contiguous_fetch_dequant_compiles(one_chip):
    """The fetch-dequant kernel over a contiguous cache."""
    n = P * PAGE

    def fn(content, rope, scale, lens):
        cache = MLACache(content=content, rope=rope, scale=scale,
                         seq_lens=lens)
        return FD.fetch_dequant_pallas(cache, page=PAGE, interpret=False)

    text = _compile(one_chip, fn, ((B, n, D_C), F8), ((B, n, D_R), BF16),
                    ((B, n), F32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_quantize_kernels_compile(one_chip):
    """Fused-Q-Quant and Fused-K-Append (contiguous-cache token prep)."""
    n = P * PAGE
    q_text = _compile(one_chip, lambda q: QK.fused_q_quant_pallas(
        q, D_C, interpret=False), ((B, H, D_C + D_R), F32))
    k_text = _compile(
        one_chip, lambda c, r, s, ckv, kr, sl: QK.fused_k_append_pallas(
            c, r, s, ckv, kr, sl, interpret=False),
        ((B, n, D_C), F8), ((B, n, D_R), BF16), ((B, n), F32),
        ((B, D_C), F32), ((B, D_R), F32), ((B,), I32))
    assert "tpu_custom_call" in q_text and "tpu_custom_call" in k_text
