"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (the rest of the suite) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, unsigned reductions, narrow dots.
These tests compile each kernel for a described (not attached) v5e chip —
Qwen1.5-4B widths (d_model 2560, d_ff 6912, 20 KV heads of 128),
DeepSeek-LLM-7B widths (d_model 4096, d_ff 11008, a 102400-wide output
head, 32 KV heads of 128), and one GQA attention case at Minitron-4B's 8
KV heads, 3 queries each — and check that the program holds the Mosaic
kernel. Nothing runs; the topology is described inside a fixture so that
importing this file touches no TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ecc_decode, ecc_encode, paged_attention, platform
from repro.kernels.ecc_qmatmul import ecc_qmatmul

D_MODEL, D_FF, BATCH, SEQ, HD = 2560, 6912, 4, 512, 128
CELL_SEQ = 384   # the serving benchmark's strip length (max_len)
DS_MODEL, DS_FF, DS_VOCAB = 4096, 11008, 102400


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip, monkeypatch):
    """``compile_tpu(fn, *(shape, dtype))`` -> HLO text of ``fn`` compiled
    for one described v5e chip, with the kernels lowered the way a TPU
    backend lowers them. The persistent compile cache stays off: an
    executable for an absent chip could not be read back."""
    monkeypatch.setattr(platform, "interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _mosaic(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL),
                                 (DS_MODEL, DS_FF), (DS_FF, DS_MODEL),
                                 (DS_MODEL, DS_VOCAB)])
@pytest.mark.parametrize("path", ["float", "requant", "float-abft",
                                  "requant-abft"])
def test_ecc_qmatmul_compiles(compile_tpu, path, k, n):
    abft = path.endswith("-abft")
    if path.startswith("float"):
        fn = lambda a, w: ecc_qmatmul(a, w, jnp.float32(0.01),
                                      with_flags=True, with_abft=abft)
        specs = [((BATCH, k), jnp.bfloat16), ((k, n), jnp.uint8)]
    else:
        fn = lambda a, w, s: ecc_qmatmul(a, w, jnp.float32(0.01), a_scale=s,
                                         with_flags=True, with_abft=abft)
        specs = [((BATCH, k), jnp.int8), ((k, n), jnp.uint8),
                 ((BATCH, 1), jnp.float32)]
    assert _mosaic(compile_tpu(fn, *specs))


@pytest.mark.parametrize("kernel", ["strip", "chunked"])
@pytest.mark.parametrize("kv,rep,seq", [(20, 1, SEQ), (8, 3, SEQ),
                                        (32, 1, SEQ), (20, 1, CELL_SEQ),
                                        (32, 1, CELL_SEQ)],
                         ids=["qwen1.5-4b", "minitron-4b-gqa",
                              "deepseek-llm-7b", "qwen1.5-4b-384",
                              "deepseek-llm-7b-384"])
def test_page_attention_compiles(compile_tpu, kernel, kv, rep, seq):
    attend = (paged_attention.fused_page_attention if kernel == "strip"
              else paged_attention.chunked_page_attention)
    fn = lambda q, ke, ksc, ve, vsc, pos: attend(q, ke, None, ksc, ve, None,
                                                 vsc, pos)
    strip = ((BATCH, seq, kv, HD), jnp.uint8)
    hlo = compile_tpu(fn, ((BATCH, kv * rep, 1, HD), jnp.bfloat16), strip,
                      ((BATCH, seq), jnp.float32), strip,
                      ((BATCH, seq), jnp.float32), ((BATCH,), jnp.int32))
    assert _mosaic(hlo)


@pytest.mark.parametrize("codec", ["decode", "encode"])
def test_block_codec_compiles(compile_tpu, codec):
    fn = ecc_decode.ecc_decode if codec == "decode" else ecc_encode.ecc_encode
    assert _mosaic(compile_tpu(fn, ((D_MODEL, D_FF // 8, 8), jnp.uint8)))
