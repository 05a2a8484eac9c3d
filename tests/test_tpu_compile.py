"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU but checks neither tile
alignment nor VMEM limits; the TPU compiler, which is installed here, does.
Each test lowers one kernel at the widths the chip serves (qwen3-1.7b's GQA
attention: 16 heads, 8 KV heads, head_dim 128; DeepSeek-V2's MLA: latent
512, rope 64; page 16; bf16) against a described ``v5e:2x2`` topology and
asserts that the compiled program holds the Mosaic kernel. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chunk_attention as CA
from repro.kernels import decode_attention as DA
from repro.kernels import flash_attention as FA
from repro.kernels import page_copy as PC

B, H, HKV, DH = 4, 16, 8, 128      # qwen3-1.7b attention, batch of 4 slots
LAT, ROPE = 512, 64                # DeepSeek-V2 MLA latent / rope widths
P_SZ, N_PAGES, N_PP = 16, 257, 64  # page 16, 1k tokens per slot
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo, name):
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel compiled"
    assert name in hlo, f"{name}: kernel name missing from compiled HLO"


def test_decode_attention_compiles(one_chip):
    s = 2048
    hlo = _compile(DA.decode_attention, one_chip,
                   ((B, H, DH), BF16), ((B, s, HKV, DH), BF16),
                   ((B, s, HKV, DH), BF16), ((B, s), I32), ((B,), I32))
    _assert_kernel(hlo, "decode_attention")


def test_paged_decode_attention_compiles(one_chip):
    hlo = _compile(DA.paged_decode_attention, one_chip,
                   ((B, H, DH), BF16), ((N_PAGES, P_SZ, HKV, DH), BF16),
                   ((N_PAGES, P_SZ, HKV, DH), BF16), ((N_PAGES, P_SZ), I32),
                   ((B, N_PP), I32), ((B,), I32))
    _assert_kernel(hlo, "paged_decode_attention")


def test_paged_mla_decode_attention_compiles(one_chip):
    fn = functools.partial(DA.paged_mla_decode_attention, scale=0.07)
    hlo = _compile(fn, one_chip,
                   ((B, H, LAT), BF16), ((B, H, ROPE), BF16),
                   ((N_PAGES, P_SZ, LAT), BF16), ((N_PAGES, P_SZ, ROPE), BF16),
                   ((N_PAGES, P_SZ), I32), ((B, N_PP), I32), ((B,), I32))
    _assert_kernel(hlo, "paged_mla_decode_attention")


@pytest.mark.parametrize("c,sk", [(256, 1280), (64, 96)])
def test_chunk_attention_compiles(one_chip, c, sk):
    hlo = _compile(CA.chunk_attention, one_chip,
                   ((1, c, H, DH), BF16), ((1, sk, HKV, DH), BF16),
                   ((1, sk, HKV, DH), BF16), ((1, c), I32), ((1, sk), I32))
    _assert_kernel(hlo, "chunk_attention")


def test_mla_chunk_attention_compiles(one_chip):
    c, sk = 256, 1280
    fn = functools.partial(CA.mla_chunk_attention, scale=0.07)
    hlo = _compile(fn, one_chip,
                   ((1, c, H, LAT), BF16), ((1, c, H, ROPE), BF16),
                   ((1, sk, LAT), BF16), ((1, sk, ROPE), BF16),
                   ((1, c), I32), ((1, sk), I32))
    _assert_kernel(hlo, "mla_chunk_attention")


@pytest.mark.parametrize("row,dt", [
    ((P_SZ, HKV, DH), BF16),     # GQA K/V pools
    ((P_SZ, LAT), BF16),         # MLA latent pool
    ((P_SZ, ROPE), BF16),        # MLA rope pool: minor dim under 128 lanes
    ((P_SZ,), I32),              # position lanes
])
def test_copy_pages_compiles(one_chip, row, dt):
    hlo = _compile(PC.copy_pages, one_chip,
                   ((N_PAGES,) + row, dt), ((8,), I32), ((8,), I32))
    _assert_kernel(hlo, "copy_pages")


def test_flash_attention_compiles(one_chip):
    s = 512
    hlo = _compile(FA.flash_attention, one_chip,
                   ((1, s, H, DH), BF16), ((1, s, H, DH), BF16),
                   ((1, s, H, DH), BF16))
    _assert_kernel(hlo, "flash_attention")
