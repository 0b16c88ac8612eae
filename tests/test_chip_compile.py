"""The kernels of the save -> resume path compile for a TPU v5e.

Each test compiles for one chip of a described ``v5e:2x2`` topology, with
no chip attached, and asserts that Mosaic lowered the kernel (a
``tpu_custom_call`` in the compiled module).  What the chip's compiler
refuses, such as a block that breaks the (8, 128) tiling, fails here at no
chip time; the interpret-mode tests check the numbers.

The topology is described inside a fixture only: one process at a time may
load the TPU library, so a description made while the file is imported
would make the other test workers fail.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.kernels.ckpt_codec import dequantize, quantize, quantize_delta
from repro.kernels.ckpt_codec.blocks import BLOCK
from repro.kernels.flash_attention import attention
from repro.models.attention import sharded_flash_attention
from repro.sharding import TP_RULES, use_rules

# the widest leaf of a qwen2.5-3b layer (an MLP projection) and a ragged one
CODEC_SHAPES = [(2048, 11008), (3, 5)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


def _codes(shape, one_chip):
    nb = -(-shape[0] * shape[1] // BLOCK)
    return (_spec((nb, BLOCK), jnp.int8, one_chip),
            _spec((nb, 1), jnp.float32, one_chip))


@pytest.mark.parametrize("shape", CODEC_SHAPES)
def test_quantize_compiles(one_chip, shape):
    x = _spec(shape, jnp.float32, one_chip)
    _assert_kernel(quantize.lower(x, impl="pallas"))


@pytest.mark.parametrize("shape", CODEC_SHAPES)
def test_quantize_delta_compiles(one_chip, shape):
    x = _spec(shape, jnp.float32, one_chip)
    prev_q, _ = _codes(shape, one_chip)
    _assert_kernel(quantize_delta.lower(x, prev_q, impl="pallas"))


@pytest.mark.parametrize("shape", CODEC_SHAPES)
def test_dequantize_compiles(one_chip, shape):
    q, scale = _codes(shape, one_chip)
    _assert_kernel(dequantize.lower(q, scale, shape, impl="pallas"))


# qwen2.5-3b attention at train_4k: 16 query / 2 kv heads of 128, batch 2
Q_SHAPE, KV_SHAPE = (2, 16, 4096, 128), (2, 2, 4096, 128)


def test_flash_attention_forward_compiles(one_chip):
    q = _spec(Q_SHAPE, jnp.bfloat16, one_chip)
    kv = _spec(KV_SHAPE, jnp.bfloat16, one_chip)
    _assert_kernel(jax.jit(lambda q, k, v: attention(q, k, v, impl="pallas"))
                   .lower(q, kv, kv))


def test_flash_attention_grad_compiles(one_chip):
    q = _spec(Q_SHAPE, jnp.bfloat16, one_chip)
    kv = _spec(KV_SHAPE, jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return attention(q, k, v, impl="pallas").astype(jnp.float32).sum()

    _assert_kernel(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                   .lower(q, kv, kv))


def test_flash_attention_grad_compiles_on_a_mesh(topo):
    """Data-parallel over two chips, as ElasticTrainer runs two ranks: XLA
    does not partition a Mosaic kernel, so the model runs it per shard."""
    mesh = Mesh(np.asarray(topo.devices[:2]), ("data",))
    by_batch = NamedSharding(mesh, PartitionSpec("data"))
    q = _spec(Q_SHAPE, jnp.bfloat16, by_batch)
    kv = _spec(KV_SHAPE, jnp.bfloat16, by_batch)

    def loss(q, k, v):
        with use_rules(mesh, TP_RULES):
            o = sharded_flash_attention(q, k, v, impl="pallas")
        return o.astype(jnp.float32).sum()

    _assert_kernel(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                   .lower(q, kv, kv))


# deepseek-v2-lite latent attention at train_4k: 16 heads, q and k 192 wide
# (128 nope + 64 rope), v 128 wide, batch 4
MLA_QK, MLA_V = (4, 16, 4096, 192), (4, 16, 4096, 128)


def test_flash_attention_mla_forward_compiles(one_chip):
    qk = _spec(MLA_QK, jnp.bfloat16, one_chip)
    v = _spec(MLA_V, jnp.bfloat16, one_chip)
    _assert_kernel(jax.jit(lambda q, k, v: attention(q, k, v, impl="pallas"))
                   .lower(qk, qk, v))


def test_expert_grouped_matmul_compiles(one_chip):
    """The MoE layer's grouped matmuls at deepseek-v2-lite's widths (8 held
    experts, 2048 -> 2 x 1408 -> 2048) over a batch of 4 x 4096 tokens' 6
    slots, forward and backward: each a Mosaic ragged-dot kernel."""
    rows, d, f, e = 4 * 4096 * 6, 2048, 1408, 8
    x = _spec((rows, d), jnp.bfloat16, one_chip)
    w_gu = _spec((e, d, 2 * f), jnp.bfloat16, one_chip)
    w_down = _spec((e, f, d), jnp.bfloat16, one_chip)
    sizes = _spec((e,), jnp.int32, one_chip)

    def loss(x, w_gu, w_down, sizes):
        gu = jax.lax.ragged_dot(x, w_gu, sizes)
        h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        y = jax.lax.ragged_dot(h, w_down, sizes)
        return jnp.square(y.astype(jnp.float32)).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, w_gu, w_down, sizes).compile().as_text()
    kernels = [ln for ln in text.splitlines()
               if "tpu_custom_call" in ln and "ragged-dot" in ln]
    # gate/up and down forward, and each one's two backward products
    assert sum("ragged-dot-metadata" not in ln for ln in kernels) >= 6
