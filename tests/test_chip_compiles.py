"""Kernels of the executor's main path compiled at real widths for a v5e that
is described, not attached: what the Pallas interpreter cannot refuse (a tile
that does not fit the scoped VMEM, a slice off the tiling) the chip's
compiler does, here, at no chip time. Nothing runs: no result, no time.

The topology is described inside a fixture and only there: the TPU's library
belongs to one process at a time, so nothing here may touch it while a module
is imported, and these tests stay in this one file."""

import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tokens,tile,chunk", [
    (512, 128, 4096),      # a generating pass of sdar_30b_a3b.gen64
    (2048, 256, 12288),    # 128 pairs an expert: the middle tile, two chunks
])
def test_expert_ffn_compiles_for_a_v5e_at_each_row_tile(
        tokens, tile, chunk, one_chip, monkeypatch):
    """One layer's ``ExpertFFN`` of ``sdar_30b_a3b`` (top-8 of 128 experts,
    h 2,048, f 768, ``swiglu``) with the megablox kernel on, at the two row
    tiles ``ops._expert_tiling`` gives under 512 expected pairs an expert:
    their whole-``k`` weight tiles fit the chip's scoped VMEM. (The 512-row
    tile's program is every earlier one's and takes 20 s to compile.)"""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.onnx import ops

    h, f, experts, k = 2048, 768, 128, 8
    assert ops._expert_tiling(tokens * k, experts) == (tile, chunk)
    monkeypatch.setattr(ops, "_kernels_on", lambda: True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def expert_ffn(*inputs):
        return ops._expert_ffn(
            list(inputs), dict(first_expert=0, num_experts=experts,
                               activation="swiglu"), {"n_outputs": 1})

    compiled = jax.jit(expert_ffn).lower(
        shape((1, tokens, h), jnp.bfloat16), shape((1, tokens, k), jnp.int32),
        shape((1, tokens, k), jnp.float32),
        shape((experts, h, f), jnp.bfloat16),
        shape((experts, f, h), jnp.bfloat16),
        shape((experts, h, f), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_flash_kernel_compiles_for_a_v5e_at_192_and_128(one_chip):
    """The prompt pass's attention of ``joyai_llm_flash``: queries and keys
    of 192 = 128 + 64 numbers a head as they lie (a block's last dimension
    is the array's own, not a multiple of 128), values and result of 128,
    ``[16, 4096, 32]`` causal, at the blocks ``flash._pick_blocks`` gives:
    Mosaic takes the contraction and the blocks fit the scoped VMEM."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    def shape(width):
        return jax.ShapeDtypeStruct((16, 4096, 32, width), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True)).lower(shape(192), shape(192),
                                     shape(128)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows,length,whole", [
    (128, 320, True),     # a generating pass of sdar_30b_a3b.gen64
    (16, 4224, False),    # a cache beyond one key block: the online softmax
])
def test_cached_attention_compiles_for_a_v5e(rows, length, whole, one_chip,
                                             monkeypatch):
    """A layer's cached ``Attention`` of ``sdar_30b_a3b`` (4 queries a row,
    32 / 4 heads of 128, bfloat16, the mask over key positions) through
    ``ops._attention`` with the kernels on: Mosaic takes a key-value head as
    a column block of the rank-3 cache, the batched products and the rows
    and keys a step ``flash._cached_blocks`` gives, and the cache reaches
    the kernel as it lies (no copy of it in the compiled program)."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import flash

    assert (flash._cached_blocks(rows, 32, length, 128, 2)[1]
            == length) is whole
    monkeypatch.setattr(ops, "_kernels_on", lambda: True)
    notes = {}

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def attention(*inputs):
        return ops._attention(list(inputs),
                              dict(q_num_heads=32, kv_num_heads=4),
                              {"n_outputs": 1, "notes": notes})

    text = jax.jit(attention).lower(
        shape((rows, 4, 4096)), shape((rows, length, 512)),
        shape((rows, length, 512)), shape((1, length), jnp.bool_)
    ).compile().as_text()
    assert notes["attention_cached"] == 1 and "attention_masked" not in notes
    assert text.count("tpu_custom_call") == 1
    cache = f"bf16[{rows},{length},"
    assert not [line for line in text.splitlines()
                if " copy(" in line and cache in line.split(" copy(")[0]]
