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


@pytest.mark.parametrize("rows,length,heads,kv,d,causal_block,group", [
    (16, 4096, 32, 32, 192, 1, 2),   # joyai_llm_flash's prompt pass
    (16, 4096, 32, 2, 128, 1, 2),    # nemotron3_nano's attention block
    (128, 256, 32, 4, 128, 4, 8),    # sdar_30b_a3b's prompt pass
    (128, 128, 20, 1, 128, 1, 20),   # jamba2_3b's: 20 heads on ONE
])
def test_flash_kernel_compiles_for_a_v5e_at_192_and_128(
        rows, length, heads, kv, d, causal_block, group, one_chip,
        monkeypatch):
    """A causal ``Attention`` node at the three loads that run the flash
    kernel in a cell, through ``ops._attention`` with the kernels on, at the
    blocks ``flash._pick_blocks`` gives and the heads a step
    ``flash._heads_a_step`` gives: Mosaic takes a head as a block of ``[B,
    S, H x D]`` where it lies (grouped heads through the index map, two and
    eight query heads a step sharing a key-value head's blocks; 192-wide
    heads as rows of ``[B, H x D, S]``, the layout the compiler gives them
    behind the products and the ``Concat`` that make them), the diagonal's
    tile as two halves, and the blocks fit the scoped VMEM; the compiled
    program is ONE custom call with no transpose and no copy of ``q``,
    ``k``, ``v`` or the result at all."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import flash

    block = min(length, 1024)
    assert flash._pick_blocks(rows * heads, length, length) == (block, block)
    # a tile of 128 has no half of whole 128-lane tiles: it goes whole
    assert flash._diag_rows(block, block, 0, causal_block) == (
        block // 2 if block > 128 else block)
    assert flash._heads_a_step(heads, kv, d, 128, block, block, 2) == group
    monkeypatch.setattr(ops, "_kernels_on", lambda: True)
    notes = {}

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    mask = None if causal_block == 1 else (
        np.arange(length)[None, :]
        <= (np.arange(length)[:, None] | (causal_block - 1)))

    def attention(q, k, v):
        return ops._attention(
            [q, k, v, mask], dict(q_num_heads=heads, kv_num_heads=kv,
                                  is_causal=int(causal_block == 1)),
            {"n_outputs": 1, "notes": notes})

    def product(x, w):
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(jnp.bfloat16)

    def expanded_latent_attention(q_latent, w_uq, kv_latent, w_uk, w_uv,
                                  k_rope):
        # as 192-wide heads reach the node: heads of 128 + 64 numbers, the
        # 64 of a key shared by every head (models/joyai_flash.py)
        k = jnp.concatenate(
            [product(kv_latent, w_uk).reshape(rows, length, heads, 128),
             jnp.broadcast_to(k_rope[:, :, None, :],
                              (rows, length, heads, 64))], axis=-1)
        return attention(product(q_latent, w_uq),
                         k.reshape(rows, length, heads * d),
                         product(kv_latent, w_uv))

    if d == 192:
        lowered = jax.jit(expanded_latent_attention).lower(
            shape(rows, length, 1536), shape(1536, heads * d),
            shape(rows, length, 512), shape(512, heads * 128),
            shape(512, heads * 128), shape(rows, length, 64))
    else:
        lowered = jax.jit(attention).lower(
            shape(rows, length, heads * d), shape(rows, length, kv * d),
            shape(rows, length, kv * 128))
    text = lowered.compile().as_text()
    assert notes["attention_lowering"][("flash",)] == 1 \
        and notes["attention_flash_form"][("in_place",)] == 1
    assert text.count("tpu_custom_call") == 1
    assert not [line for line in text.splitlines()
                if " copy(" in line or " transpose(" in line]


@pytest.mark.parametrize("rows,length,whole,queries,heads,kv", [
    # a generating pass of sdar_30b_a3b.gen64
    pytest.param(128, 320, True, 4, 32, 4, id="128-320-True"),
    # a cache beyond one key block: the online softmax
    pytest.param(16, 4224, False, 4, 32, 4, id="16-4224-False"),
    # a decode pass of olmo_hybrid_7b: ONE query row a key-value head
    pytest.param(128, 384, True, 1, 30, 30, id="olmo-128-384"),
    # a decode pass of jamba2_3b: 20 query heads on one key-value head
    pytest.param(128, 256, True, 1, 20, 1, id="jamba-128-256"),
])
def test_cached_attention_compiles_for_a_v5e(rows, length, whole, queries,
                                             heads, kv, one_chip,
                                             monkeypatch):
    """A layer's cached ``Attention`` (bfloat16 heads of 128, the mask over
    key positions) through ``ops._attention`` with the kernels on, at
    ``sdar_30b_a3b``'s 4 queries on 32 / 4 heads and at one query on
    ``olmo_hybrid_7b``'s 30 / 30 and ``jamba2_3b``'s 20 / 1 (query rows a
    key-value head that are no whole sublane tile): Mosaic takes a key-value
    head as a column block of the rank-3 cache, the batched products and the
    rows and keys a step ``flash._cached_blocks`` gives, and the cache
    reaches the kernel as it lies, in its own type: the compiled program
    holds no copy of it and no float32 array over its rows and positions (a
    converted cache, or scores written to HBM)."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import flash

    n = queries * heads // kv
    assert (flash._cached_blocks(rows, n, length, 128, 2)[1]
            == length) is whole
    monkeypatch.setattr(ops, "_kernels_on", lambda: True)
    notes = {}

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def attention(*inputs):
        return ops._attention(list(inputs),
                              dict(q_num_heads=heads, kv_num_heads=kv),
                              {"n_outputs": 1, "notes": notes})

    text = jax.jit(attention).lower(
        shape((rows, queries, heads * 128)), shape((rows, length, kv * 128)),
        shape((rows, length, kv * 128)), shape((1, length), jnp.bool_)
    ).compile().as_text()
    assert notes["attention_lowering"][("cached",)] == 1 \
        and ("masked",) not in notes["attention_lowering"]
    assert text.count("tpu_custom_call") == 1
    cache = f"[{rows},{length},"
    made = [line.split(" = ", 1)[1] for line in text.splitlines()
            if " = " in line]
    assert not [r for r in made if r.startswith("bf16" + cache)
                and " copy(" in r]
    assert not [r for r in made if r.startswith("f32" + cache)]


@pytest.mark.parametrize("rows,length,entering", [
    (128, 128, False),    # the prompt pass of jamba2_3b.s128_gen128
    (16, 4096, True),     # a long prompt: eight blocks of positions a row
])
def test_selective_scan_compiles_for_a_v5e(rows, length, entering, one_chip,
                                           monkeypatch):
    """A Mamba layer's ``SelectiveScan`` of ``jamba2_3b`` (5,120 channels, 16
    states; bfloat16 ``u``, ``delta``, ``z``, float32 ``A``, ``B``, ``C``,
    ``D``, bias and state) through ``ops._selective_scan`` with the kernels
    on: Mosaic takes the blocks ``selective_scan._blocks`` gives (eight
    positions of bfloat16 at a time among them), the program is ONE custom
    call, and the state is made and read ``[rows, 16, 5120]``, channels
    minor: nothing lays it out ``[rows, 5120, 16]``."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import selective_scan as scan

    d, n = 5120, 16
    assert scan._blocks(length, d, 2) == (1024, min(length, 512))
    monkeypatch.setattr(ops, "_kernels_on", lambda: True)
    notes = {}

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def selective_scan(*inputs):
        return ops._selective_scan(list(inputs), {},
                                   {"n_outputs": 2, "notes": notes})

    wide = shape((rows, length, d), jnp.bfloat16)
    narrow = shape((rows, length, n))
    operands = [wide, wide, shape((d, n)), narrow, narrow, shape((d,)), wide,
                shape((d,))] + [shape((rows, n, d))] * entering
    text = jax.jit(selective_scan).lower(*operands).compile().as_text()
    assert notes == {"selective_scan": {("kernel",): 1}}
    assert text.count("tpu_custom_call") == 1
    assert f"[{rows},{d},{n}]" not in text
    out, state = jax.eval_shape(selective_scan, *operands)
    assert out.shape == (rows, length, d) and out.dtype == jnp.bfloat16
    assert state.shape == (rows, n, d) and state.dtype == jnp.float32


@pytest.mark.parametrize("rows,length,entering", [
    (128, 1, True),       # a decode pass of olmo_hybrid_7b.s256_gen128
    (128, 256, False),    # its prompt pass: the chunked kernel
])
def test_gated_delta_rule_compiles_for_a_v5e(rows, length, entering,
                                             one_chip, monkeypatch):
    """A delta rule layer's ``GatedDeltaRule`` of ``olmo_hybrid_7b`` (30
    heads of 96 / 192; bfloat16 ``q``, ``k``, ``v``, float32 ``g``, ``beta``
    and state) through ``ops._gated_delta_rule`` with the kernels on: a
    single position is ONE custom call that writes the state where it read
    it (aliased, no copy of it), in the unpadded ``[rows, 96, 5760]``; a
    prompt is ONE custom call too, the chunked kernel, and no float32
    intermediate of the chunked form's ``[rows, H, chunks, 64, ...]`` is
    left in the program."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.onnx import ops

    h, dk, dv = 30, 96, 192
    monkeypatch.setattr(ops, "_kernels_on", lambda: True)
    notes = {}

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def rule(*inputs):
        return ops._gated_delta_rule(list(inputs), {},
                                     {"n_outputs": 2, "notes": notes})

    qk = shape((rows, length, h, dk), jnp.bfloat16)
    operands = [qk, qk, shape((rows, length, h, dv), jnp.bfloat16),
                shape((rows, length, h)), shape((rows, length, h))] \
        + [shape((rows, dk, h * dv))] * entering
    compiled = jax.jit(rule, donate_argnums=(5,) if entering else ()).lower(
        *operands).compile()
    text = compiled.as_text()
    state = f"f32[{rows},{dk},{h * dv}]"
    if length == 1:
        assert notes == {"gated_delta": {("kernel",): 1},
                         "recurrent_state_bytes": {(): rows * dk * h * dv * 4}}
        assert text.count("tpu_custom_call") == 1
        assert compiled.memory_analysis().alias_size_in_bytes \
            == rows * dk * h * dv * 4
        assert not [line for line in text.splitlines()
                    if " copy(" in line and state in line.split(" copy(")[0]]
    else:
        assert notes == {"gated_delta": {("chunked_kernel",): 1}}
        assert text.count("tpu_custom_call") == 1
        assert f"f32[{rows},{h},{length // 64},64," not in text
    out, last = jax.eval_shape(rule, *operands)
    assert out.shape == (rows, length, h, dv) and out.dtype == jnp.bfloat16
    assert last.shape == (rows, dk, h * dv) and last.dtype == jnp.float32
