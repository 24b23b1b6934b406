"""Sequence-parallel attention tests on the virtual 8-device mesh.

Net-new capability (SURVEY.md §5): parity of ring / Ulysses attention
against dense single-device attention, causal variants, and dtype behavior.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from synapseml_tpu.parallel import (
    ring_attention,
    sequence_sharded_attention,
    ulysses_attention,
)


def _dense_reference(q, k, v, causal=False):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = np.einsum("bqhd,bkhd->bqhk", q.astype(np.float64),
                  k.astype(np.float64)) * scale
    if causal:
        S = s.shape[1]
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask[None, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bqhk,bkhd->bqhd", p, v.astype(np.float64))


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:8])
    if devs.size < 8:
        pytest.skip("needs 8 devices (conftest provides the virtual mesh)")
    return Mesh(devs, ("seq",))


def _qkv(seed=0, b=2, s=64, h=8, d=16):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.normal(size=(b, s, h, d)).astype(np.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sequence_parallel_matches_dense(mesh, strategy, causal):
    q, k, v = _qkv()
    out = np.asarray(sequence_sharded_attention(
        q, k, v, mesh, strategy=strategy, causal=causal))
    ref = _dense_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ring_attention_bf16_inputs(mesh):
    q, k, v = _qkv(seed=1)
    out = np.asarray(sequence_sharded_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), mesh, strategy="ring").astype(
            jnp.float32))
    ref = _dense_reference(q, k, v)
    # bf16 inputs, f32 accumulation: loose tolerance
    np.testing.assert_allclose(out, ref, rtol=0.05, atol=0.05)


def test_sequence_length_must_divide(mesh):
    q, k, v = _qkv(s=63)
    with pytest.raises(ValueError, match="divisible"):
        sequence_sharded_attention(q, k, v, mesh)


def test_ulysses_non_divisible_heads(mesh):
    """Heads that don't divide the axis are zero-padded through the
    all-to-all and sliced off — real checkpoints hit this immediately."""
    q, k, v = _qkv(h=6)  # 6 heads over an 8-shard axis
    out = np.asarray(sequence_sharded_attention(
        q, k, v, mesh, strategy="ulysses"))
    ref = _dense_reference(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_gqa_grouped_kv_heads(mesh, strategy):
    """GQA: 8 query heads over 2 K/V heads — grouped blocks ride the
    collectives and expand locally (Llama/Mistral-style checkpoints)."""
    rng = np.random.default_rng(7)
    q = rng.normal(size=(2, 64, 8, 16)).astype(np.float32)
    k = rng.normal(size=(2, 64, 2, 16)).astype(np.float32)
    v = rng.normal(size=(2, 64, 2, 16)).astype(np.float32)
    out = np.asarray(sequence_sharded_attention(
        q, k, v, mesh, strategy=strategy, causal=True))
    kx = np.repeat(k, 4, axis=2)
    vx = np.repeat(v, 4, axis=2)
    ref = _dense_reference(q, kx, vx, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_gqa_bad_group_raises(mesh):
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="multiple of kv heads"):
        sequence_sharded_attention(q, k[:, :, :3], v[:, :, :3], mesh)


def test_ulysses_flash_block_override(mesh):
    """block_q/block_k plumb through to the flash kernel (gathered lengths
    rarely divide the 512 default)."""
    q, k, v = _qkv(s=96)  # gathered S=96: 512 default would fail
    out = np.asarray(sequence_sharded_attention(
        q, k, v, mesh, strategy="ulysses", local="flash", interpret=True,
        block_q=32, block_k=32))
    ref = _dense_reference(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_ulysses_flash_auto_block(mesh):
    """With no override the flash block auto-picks a divisor of S; when the
    divisor falls below the (8, 128) Mosaic tile minimum the path falls back
    to dense local attention instead of invoking a sub-tile kernel (here
    S=96 -> auto block 32 -> dense fallback, still exact)."""
    q, k, v = _qkv(s=96)
    out = np.asarray(sequence_sharded_attention(
        q, k, v, mesh, strategy="ulysses", local="flash", interpret=True))
    ref = _dense_reference(q, k, v)
    # dense-fallback results are f32-exact, tighter than the flash tolerance
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ulysses_flash_odd_length_falls_back_to_dense(mesh):
    """A gathered length with only tiny power-of-2 factors (s_local=12 ->
    S=96... use 8*13=104 -> auto block 8) must not reach the flash kernel
    at sub-tile block sizes — it runs dense (and says so, see
    test_dense_substitute_leaves_a_visible_trace) and stays correct."""
    q, k, v = _qkv(s=104)  # S=104 = 8 * 13: auto block degrades to 8
    out = np.asarray(sequence_sharded_attention(
        q, k, v, mesh, strategy="ulysses", local="flash", causal=True,
        interpret=True))
    ref = _dense_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_unknown_strategy(mesh):
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="strategy"):
        sequence_sharded_attention(q, k, v, mesh, strategy="nope")


def test_ring_peak_memory_is_blockwise(mesh):
    """The ring never materializes the (S, S) score matrix — the jaxpr of the
    shard-mapped fn must not contain a full-sequence-squared intermediate."""
    from functools import partial
    from jax.sharding import PartitionSpec as P

    from synapseml_tpu.runtime.topology import shard_map_compat

    b, s, h, d = 1, 512, 4, 8
    q, k, v = _qkv(seed=2, b=b, s=s, h=h, d=d)
    spec = P(None, "seq", None, None)
    fn = shard_map_compat(partial(ring_attention, axis_name="seq"),
                          mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec, check=False)
    jaxpr = jax.make_jaxpr(fn)(q, k, v)
    s_local = s // 8
    # largest score-shaped buffer is (b, s_local, h, s_local), never (.., s)
    text = str(jaxpr)
    assert f"{s_local},{h},{s}" not in text.replace(" ", "")


# -- pallas flash attention (interpret mode on the CPU test mesh) -------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(256, 256), (128, 512)])
def test_flash_attention_matches_dense(causal, sq, sk):
    from synapseml_tpu.parallel import dense_attention, flash_attention

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, sq, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, sk, 4, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, sk, 4, 64)), jnp.float32)
    ref = dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,kv", [(256, 256, 32), (256, 256, 2),
                                      (128, 384, 2)])
def test_flash_attention_in_place_at_heads_of_128(causal, sq, sk, kv):
    """A head size of 128: the kernel reads ``(B, S, H x D)`` where it lies
    (grouped 32 / 2 as ``nemotron3_nano`` has it: the block index map
    divides), fewer queries than keys, with and without the mask."""
    from synapseml_tpu.parallel import dense_attention, flash_attention
    from synapseml_tpu.parallel.flash import reads_in_place

    assert reads_in_place(128, 128, 4) and not reads_in_place(64, 64, 4)
    rng = np.random.default_rng(37)
    q = jnp.asarray(rng.normal(size=(1, sq, 32, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, sk, kv, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, sk, kv, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = dense_attention(q, jnp.repeat(k, 32 // kv, axis=2),
                              jnp.repeat(v, 32 // kv, axis=2), causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    from synapseml_tpu.parallel import dense_attention, flash_attention

    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.bfloat16)
    ref = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    # bf16 dots: ~1e-2 absolute agreement is the expected precision
    assert float(jnp.abs(out.astype(jnp.float32) - ref).max()) < 5e-2


def test_flash_attention_shape_errors():
    from synapseml_tpu.parallel import flash_attention

    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    k = jnp.zeros((1, 200, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, jnp.zeros((1, 256, 2, 64)), jnp.zeros((1, 256, 2, 64)),
                        block_q=96, interpret=True)
    with pytest.raises(ValueError, match="mismatch"):
        flash_attention(q, k, jnp.zeros((1, 200, 4, 64), jnp.float32),
                        interpret=True)
    with pytest.raises(ValueError, match="s_q <= s_k"):
        flash_attention(q, jnp.zeros((1, 128, 2, 64), jnp.float32),
                        jnp.zeros((1, 128, 2, 64), jnp.float32),
                        causal=True, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_local_matches_dense(mesh, causal):
    """Ulysses with the Pallas flash kernel as its local attention (through
    the interpreter on the CPU mesh) must match dense sequence-sharded
    attention."""
    from synapseml_tpu.parallel import (dense_attention,
                                        sequence_sharded_attention)

    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(2, 512, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 512, 8, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 512, 8, 64)), jnp.float32)
    ref = dense_attention(q, k, v, causal=causal)
    out = sequence_sharded_attention(q, k, v, mesh, strategy="ulysses",
                                     causal=causal, local="flash",
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_native_gqa_matches_expanded(mesh):
    """The flash kernel resolves GQA in-kernel (grouped K/V never expand in
    HBM): grouped inputs must match the pre-expanded computation exactly."""
    from synapseml_tpu.parallel.flash import flash_attention

    rng = np.random.default_rng(17)
    B, S, H, Hkv, D = 2, 256, 8, 2, 16
    q = rng.normal(size=(B, S, H, D)).astype(np.float32)
    k = rng.normal(size=(B, S, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(B, S, Hkv, D)).astype(np.float32)
    grouped = np.asarray(flash_attention(q, k, v, causal=True, block_q=128,
                                         block_k=128, interpret=True))
    kx, vx = np.repeat(k, 4, axis=2), np.repeat(v, 4, axis=2)
    expanded = np.asarray(flash_attention(q, kx, vx, causal=True, block_q=128,
                                          block_k=128, interpret=True))
    np.testing.assert_allclose(grouped, expanded, rtol=1e-6, atol=1e-6)
    ref = _dense_reference(q, kx, vx, causal=True)
    np.testing.assert_allclose(grouped, ref, rtol=2e-3, atol=2e-3)


def test_flash_auto_blocks():
    """With no explicit blocks the kernel auto-picks divisors from the r5
    sweep table; non-power-of-2-friendly lengths clamp to divisors."""
    from synapseml_tpu.parallel.flash import _pick_blocks, flash_attention

    assert _pick_blocks(8, 32768, 32768) == (2048, 1024)
    assert _pick_blocks(64, 8192, 8192) == (1024, 1024)
    assert _pick_blocks(8, 8192, 8192) == (1024, 1024)
    # 3*512: largest pow2 divisor <= target
    assert _pick_blocks(8, 1536, 1536) == (512, 512)
    rng = np.random.default_rng(18)
    q = rng.normal(size=(1, 1536, 4, 16)).astype(np.float32)
    out = np.asarray(flash_attention(q, q, q, causal=True, interpret=True))
    ref = _dense_reference(q, q, q, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_ulysses_flash_gqa_grouped_in_kernel(mesh):
    """Ulysses + local flash passes GROUPED K/V straight to the kernel."""
    rng = np.random.default_rng(19)
    q = rng.normal(size=(2, 128, 8, 16)).astype(np.float32)
    k = rng.normal(size=(2, 128, 2, 16)).astype(np.float32)
    v = rng.normal(size=(2, 128, 2, 16)).astype(np.float32)
    out = np.asarray(sequence_sharded_attention(
        q, k, v, mesh, strategy="ulysses", local="flash", causal=True,
        interpret=True, block_q=128, block_k=128))
    kx, vx = np.repeat(k, 4, axis=2), np.repeat(v, 4, axis=2)
    ref = _dense_reference(q, kx, vx, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_subtile_auto_falls_back_to_dense():
    """ADVICE r5 hazard: sequence lengths with small power-of-2 factors
    auto-pick sub-(8,128) blocks; instead of an opaque Mosaic failure the
    compiled path must fall back to dense attention (exact match)."""
    from synapseml_tpu.parallel import flash_attention
    from synapseml_tpu.parallel.flash import dense_attention

    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 300, 2, 64)).astype(np.float32))
    out = flash_attention(q, q, q, causal=True)  # S=300 -> block 4: no tile
    ref = dense_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # GQA fallback expands K/V before dense
    q4 = jnp.asarray(rng.normal(size=(1, 300, 4, 64)).astype(np.float32))
    outg = flash_attention(q4, q, q, causal=True)
    refg = dense_attention(q4, jnp.repeat(q, 2, axis=2),
                           jnp.repeat(q, 2, axis=2), causal=True)
    np.testing.assert_allclose(np.asarray(outg), np.asarray(refg), atol=1e-5)


def test_dense_substitute_leaves_a_visible_trace(mesh, caplog):
    """A caller that asked for the Pallas kernel and got dense attention
    can see it: one warning on the ``synapseml_tpu.flash`` logger and one
    ``flash/dense_substitute`` telemetry event per caller and shape — from
    ``flash_attention`` itself and from the Ulysses wrapper — and nothing
    more when the same shape comes again."""
    import logging

    from synapseml_tpu.core import telemetry
    from synapseml_tpu.parallel import flash_attention
    from synapseml_tpu.parallel.flash import note_dense_substitute

    def events():
        return [(e["uid"], tuple(e["shape"]), tuple(e["blocks"]))
                for e in telemetry.recent_events()
                if e.get("className") == "flash"
                and e.get("method") == "dense_substitute"]

    note_dense_substitute.cache_clear()
    telemetry.clear_events()
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(1, 300, 2, 64)).astype(np.float32))
    with caplog.at_level(logging.WARNING, logger="synapseml_tpu.flash"):
        flash_attention(q, q, q, causal=True)  # S=300 -> block 4: no tile
        assert events() == [("flash_attention", (1, 300, 300, 2, 2, 64),
                             (4, 4))]
        flash_attention(q, q, q, causal=True)
        assert len(events()) == 1  # once per shape
        # the Ulysses wrapper decides before the kernel is ever called, so
        # it says so itself (gathered S=88 auto-blocks to 8; a length no
        # other test traces — the event fires when the program is traced)
        qs, ks, vs = _qkv(s=88)
        sequence_sharded_attention(qs, ks, vs, mesh, strategy="ulysses",
                                   local="flash", causal=True,
                                   interpret=True)
        assert [e[0] for e in events()] == ["flash_attention",
                                            "ulysses_attention"]
        assert events()[1][2] == (8, 8)
    said = [r.getMessage() for r in caplog.records
            if r.name == "synapseml_tpu.flash"]
    assert len(said) == 2
    assert all("dense attention, not the Pallas kernel" in m for m in said)
    # a shape that does reach the kernel says nothing
    q2 = jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
    flash_attention(q2, q2, q2, causal=True, interpret=True)
    assert len(events()) == 2


def test_flash_explicit_subtile_blocks_raise_but_clamped_ok():
    """Blocks the USER requested below Mosaic's (8, 128) minimum raise a
    clear error (unless interpret=True); a LEGAL explicit block that a
    short sequence clamps below the minimum takes the dense fallback —
    'pass bigger blocks' would be unsatisfiable advice at s_k=64."""
    from synapseml_tpu.parallel import flash_attention
    from synapseml_tpu.parallel.flash import dense_attention

    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
    with pytest.raises(ValueError, match="Mosaic"):
        flash_attention(q, q, q, block_k=64)
    with pytest.raises(ValueError, match="Mosaic"):
        flash_attention(q, q, q, block_q=4)
    # interpret=True keeps small explicit blocks (CPU parity tests)
    out = flash_attention(q, q, q, block_q=32, block_k=32, interpret=True)
    assert out.shape == q.shape
    # requested 1024 >= minimum, clamped by s=64: dense fallback, no raise
    qs = jnp.asarray(rng.normal(size=(1, 64, 2, 64)).astype(np.float32))
    out = flash_attention(qs, qs, qs, block_q=1024, block_k=1024)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(qs, qs, qs)),
                               atol=1e-5)


def test_flash_untileable_huge_sequence_raises_clearly():
    """A long ODD sequence can neither tile nor afford the dense score
    tensor: the error must name the fix (pad to a multiple of 128)."""
    from synapseml_tpu.parallel import flash_attention

    q = jnp.zeros((1, 100001, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="[Pp]ad the sequences"):
        flash_attention(q, q, q)
