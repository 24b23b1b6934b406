"""``reference/sdar_moe.py`` against a second, even plainer form at the tiny
size: a loop over positions, heads and picks in numpy float64, one token at a
time; and its rebuilding of the ids a pass saw."""

import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref
from benchmark.reference.onnx_initializers import read_initializers

CONFIG = {"num_hidden_layers": 2, "hidden_size": 64,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "num_experts": 8, "num_experts_per_tok": 2, "rms_norm_eps": 1e-6,
          "rope_theta": 1e6, "mask_token_id": 255,
          "builder_kwargs": {"block": 4, "passes": 2}}


@pytest.fixture(scope="module")
def weights():
    from synapseml_tpu.models.zoo import build_model_bytes

    return read_initializers(build_model_bytes("SDARMoETiny", seed=5))


def _norm(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _token_by_token(w, ids, block=4):
    """The final norm's output ``[length, hidden]`` of one row, float64."""
    f = {k: np.asarray(v).astype(np.float64) for k, v in w.items()
         if np.asarray(v).dtype.kind not in "iub"}
    x = f["tok_emb"][ids]
    length, d, heads, kv = len(ids), 16, 4, 2
    inv = 1e6 ** (-np.arange(0, d, 2) / d)

    def turn(vec, position):  # one head's vector at one position
        a, b = vec[:d // 2], vec[d // 2:]
        c, s = np.cos(position * inv), np.sin(position * inv)
        return np.concatenate([a * c - b * s, b * c + a * s])

    for i in range(2):
        u = _norm(x, f[f"l{i}_norm_in_w"])
        q = (u @ f[f"l{i}_q_w"]).reshape(length, heads, d)
        k = (u @ f[f"l{i}_k_w"]).reshape(length, kv, d)
        v = (u @ f[f"l{i}_v_w"]).reshape(length, kv, d)
        q = np.stack([[turn(_norm(q[t, h], f[f"l{i}_q_norm_w"]), t)
                       for h in range(heads)] for t in range(length)])
        k = np.stack([[turn(_norm(k[t, h], f[f"l{i}_k_norm_w"]), t)
                       for h in range(kv)] for t in range(length)])
        ctx = np.zeros((length, heads, d))
        for t in range(length):
            seen = (t // block + 1) * block  # through the end of t's block
            for h in range(heads):
                s = q[t, h] @ k[:seen, h // 2].T / np.sqrt(d)
                p = np.exp(s - s.max())
                ctx[t, h] = (p / p.sum()) @ v[:seen, h // 2]
        x = x + ctx.reshape(length, -1) @ f[f"l{i}_o_w"]
        u = _norm(x, f[f"l{i}_norm_post_w"])
        out = np.zeros_like(x)
        for t in range(length):
            r = u[t] @ f[f"l{i}_router_w"]
            p = np.exp(r - r.max())
            p /= p.sum()
            picks = np.argsort(-p, kind="stable")[:2]
            for e in picks:
                gate = u[t] @ f[f"l{i}_experts_gate"][e]
                hidden = gate / (1 + np.exp(-gate)) * (
                    u[t] @ f[f"l{i}_experts_up"][e])
                out[t] += p[e] / p[picks].sum() * (
                    hidden @ f[f"l{i}_experts_down"][e])
        x = x + out
    return _norm(x, f["norm_f_w"])


def test_the_forward_agrees_with_a_token_by_token_loop(weights):
    import jax

    ids = np.random.default_rng(1).integers(0, 255, (2, 12))
    reference = ref.Reference(CONFIG, weights)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.final_norm(ids))
    for r in range(2):
        want = _token_by_token(weights, ids[r])
        assert np.linalg.norm(got[r] - want) / np.linalg.norm(want) < 2e-5


def test_a_later_block_changes_nothing_an_earlier_position_reads(weights):
    reference = ref.Reference(CONFIG, weights)
    ids = np.random.default_rng(2).integers(0, 255, (1, 16))
    other = ids.copy()
    other[0, 8:] = 7
    a, b = (np.asarray(reference.final_norm(x)) for x in (ids, other))
    np.testing.assert_array_equal(a[0, :8], b[0, :8])
    assert np.abs(a[0, 8:] - b[0, 8:]).max() > 0.1
    # inside a block both directions: position 4 reads position 7
    other = ids.copy()
    other[0, 7] = (ids[0, 7] + 1) % 255
    b = np.asarray(reference.final_norm(other))
    assert np.abs(a[0, 4] - b[0, 4]).max() > 1e-3
    np.testing.assert_array_equal(a[0, :4], b[0, :4])


def test_ids_at_rebuilds_what_a_pass_saw():
    prompt = np.arange(8)
    tokens = np.asarray([10, 11, 12, 13, 20, 21, 22, 23])
    fixed_at = np.asarray([0, 1, 1, 0, 1, 0, 0, 1])
    m = 255
    assert ref.ids_at(prompt, tokens, fixed_at, 0, 0, 4, m).tolist() == \
        list(range(8)) + [m] * 8
    assert ref.ids_at(prompt, tokens, fixed_at, 0, 1, 4, m).tolist() == \
        list(range(8)) + [10, m, m, 13] + [m] * 4
    assert ref.ids_at(prompt, tokens, fixed_at, 1, 1, 4, m).tolist() == \
        list(range(8)) + [10, 11, 12, 13, m, 21, 22, m]


def test_replay_reads_each_state_at_its_block_and_pools_the_final_ids(weights):
    reference = ref.Reference(CONFIG, weights)
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, 255, (3, 8))
    tokens = rng.integers(0, 255, (3, 8))
    fixed_at = np.tile([0, 1, 0, 1], (3, 2))
    blocks = [[0, 1], [1, 0], [0, 1]]
    out = reference.replay(prompts, tokens, fixed_at, blocks, block_rows=2)
    assert out["logits"].shape == (3, 2, 2, 4, 256)
    whole = reference.replay(prompts, tokens, fixed_at, blocks)
    np.testing.assert_allclose(out["logits"], whole["logits"], atol=1e-5)
    np.testing.assert_allclose(out["pooled"], reference.pooled(prompts,
                                                               tokens),
                               atol=1e-6)
    # row 1's first sampled block is block 1, at its second pass
    ids = ref.ids_at(prompts[1], tokens[1], fixed_at[1], 1, 1, 4, 255)
    final = reference.final_norm(ids[None])
    want = np.asarray(final[0, 12:16]) @ np.asarray(
        weights["lm_head"]).astype(np.float32)
    np.testing.assert_allclose(out["logits"][1, 0, 1], want, rtol=1e-4,
                               atol=1e-4)
