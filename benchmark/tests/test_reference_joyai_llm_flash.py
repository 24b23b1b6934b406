"""``reference/joyai_llm_flash.py`` against a second, even plainer writing of
the equations at the tiny size: a loop over positions, heads and picks in
numpy float64, one token at a time, nothing blocked; the ``float8`` control
differs; ``replay`` reads the positions the ids were chosen from."""

import numpy as np
import pytest

import run
from benchmark.reference import joyai_llm_flash as ref
from benchmark.reference.onnx_initializers import read_initializers

CONFIG = run.load_json("configs", "joyai_flash_tiny.json")
HEADS, NOPE, ROPE, V, RANK, TOP_K = 4, 16, 8, 16, 32, 2


@pytest.fixture(scope="module")
def weights():
    from synapseml_tpu.models.zoo import build_model_bytes

    return read_initializers(build_model_bytes("JoyAIFlashTiny", seed=5,
                                               mtp=1))


def _norm(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _turn(vec, position):
    """Neighbouring pairs of one vector at one position."""
    out = vec.copy()
    for j in range(ROPE // 2):
        angle = position * 32e6 ** (-2 * j / ROPE)
        a, b = vec[2 * j], vec[2 * j + 1]
        out[2 * j] = a * np.cos(angle) - b * np.sin(angle)
        out[2 * j + 1] = a * np.sin(angle) + b * np.cos(angle)
    return out


def _block_token_by_token(f, i, x, dense):
    length = len(x)
    u = _norm(x, f[f"l{i}_norm_in_w"])
    ctx = np.zeros((length, HEADS * V))
    keys, values = [], []
    for t in range(length):
        kv = u[t] @ f[f"l{i}_dkv_w"]
        c_kv = _norm(kv[:RANK], f[f"l{i}_kv_norm_w"])
        k_rope = _turn(kv[RANK:], t)
        k_nope = (c_kv @ f[f"l{i}_uk_w"]).reshape(HEADS, NOPE)
        keys.append([np.concatenate([k_nope[h], k_rope])
                     for h in range(HEADS)])
        values.append((c_kv @ f[f"l{i}_uv_w"]).reshape(HEADS, V))
        c_q = _norm(u[t] @ f[f"l{i}_dq_w"], f[f"l{i}_q_norm_w"])
        q = (c_q @ f[f"l{i}_uq_w"]).reshape(HEADS, NOPE + ROPE)
        for h in range(HEADS):
            q_h = np.concatenate([q[h, :NOPE], _turn(q[h, NOPE:], t)])
            s = np.asarray([q_h @ keys[j][h] for j in range(t + 1)]) \
                / np.sqrt(NOPE + ROPE)
            p = np.exp(s - s.max())
            p /= p.sum()
            ctx[t, h * V:(h + 1) * V] = sum(p[j] * values[j][h]
                                            for j in range(t + 1))
    x = x + ctx @ f[f"l{i}_o_w"]
    u = _norm(x, f[f"l{i}_norm_post_w"])
    if dense:
        return x + (_silu(u @ f[f"l{i}_ffn_gate_w"])
                    * (u @ f[f"l{i}_ffn_up_w"])) @ f[f"l{i}_ffn_down_w"]
    out = (_silu(u @ f[f"l{i}_shared_gate_w"])
           * (u @ f[f"l{i}_shared_up_w"])) @ f[f"l{i}_shared_down_w"]
    for t in range(length):
        scores = 1 / (1 + np.exp(-(u[t] @ f[f"l{i}_router_w"])))
        picks = np.argsort(-(scores + f[f"l{i}_router_bias"]),
                           kind="stable")[:TOP_K]
        for e in picks:
            hidden = _silu(u[t] @ f[f"l{i}_experts_gate"][e]) * (
                u[t] @ f[f"l{i}_experts_up"][e])
            out[t] += 2.5 * scores[e] / (scores[picks].sum() + 1e-20) * (
                hidden @ f[f"l{i}_experts_down"][e])
    return x + out


def _token_by_token(w, ids, next_ids=None):
    """The final norm's output ``[length, hidden]`` of one row, float64;
    with ``next_ids`` the prediction module's too."""
    f = {k: np.asarray(v).astype(np.float64) for k, v in w.items()
         if np.asarray(v).dtype.kind not in "iub"}
    x = f["tok_emb"][ids]
    for i in range(3):
        x = _block_token_by_token(f, i, x, dense=i == 0)
    final = _norm(x, f["norm_f_w"])
    if next_ids is None:
        return final
    both = np.concatenate([_norm(f["tok_emb"][next_ids], f["mtp_norm_e_w"]),
                           _norm(final, f["mtp_norm_h_w"])], -1)
    x = _block_token_by_token(f, 3, both @ f["mtp_eh_w"], dense=False)
    return final, _norm(x, f["mtp_norm_s_w"])


def test_the_forward_agrees_with_a_token_by_token_loop(weights, monkeypatch):
    import jax

    monkeypatch.setattr(ref, "QUERY_BLOCK", 5)  # three blocks, the last short
    rng = np.random.default_rng(1)
    ids, next_ids = rng.integers(0, 255, (2, 12)), rng.integers(0, 255,
                                                                (2, 12))
    reference = ref.Reference(CONFIG, weights)
    with jax.default_matmul_precision("highest"):
        final = reference.final_norm(ids)
        drafted = np.asarray(reference.draft_norm(final, next_ids))
    for r in range(2):
        want, want_drafted = _token_by_token(weights, ids[r], next_ids[r])
        assert np.linalg.norm(np.asarray(final[r]) - want) \
            / np.linalg.norm(want) < 2e-5
        assert np.linalg.norm(drafted[r] - want_drafted) \
            / np.linalg.norm(want_drafted) < 2e-5


def test_the_forward_is_causal(weights):
    reference = ref.Reference(CONFIG, weights)
    ids = np.random.default_rng(2).integers(0, 255, (1, 16))
    other = ids.copy()
    other[0, 8:] = 7
    a, b = (np.asarray(reference.final_norm(x)) for x in (ids, other))
    np.testing.assert_array_equal(a[0, :8], b[0, :8])
    assert np.abs(a[0, 8:] - b[0, 8:]).max() > 0.1


def test_the_float8_control_differs_and_bfloat16_lies_between(weights):
    reference = ref.Reference(CONFIG, weights)
    ids = np.random.default_rng(4).integers(0, 255, (4, 24))
    exact = np.asarray(reference.final_norm(ids))

    def off(precision):
        got = np.asarray(reference.final_norm(ids, precision))
        return np.linalg.norm(got - exact) / np.linalg.norm(exact)

    assert 0 < off("bfloat16") < off("float8")
    assert off("float8") > 0.05
    with pytest.raises(ValueError, match="precision"):
        reference.final_norm(ids, "float16")


def test_replay_reads_the_positions_the_ids_were_chosen_from(weights):
    reference = ref.Reference(CONFIG, weights)
    rng = np.random.default_rng(3)
    prompts, tokens = rng.integers(0, 255, (3, 8)), rng.integers(0, 255,
                                                                 (3, 8))
    out = reference.replay(prompts, tokens, block_rows=2)
    assert out["logits"].shape == out["draft_logits"].shape == (3, 8, 256)
    whole = reference.replay(prompts, tokens)
    np.testing.assert_allclose(out["logits"], whole["logits"], atol=1e-5)
    # one causal forward over the prompt and the ids but the last; the last
    # id is read by the prediction module alone
    ids = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    final = np.asarray(reference.final_norm(ids))
    head = np.asarray(weights["lm_head"]).astype(np.float32)
    np.testing.assert_allclose(out["logits"][1], final[1, 7:] @ head,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out["pooled"], final[:, 7:].mean(1),
                               atol=1e-6)
    other = tokens.copy()
    other[:, -1] = (tokens[:, -1] + 1) % 255
    again = reference.replay(prompts, other)
    np.testing.assert_array_equal(again["logits"], whole["logits"])
    assert np.abs(again["draft_logits"][:, -1]
                  - whole["draft_logits"][:, -1]).max() > 1e-3
    np.testing.assert_array_equal(again["draft_logits"][:, :-1],
                                  whole["draft_logits"][:, :-1])
