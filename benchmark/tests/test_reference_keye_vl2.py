"""``reference/keye_vl2.py`` against a second, even plainer writing of the
equations at the tiny size: numpy float64, one token at a time, the set a
stable sort; the forward is causal; the ``float8`` control differs;
``replay`` reads the positions the ids were chosen from through the UNTIED
head; and the rehearsal cell, the whole command on the CPU, is correct while
its control is not."""

import numpy as np
import pytest

import run
from benchmark.reference import keye_vl2 as ref
from benchmark.reference.onnx_initializers import read_initializers

CELL = "keye_vl2_tiny.rehearsal"
CONFIG = run.load_json("configs", "keye_vl2_tiny.json")
HEADS, KV, D = 4, 2, 16
INDEX_HEADS, INDEX_DIM, TOPK = 4, 8, 8


@pytest.fixture(scope="module")
def weights():
    from synapseml_tpu.models.zoo import build_model_bytes

    return read_initializers(build_model_bytes("KeyeVL2Tiny", seed=5))


def _norm(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _turn(x, t, dim):
    """``x [heads, dim]`` turned at position ``t``, rotate-half, theta
    1e7."""
    angle = t * 1e7 ** (-np.arange(0, dim, 2) / dim)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return np.concatenate([x1 * np.cos(angle) - x2 * np.sin(angle),
                           x2 * np.cos(angle) + x1 * np.sin(angle)], -1)


def _token_by_token(f, ids):
    """One row's final norm output, position by position: each layer keeps
    its keys, values and index keys; a query's set is a stable sort of its
    scores (largest first, then the lower position)."""
    f = {k: np.asarray(v, np.float64) for k, v in f.items()}
    layers = CONFIG["num_hidden_layers"]
    kept = {i: ([], [], []) for i in range(layers)}
    out = []
    for t, token in enumerate(ids):
        x = f["tok_emb"][token]
        for i in range(layers):
            p = f"l{i}_"
            u = _norm(x, f[p + "norm_in_w"])
            q = _turn(_norm((u @ f[p + "q_w"]).reshape(HEADS, D),
                            f[p + "q_norm_w"]), t, D)
            keys, values, index_keys = kept[i]
            keys.append(_turn(_norm((u @ f[p + "k_w"]).reshape(KV, D),
                                    f[p + "k_norm_w"]), t, D))
            values.append((u @ f[p + "v_w"]).reshape(KV, D))
            ki = u @ f[p + "index_k_w"]
            ki = (ki - ki.mean()) / np.sqrt(ki.var() + 1e-6) \
                * f[p + "index_k_norm_w"] + f[p + "index_k_norm_b"]
            index_keys.append(_turn(ki[None], t, INDEX_DIM)[0])
            qi = _turn((u @ f[p + "index_q_w"]).reshape(INDEX_HEADS, -1), t,
                       INDEX_DIM)
            w = u @ f[p + "index_w_w"] / np.sqrt(INDEX_HEADS * INDEX_DIM)
            scores = np.maximum(qi @ np.stack(index_keys).T, 0).T @ w
            chosen = sorted(range(t + 1), key=lambda s: (-scores[s], s))
            chosen = chosen[:TOPK]
            k, v = np.stack(keys)[chosen], np.stack(values)[chosen]
            ctx = []
            for h in range(HEADS):
                g = h // (HEADS // KV)
                a = k[:, g] @ q[h] / np.sqrt(D)
                a = np.exp(a - a.max())
                ctx.append((a / a.sum()) @ v[:, g])
            x = x + np.concatenate(ctx) @ f[p + "o_w"]
            y = _norm(x, f[p + "norm_post_w"])
            logits = y @ f[p + "router_w"]
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            top = np.argsort(-probs, kind="stable")[:2]
            for e in top:
                hidden = y @ f[p + "experts_gate"][e]
                hidden = hidden / (1 + np.exp(-hidden)) \
                    * (y @ f[p + "experts_up"][e])
                x = x + probs[e] / probs[top].sum() * (
                    hidden @ f[p + "experts_down"][e])
        out.append(_norm(x, f["norm_f_w"]))
    return np.stack(out)


def test_the_forward_agrees_with_a_token_by_token_loop(weights):
    import jax

    ids = np.random.default_rng(1).integers(0, 255, (2, 20))
    reference = ref.Reference(CONFIG, weights)
    with jax.default_matmul_precision("highest"):
        final = np.asarray(reference.final_norm(ids))
    for r in range(2):
        want = _token_by_token(weights, ids[r])
        assert np.linalg.norm(final[r] - want) / np.linalg.norm(want) < 2e-5


def test_the_file_holds_the_index_keys_layernorm_and_an_untied_head(weights):
    assert weights["l0_index_k_norm_b"].shape == (INDEX_DIM,)
    assert not np.asarray(weights["l0_index_k_norm_b"], np.float32).any()
    assert weights["l0_index_w_w"].shape == (64, INDEX_HEADS)
    assert weights["lm_head"].shape == (64, 256)
    assert not np.array_equal(np.asarray(weights["lm_head"]).T,
                              np.asarray(weights["tok_emb"]))


def test_the_forward_is_causal(weights):
    reference = ref.Reference(CONFIG, weights)
    ids = np.random.default_rng(2).integers(0, 255, (1, 20))
    other = ids.copy()
    other[0, 12:] = 7
    a, b = (np.asarray(reference.final_norm(x)) for x in (ids, other))
    np.testing.assert_array_equal(a[0, :12], b[0, :12])
    assert np.abs(a[0, 12:] - b[0, 12:]).max() > 0.1


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
    assert out["logits"].shape == (3, 8, 256)
    whole = reference.replay(prompts, tokens)
    np.testing.assert_allclose(out["logits"], whole["logits"], atol=1e-5)
    ids = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    final = np.asarray(reference.final_norm(ids))
    head = np.asarray(weights["lm_head"]).astype(np.float32)
    np.testing.assert_allclose(out["logits"][1], final[1, 7:] @ head,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out["pooled"], final[:, 7:].mean(1),
                               atol=1e-6)
    # the first topk (8) positions, where a query keeps every key it sees
    np.testing.assert_allclose(out["prompt_pooled"], final[:, :8].mean(1),
                               atol=1e-6)


def test_replay_runs_the_last_layer_where_it_reads_and_agrees(weights):
    """A prompt of 150: the last layer's queries at blocks 0 and 128 alone
    give what one forward at every position gives there."""
    reference = ref.Reference(CONFIG, weights)
    rng = np.random.default_rng(5)
    prompts, tokens = rng.integers(0, 255, (2, 150)), rng.integers(0, 255,
                                                                   (2, 8))
    out = reference.replay(prompts, tokens)
    ids = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    final = np.asarray(reference.final_norm(ids))
    head = np.asarray(weights["lm_head"]).astype(np.float32)
    np.testing.assert_allclose(out["logits"], final[:, 149:] @ head,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out["pooled"], final[:, 149:].mean(1),
                               atol=1e-5)
    np.testing.assert_allclose(out["prompt_pooled"], final[:, :8].mean(1),
                               atol=1e-5)


@pytest.fixture(scope="module")
def sound():
    return run.run_cell(CELL, seed=2_147_484_241, seconds=0.5, trace=False,
                        rehearse=True, with_control=True)


def test_the_rehearsal_cell_is_correct_and_its_control_is_not(sound):
    assert sound["correct"] is True and sound["attempted"] >= 1
    assert all(row["value"] <= row["limit"]
               for row in sound["compared"].values())
    control = sound["control"]
    assert control["correct"] is False
    limits = run.load_json("workloads", CELL + ".json")["check"]["limits"]
    for name, value in sound["numbers"].items():
        assert value * 1.5 <= limits[name] or value == limits[name] == 0
    assert any(control["numbers"][k] >= 1.5 * limits[k]
               for k in ("pooled.rel_rms", "pooled.worst_row"))
    assert control["numbers"]["prompt_pooled.rel_rms"] > limits[
        "prompt_pooled.rel_rms"]
