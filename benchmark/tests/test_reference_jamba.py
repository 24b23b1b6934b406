"""``reference/jamba.py`` against a second, even plainer writing of the
equations at the tiny size: a loop over positions, channels' vectors and
heads in numpy float64, one token at a time; the forward is causal; the
``float8`` control differs; ``replay`` reads the positions the ids were
chosen from through the TIED head; and the rehearsal cell, the whole command
on the CPU, is correct while its control is not."""

import numpy as np
import pytest

import run
from benchmark.reference import jamba as ref
from benchmark.reference.onnx_initializers import read_initializers

CELL = "jamba_tiny.rehearsal"
CONFIG = run.load_json("configs", "jamba_tiny.json")
HEADS, HEAD_DIM, RANK, STATE, TAPS = 4, 16, 8, 16, 4


@pytest.fixture(scope="module")
def weights():
    from synapseml_tpu.models.zoo import build_model_bytes

    return read_initializers(build_model_bytes("JambaTiny", seed=5))


def _norm(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _mamba_token_by_token(f, i, u):
    w = {k: np.asarray(f[f"l{i}_{k}"]).astype(np.float64) for k in ref._MAMBA}
    d = w["out_w"].shape[0]
    a = -np.exp(w["a_log"])
    state = np.zeros((d, STATE))  # the published layout
    kept = np.zeros((TAPS, d))    # the last TAPS rows of step 1's x
    out = np.zeros((len(u), w["out_w"].shape[1]))
    for t in range(len(u)):
        xz = u[t] @ w["in_w"]
        kept = np.vstack([kept[1:], xz[:d]])
        x = _silu(sum(kept[k] * w["conv_w"][:, 0, k] for k in range(TAPS))
                  + w["conv_b"])
        dbc = x @ w["x_w"]
        dt_r = _norm(dbc[:RANK], w["dt_norm_w"])
        b = _norm(dbc[RANK:RANK + STATE], w["b_norm_w"])
        c = _norm(dbc[RANK + STATE:], w["c_norm_w"])
        delta = np.logaddexp(dt_r @ w["dt_w"] + w["dt_b"], 0.0)
        for ch in range(d):
            state[ch] = np.exp(delta[ch] * a[ch]) * state[ch] \
                + delta[ch] * b * x[ch]
        y = state @ c + w["d"] * x
        out[t] = (y * _silu(xz[d:])) @ w["out_w"]
    return out


def _attention_token_by_token(f, i, u):
    w = {k: np.asarray(f[f"l{i}_{k}"]).astype(np.float64)
         for k in ref._ATTENTION}
    keys, values = [], []
    ctx = np.zeros((len(u), HEADS * HEAD_DIM))
    for t in range(len(u)):
        keys.append(u[t] @ w["k_w"])     # ONE key-value head
        values.append(u[t] @ w["v_w"])
        q = (u[t] @ w["q_w"]).reshape(HEADS, HEAD_DIM)
        for h in range(HEADS):
            scores = np.array([q[h] @ k for k in keys]) / np.sqrt(HEAD_DIM)
            probs = np.exp(scores - scores.max())
            probs /= probs.sum()
            ctx[t, h * HEAD_DIM:(h + 1) * HEAD_DIM] = sum(
                p * v for p, v in zip(probs, values))
    return ctx @ w["o_w"]


def _token_by_token(f, ids):
    def of(name):
        return np.asarray(f[name]).astype(np.float64)

    x = of("tok_emb")[ids]
    for i in range(CONFIG["num_hidden_layers"]):
        u = _norm(x, of(f"l{i}_norm_in_w"))
        mixer = _attention_token_by_token if i == 2 else _mamba_token_by_token
        x = x + mixer(f, i, u)
        u = _norm(x, of(f"l{i}_norm_post_w"))
        x = x + (_silu(u @ of(f"l{i}_ffn_gate_w")) * (u @ of(f"l{i}_ffn_up_w"))
                 ) @ of(f"l{i}_ffn_down_w")
    return _norm(x, of("norm_f_w"))


def test_the_forward_agrees_with_a_token_by_token_loop(weights):
    import jax

    ids = np.random.default_rng(1).integers(0, 511, (2, 12))
    reference = ref.Reference(CONFIG, weights)
    with jax.default_matmul_precision("highest"):
        final = np.asarray(reference.final_norm(ids))
    for r in range(2):
        want = _token_by_token(weights, ids[r])
        assert np.linalg.norm(final[r] - want) / np.linalg.norm(want) < 2e-5


def test_the_file_holds_the_familys_initialisation(weights):
    """``A_log[c, j] = log(j + 1)`` and ``D = 1`` as FLOAT initializers whose
    numbers a bfloat16 holds (the policy's cast of them is exact)."""
    import ml_dtypes

    a_log, skip = weights["l0_a_log"], weights["l0_d"]
    assert a_log.dtype == np.float32 and skip.dtype == np.float32
    assert a_log.shape == (128, STATE) and (skip == 1).all()
    np.testing.assert_array_equal(
        a_log, a_log.astype(ml_dtypes.bfloat16).astype(np.float32))
    np.testing.assert_allclose(a_log[7], np.log(np.arange(1, STATE + 1)),
                               rtol=2 ** -8)
    assert "lm_head" not in weights  # the head is the embedding's transpose


def test_the_forward_is_causal(weights):
    reference = ref.Reference(CONFIG, weights)
    ids = np.random.default_rng(2).integers(0, 511, (1, 16))
    other = ids.copy()
    other[0, 8:] = 7
    a, b = (np.asarray(reference.final_norm(x)) for x in (ids, other))
    np.testing.assert_array_equal(a[0, :8], b[0, :8])
    assert np.abs(a[0, 8:] - b[0, 8:]).max() > 0.1


def test_the_float8_control_differs_and_bfloat16_lies_between(weights):
    reference = ref.Reference(CONFIG, weights)
    ids = np.random.default_rng(4).integers(0, 511, (4, 24))
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
    prompts, tokens = rng.integers(0, 511, (3, 8)), rng.integers(0, 511,
                                                                 (3, 8))
    out = reference.replay(prompts, tokens, block_rows=2)
    assert out["logits"].shape == (3, 8, 512)
    whole = reference.replay(prompts, tokens)
    np.testing.assert_allclose(out["logits"], whole["logits"], atol=1e-5)
    # one causal forward over the prompt and the ids but the last, through
    # the embedding's transpose
    ids = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    final = np.asarray(reference.final_norm(ids))
    head = np.asarray(weights["tok_emb"]).astype(np.float32).T
    np.testing.assert_allclose(out["logits"][1], final[1, 7:] @ head,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out["pooled"], final[:, 7:].mean(1),
                               atol=1e-6)
    other = tokens.copy()
    other[:, -1] = (tokens[:, -1] + 1) % 511
    np.testing.assert_array_equal(
        reference.replay(prompts, other)["logits"], whole["logits"])


@pytest.fixture(scope="module")
def sound():
    return run.run_cell(CELL, seed=2_147_484_239, seconds=0.5, trace=False,
                        rehearse=True, with_control=True)


def test_the_rehearsal_cell_is_correct_and_its_control_is_not(sound):
    assert sound["correct"] is True and sound["attempted"] >= 1
    assert set(sound["compared"]) == {
        "chosen_logprob.rel_rms", "chosen_logprob.worst_row", "argmax_gap",
        "pooled.rel_rms", "pooled.worst_row", "repeat_mismatch", "nonfinite"}
    assert all(row["value"] <= row["limit"]
               for row in sound["compared"].values())
    control = sound["control"]
    assert control["correct"] is False
    limits = run.load_json("workloads", CELL + ".json")["check"]["limits"]
    # the contract: every limit 1.5 over the program's reading; the control
    # fails one of the two that judge precision by 1.5
    for name, value in sound["numbers"].items():
        assert value * 1.5 <= limits[name] or value == limits[name] == 0
    assert any(control["numbers"][k] >= 1.5 * limits[k]
               for k in ("pooled.rel_rms", "pooled.worst_row"))
