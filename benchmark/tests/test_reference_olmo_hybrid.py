"""``reference/olmo_hybrid.py`` against a second, even plainer writing of the
equations at the tiny size: numpy float64, one token at a time, the state a
head; the forward is causal; the ``float8`` control differs; ``replay``
reads the positions the ids were chosen from through the UNTIED head; the
work module counts the published model's parameters; and the rehearsal
cell, the whole command on the CPU, is correct while its control is not."""

import numpy as np
import pytest

import run
from benchmark.reference import olmo_hybrid as ref
from benchmark.reference.onnx_initializers import read_initializers
from benchmark.work import olmo_hybrid as work

CELL = "olmo_hybrid_tiny.rehearsal"
CONFIG = run.load_json("configs", "olmo_hybrid_tiny.json")
HEADS, DK, DV, TAPS = 4, 16, 32, 4


@pytest.fixture(scope="module")
def weights():
    from synapseml_tpu.models.zoo import build_model_bytes

    return read_initializers(build_model_bytes("OlmoHybridTiny", seed=5))


def _norm(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _unit(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _token_by_token(f, ids):
    """One row's final norm output, position by position: each delta rule
    layer keeps its state and its last convolution rows, each attention
    layer its keys and values."""
    f = {k: np.asarray(v, np.float64) for k, v in f.items()}
    layers = CONFIG["num_hidden_layers"]
    states = {i: np.zeros((HEADS, DK, DV)) for i in range(layers)}
    rows = {i: [] for i in range(layers)}
    keys = {i: [] for i in range(layers)}
    out = []
    for token in ids:
        x = f["tok_emb"][token]
        for i, kind in enumerate(CONFIG["layer_types"]):
            p = f"l{i}_"
            if kind == "full_attention":
                q = _norm(x @ f[p + "q_w"], f[p + "q_norm_w"]).reshape(HEADS,
                                                                        -1)
                keys[i].append((_norm(x @ f[p + "k_w"], f[p + "k_norm_w"]),
                                x @ f[p + "v_w"]))
                k = np.stack([a for a, _ in keys[i]]).reshape(-1, HEADS, 16)
                v = np.stack([b for _, b in keys[i]]).reshape(-1, HEADS, 16)
                s = np.einsum("hd,thd->ht", q, k) / 4.0
                a = np.exp(s - s.max(-1, keepdims=True))
                a /= a.sum(-1, keepdims=True)
                mix = np.einsum("ht,thd->hd", a, v).reshape(-1) @ f[p + "o_w"]
            else:
                rows[i] = (rows[i] + [x @ f[p + "qkv_w"]])[-TAPS:]
                window = [np.zeros_like(rows[i][0])] * (TAPS - len(rows[i])) \
                    + rows[i]
                qkv = _silu(sum(window[j] * f[p + "conv_w"][:, 0, j]
                                for j in range(TAPS)))
                q = _unit(qkv[:HEADS * DK].reshape(HEADS, DK)) / np.sqrt(DK)
                k = _unit(qkv[HEADS * DK:2 * HEADS * DK].reshape(HEADS, DK))
                v = qkv[2 * HEADS * DK:].reshape(HEADS, DV)
                ab = x @ f[p + "ab_w"]
                dt = np.log1p(np.exp(ab[:HEADS] + f[p + "dt_b"]))
                g = -np.exp(f[p + "a_log"]) * dt
                beta = 2 / (1 + np.exp(-ab[HEADS:]))
                o = np.zeros((HEADS, DV))
                for h in range(HEADS):
                    s = states[i][h] * np.exp(g[h])
                    u = beta[h] * (v[h] - k[h] @ s)
                    states[i][h] = s + np.outer(k[h], u)
                    o[h] = q[h] @ states[i][h]
                gate = (x @ f[p + "gate_w"]).reshape(HEADS, DV)
                mix = (_norm(o, f[p + "o_norm_w"]) * _silu(gate)).reshape(-1) \
                    @ f[p + "out_w"]
            x = x + _norm(mix, f[p + "norm_mix_w"])
            ffn = (_silu(x @ f[p + "ffn_gate_w"]) * (x @ f[p + "ffn_up_w"])) \
                @ f[p + "ffn_down_w"]
            x = x + _norm(ffn, f[p + "norm_ffn_w"])
        out.append(_norm(x, f["norm_f_w"]))
    return np.stack(out)


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
    """``A_log`` the log of a uniform draw in (0, 16] a head, as a FLOAT
    initializer whose numbers a bfloat16 holds; the head is untied."""
    import ml_dtypes

    a_log = weights["l0_a_log"]
    assert a_log.dtype == np.float32 and a_log.shape == (HEADS,)
    np.testing.assert_array_equal(
        a_log, a_log.astype(ml_dtypes.bfloat16).astype(np.float32))
    assert (np.exp(a_log) <= 16.1).all()
    assert weights["lm_head"].shape == (64, 512)
    assert not np.array_equal(np.asarray(weights["lm_head"]).T,
                              np.asarray(weights["tok_emb"]))


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
    ids = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    final = np.asarray(reference.final_norm(ids))
    head = np.asarray(weights["lm_head"]).astype(np.float32)
    np.testing.assert_allclose(out["logits"][1], final[1, 7:] @ head,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out["pooled"], final[:, 7:].mean(1),
                               atol=1e-6)


def test_the_work_module_counts_the_published_model():
    """The parameters of the configuration's deployment note: the whole
    model and the cell's stage of eight layers with both ends."""
    config = run.load_json("configs", "olmo_hybrid_7b.json")
    assert work.parameters(config) == 2_435_748_072
    whole = dict(config, num_hidden_layers=32,
                 layer_types=config["layer_types"] * 4)
    assert work.parameters(whole) == 7_430_870_688
    # a row's state: six layers of float32 96 x 5,760 and three rows of
    # 11,520 (in bfloat16 elements)
    assert work.recurrent_state_elements(config) * 2 == 6 * (
        96 * 5760 * 4 + 3 * 11520 * 2)
    assert work.passes_per_call(config) == 127
    peaks = run.load_json("peaks.json")["TPU v5 lite"]
    least = work.generation_least_seconds(config, {"S": 128}, 128, peaks)
    assert least["prompt_s"] < least["loop_s"]
    assert least["seconds"] == pytest.approx(
        least["prompt_s"] + least["loop_s"])


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
