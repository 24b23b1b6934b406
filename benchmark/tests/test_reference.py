"""The plain reference against the program's float32 executor at toy sizes,
and the reader of initializers against the program's own parser."""

import numpy as np
import pytest

from benchmark.reference import encoder
from benchmark.reference.onnx_initializers import read_initializers

BERT = {"hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 2,
        "intermediate_size": 128, "vocab_size": 50, "layer_norm_eps": 1e-12}
VIT = {"hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 2,
       "intermediate_size": 128, "image_size": 16, "patch_size": 4,
       "num_channels": 3, "layer_norm_eps": 1e-12}


def _program(model_bytes, feeds):
    import jax
    from synapseml_tpu.onnx.importer import OnnxFunction

    with jax.default_matmul_precision("highest"):
        out = OnnxFunction(model_bytes, dtype_policy="float32")(feeds)
    return {k: np.asarray(v) for k, v in out.items()}


def _models():
    from synapseml_tpu.models import zoo
    from synapseml_tpu.onnx.wire import serialize_model

    rng = np.random.default_rng(5)
    bert = serialize_model(zoo.bert_encoder(
        layers=2, hidden=32, heads=2, vocab=50, max_seq=16, seed=3))
    vit = serialize_model(zoo.vit(patch=4, image_size=16, layers=2, hidden=32,
                                  heads=2, num_classes=7, seed=4))
    return [
        (BERT, bert, {"input_ids": rng.integers(0, 50, (5, 12))},
         ("logits", "pooled")),
        (VIT, vit, {"data": rng.standard_normal((5, 3, 16, 16),
                                                dtype=np.float32)},
         ("logits", "features")),
    ]


@pytest.mark.parametrize("case", [0, 1], ids=["bert", "vit"])
def test_reference_agrees_with_the_float32_executor(case):
    config, model_bytes, feeds, outputs = _models()[case]
    ref = encoder.Reference(config, read_initializers(model_bytes))
    want = _program(model_bytes, feeds)
    got = ref.forward_blocks(feeds, block_rows=5)
    for name in outputs:
        assert got[name].shape == want[name].shape
        np.testing.assert_allclose(got[name], want[name], rtol=2e-4, atol=2e-5)
    blocks = ref.forward_blocks(feeds, block_rows=1)
    np.testing.assert_allclose(blocks["logits"], got["logits"], rtol=1e-5,
                               atol=1e-6)


def test_lower_precisions_move_the_answer_in_order():
    config, model_bytes, feeds, _ = _models()[0]
    ref = encoder.Reference(config, read_initializers(model_bytes))
    exact = ref.forward(feeds)["pooled"]
    err = {p: float(np.linalg.norm(ref.forward(feeds, p)["pooled"] - exact)
                    / np.linalg.norm(exact))
           for p in ("bfloat16", "float8")}
    assert 0 < err["bfloat16"] < err["float8"] / 4
    with pytest.raises(ValueError):
        ref.forward(feeds, "int4")


def test_initializers_match_the_programs_parser():
    from synapseml_tpu.onnx.importer import OnnxFunction

    _, model_bytes, _, _ = _models()[1]
    mine = read_initializers(model_bytes)
    theirs = OnnxFunction(model_bytes).constants
    assert set(mine) == set(theirs)
    for name, value in theirs.items():
        assert mine[name].dtype == value.dtype, name
        np.testing.assert_array_equal(mine[name], np.asarray(value))
    with pytest.raises(ValueError):
        read_initializers(b"\x08\x01")
