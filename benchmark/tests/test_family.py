"""The family seam: the harness finds all that depends on the model's shape
by the ``family`` a configuration names.  Mistral's numbers are the
parent's, written down from it; a second, toy family (``toy_family.py``,
``toy_reference.py``) is added to a copy of the benchmark as new files and
``BENCHMARK.json`` entries only, and runs a serving and a training cell."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import serve_check, weights as W
from benchmark.harness.spec import Spec, SpecError
from benchmark.run import run_cell
from conftest import TINY_MODEL

MODEL = dict(TINY_MODEL, rms_norm_eps=1e-5, rope_theta=1e6, tie_word_embeddings=False,
             sliding_window=None)
SEED = 2147484001


def test_the_spec_finds_a_family_by_the_name_its_configuration_states(tiny_spec):
    assert tiny_spec.cell("tiny_serve")["family"] is tiny_spec.family("mistral")
    toy = tiny_spec.cell("toy_serve")
    assert toy["family"] is tiny_spec.family("toy") is not tiny_spec.family("mistral")
    assert toy["config_spec"]["model"]["final_gain"] is True        # a key of its own
    assert "final_gain" not in tiny_spec.cell("tiny_serve")["config_spec"]["model"]
    assert "out_gain" in toy["family"].leaf_shapes(toy["config_spec"]["model"])


@pytest.mark.parametrize("change,complaint", [
    (lambda c: c.pop("family"), "names no family"),
    (lambda c: c.update(family="nobody"), "missing benchmark file"),
    (lambda c: c.pop("rope_theta"), "lacks ['rope_theta']")])
def test_a_configuration_without_its_family_or_its_keys_is_refused(tiny_root, tmp_path,
                                                                   change, complaint):
    import shutil
    root = tmp_path / "benchmark"
    shutil.copytree(tiny_root, root)
    shutil.copy(tiny_root.parent / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    cfg = json.loads((root / "configs/tiny.json").read_text())
    change(cfg)
    (root / "configs/tiny.json").write_text(json.dumps(cfg))
    with pytest.raises(SpecError) as e:
        Spec(root).cell("tiny_serve")
    assert complaint in str(e.value)


# written down from the parent (e6ff119: harness/weights.py, flops.py) at seed 7
PARENT_LEAVES = {
    "model.embed_tokens.weight": [
        0.0245361328125, -0.0062255859375, -0.033447265625, -0.030517578125, 0.01519775390625,
        0.0068359375, -0.0177001953125, 0.002410888671875, -0.018310546875, -0.0032958984375,
        0.00634765625, -0.0220947265625, 0.01953125, 0.0020294189453125, 0.00567626953125,
        -0.04248046875],
    "model.layers.1.post_attention_layernorm.weight": [
        0.9453125, 0.921875, 1.2109375, 1.0703125, 0.78125, 0.91015625, 1.0390625, 0.9921875,
        0.87109375, 0.953125, 0.98046875, 0.9609375, 1.0234375, 1.0, 1.125, 1.0],
    "lm_head.weight": [
        0.00860595703125, -0.0311279296875, 0.01544189453125, 0.00994873046875,
        -0.001983642578125, 0.00714111328125, 0.005828857421875, 0.0002117156982421875,
        -0.0169677734375, 0.040771484375, -0.016845703125, -0.04052734375,
        -0.0027313232421875, 0.0186767578125, 0.00946044921875, 0.00775146484375]}


def test_mistrals_leaves_and_weights_are_the_parents():
    F = Spec().family("mistral")
    shapes = F.leaf_shapes(MODEL)
    assert len(shapes) == 21 and list(shapes)[-2:] == ["model.norm.weight", "lm_head.weight"]
    assert shapes["model.layers.0.self_attn.k_proj.weight"] == (256, 128)
    big = Spec().cell("serve_chat_steady")["config_spec"]["model"]
    assert list(F.leaf_shapes(big).items())[1:10] == [
        ("model.layers.0.input_layernorm.weight", (4096,)),
        ("model.layers.0.self_attn.q_proj.weight", (4096, 4096)),
        ("model.layers.0.self_attn.k_proj.weight", (4096, 1024)),
        ("model.layers.0.self_attn.v_proj.weight", (4096, 1024)),
        ("model.layers.0.self_attn.o_proj.weight", (4096, 4096)),
        ("model.layers.0.post_attention_layernorm.weight", (4096,)),
        ("model.layers.0.mlp.gate_proj.weight", (4096, 14336)),
        ("model.layers.0.mlp.up_proj.weight", (4096, 14336)),
        ("model.layers.0.mlp.down_proj.weight", (14336, 4096))]
    w = W.make_weights(F, MODEL, 7)
    for name, first in PARENT_LEAVES.items():
        got = np.asarray(w[name].astype(jnp.float32)).ravel()[:16]
        assert [float(x) for x in got] == first, name


@pytest.mark.parametrize("args,parents", [
    ((4096, 0), 16492942852096.0), ((1, 1000), 3889299456.0), ((64, 2048, 1), 241059233792.0)])
def test_mistrals_operation_counts_are_the_parents(args, parents):
    F = Spec().family("mistral")
    big = Spec().cell("serve_chat_steady")["config_spec"]["model"]
    assert F.forward_flops(big, *args) == parents
    assert F.train_step_flops(big, 2, 4096) == 98957657112576.0
    row = {"prompt_len": 2112, "cached": 2048, "token_times": [0.0, 0.1]}
    assert F.request_flops(big, row) == (241059233792.0, F.forward_flops(big, 1, 2112))


def test_the_toy_family_counts_and_draws_its_own(tiny_spec):
    toy, dense = tiny_spec.family("toy"), tiny_spec.family("mistral")
    model = tiny_spec.cell("toy_serve")["config_spec"]["model"]
    w = W.make_weights(toy, model, 7)
    assert w["out_gain"].shape == (256,) and abs(float(w["out_gain"].astype(jnp.float32).mean()) - 1) < 0.05
    same = W.make_weights(dense, MODEL, 7)
    assert all(bool(jnp.array_equal(w[k], same[k])) for k in same)      # a leaf added at the end
    assert toy.forward_flops(model, 10, 5) == dense.forward_flops(MODEL, 10, 5) + 2560
    row = {"prompt_len": 40, "cached": 16, "token_times": [1.0, 1.5, 1.5, 2.0, 2.0, 2.5]}
    parts = toy.request_flops(model, row)
    assert len(parts) == 4 and parts[1] == toy.forward_flops(model, 2, 40)
    assert parts[3] == toy.forward_flops(model, 1, 44)


def test_heads_times_head_dim_need_not_be_hidden(tiny_spec):
    """The wide shape (6 heads of 64 on 256 channels) through everything but
    the program, which derives head_dim: the harness's weights, the toy's
    counts, and the comparison of served tokens with the reference, on tokens
    the reference itself put first."""
    cell = tiny_spec.cell("toy_wide_serve")
    toy, model = cell["family"], cell["config_spec"]["model"]
    assert model["num_attention_heads"] * model["head_dim"] == 384 != model["hidden_size"]
    w = W.make_weights(toy, model, SEED)
    assert w["model.layers.0.self_attn.q_proj.weight"].shape == (256, 384)
    assert w["model.layers.0.self_attn.o_proj.weight"].shape == (384, 256)
    with pytest.raises(ValueError, match="derives head_dim"):
        toy.serving_program(model, cell["config_spec"]["engine"])
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, 512, 60)]
    out = []
    for _ in range(8):
        seq = jnp.asarray(np.pad(prompt + out, (0, 512 - 60 - len(out)))[None])
        out.append(int(jnp.argmax(toy.R.logits_of(model, w, seq)[0, 59 + len(out)])))
    sample = [{"rid": "x", "prompt": tuple(prompt), "output": out}]
    assert serve_check.served_gaps(toy, model, SEED, sample, 512)["max"] <= 1e-5
    assert serve_check.served_gaps(toy, model, SEED, sample, 512, control="int8")["mean"] > 1e-5
    sample[0]["output"][3] ^= 1
    assert serve_check.served_gaps(toy, model, SEED, sample, 512)["max"] > 0.01


def _run(spec, name, **kw):
    return run_cell(spec, name, 2147484001, 2.0, False, require_chip=False, **kw)


def test_the_toy_familys_serving_cell_runs_and_is_correct(tiny_spec, capsys):
    out = _run(tiny_spec, "toy_serve")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 12
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_tail5_mean_ms", "setup_s"}
    info = next(l for l in capsys.readouterr().out.splitlines() if l.startswith("info "))
    assert {"total", "python_imports", "chip_start"} == set(json.loads(info[5:])["start_to_chip_s"])
    broken = _run(tiny_spec, "toy_serve", fault="token_altered")
    assert not broken["correct"] and not broken["checks"]["served_gap_max"]["ok"]


def test_the_toy_familys_training_cell_runs_and_is_correct(tiny_spec):
    out = _run(tiny_spec, "toy_train")
    assert out["correct"] and out["attempted"] >= 3
    broken = _run(tiny_spec, "toy_train", fault="half_batch")
    assert not broken["correct"]
