"""The plain reference against the program at a tiny Mistral shape, and the
controls: the reference at int8 in the program's place reads far off."""
import jax.numpy as jnp
import numpy as np

from benchmark.harness import serve_check, weights as W
from benchmark.harness.spec import Spec
from conftest import TINY_MODEL

F = Spec().family("mistral")
R = F.R

MODEL = dict(TINY_MODEL, rms_norm_eps=1e-5, rope_theta=1e6, tie_word_embeddings=False,
             sliding_window=None)
SEED = 2147484001


def test_weights_are_a_function_of_the_seed():
    a, b, c = (W.make_weights(F, MODEL, s) for s in (SEED, SEED, SEED + 1))
    assert all(bool(jnp.array_equal(a[k], b[k])) for k in a)
    assert not bool(jnp.array_equal(a["lm_head.weight"], c["lm_head.weight"]))
    assert a["lm_head.weight"].dtype == jnp.bfloat16
    one = W.initial_leaf(F, MODEL, W.key_of(SEED, 1), "model.norm.weight")
    assert bool(jnp.array_equal(one, a["model.norm.weight"]))


def test_program_forward_agrees_with_reference():
    from paddle_tpu.core.tensor import Tensor
    net = F.serving_program(MODEL, {"max_len": 1024})
    F.load_weights(net, W.make_weights(F, MODEL, SEED))
    tokens = np.random.default_rng(0).integers(0, 512, (2, 512)).astype(np.int32)
    got = net(Tensor(jnp.asarray(tokens)))._value.astype(jnp.float32)
    w = W.make_weights(F, MODEL, SEED)
    want = R.logits(w, R.hidden_states(MODEL, w, jnp.asarray(tokens)))
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 0.02 * scale      # bf16 against f32
    lower = R.logits(w, R.hidden_states(MODEL, w, jnp.asarray(tokens), quant="int8"), "int8")
    assert float(jnp.abs(lower - want).max()) > 3 * float(jnp.abs(got - want).max())


def test_serving_control_reads_wider_gaps_than_the_reference_itself():
    w = W.make_weights(F, MODEL, SEED)
    rng = np.random.default_rng(1)
    sample = []
    for n in (40, 24):
        prompt = [int(t) for t in rng.integers(0, 512, 100)]
        out = []
        for _ in range(n):      # greedy by the reference: every gap is nought
            seq = jnp.asarray(np.pad(prompt + out, (0, 512 - 100 - len(out)))[None])
            lg = R.logits(w, R.hidden_states(MODEL, w, seq)[0, 99 + len(out)])
            out.append(int(jnp.argmax(lg)))
        sample.append({"rid": "x", "prompt": tuple(prompt), "output": out})
    own = serve_check.served_gaps(F, MODEL, SEED, sample, 512)
    assert own["max"] <= 1e-5 and own["tokens"] == 64     # blocked vs whole: reduction order only
    ctrl = serve_check.served_gaps(F, MODEL, SEED, sample, 512, control="int8")
    assert ctrl["mean"] > 1e-4 and ctrl["max"] > 3e-3
    sample[0]["output"][5] ^= 1         # one served token altered
    assert serve_check.served_gaps(F, MODEL, SEED, sample, 512)["max"] > 0.01
