"""BASELINE.md config-ladder benchmark driver.

Runs each north-star config at a scale matched to the available backend
and prints one JSON line per config:
  1 LeNet/MNIST        -> trains to accuracy target (smoke)
  2 ResNet-50          -> images/sec
  3 BERT-base pretrain -> tokens/sec
  4 Llama train step   -> MFU (delegates to bench.py's model/config)
  5 MoE decoder        -> tokens/sec
  6 Llama KV-cache decode -> tokens/sec (env LADDER_DECODE_B batch,
    LADDER_DECODE_WEIGHTS=int8 for quantized weights)
  7 ViT-Base/16 train  -> images/sec
  8 MoE TRAIN step     -> tokens/sec + activated-param MFU (config 5's
    real metric; row 5 is forward-only)

On CPU the model sizes shrink to keep the run under a few minutes while
exercising the exact same code paths; on a real TPU chip the full-size
configs run. Usage: python tools/ladder_bench.py [1 2 3 5 6 7 8]
(no args = configs 1,2,3,5,6).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _backend():
    """The platform jax runs on, asked in THIS process: a chip belongs
    to one process at a time, so no child may probe it first. Every row
    names the backend it ran on."""
    import jax
    return jax.devices()[0].platform


def bench_lenet():
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-3)
    rng = np.random.default_rng(0)
    # synthetic MNIST-shaped task (dataset download is offline):
    # class-template images + noise — digit-recognition difficulty class
    templates = rng.normal(0, 1, (10, 1, 28, 28)).astype(np.float32)
    y = rng.integers(0, 10, 512)
    X = (templates[y]
         + 0.3 * rng.normal(0, 1, (512, 1, 28, 28))).astype(np.float32)
    for epoch in range(3):
        for i in range(0, 512, 64):
            xb = paddle.to_tensor(X[i:i + 64])
            yb = paddle.to_tensor(y[i:i + 64].astype(np.int64))
            loss = paddle.nn.functional.cross_entropy(model(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
    model.eval()
    pred = np.argmax(model(paddle.to_tensor(X)).numpy(), 1)
    acc = float((pred == y).mean())
    return {"metric": "lenet_train_acc", "value": round(acc, 4),
            "unit": "accuracy", "target": 0.9}


def bench_resnet50(on_tpu):
    """BASELINE config 2 metric is TRAINING images/sec (PaddleClas
    recipe): full fwd+bwd+SGD-momentum with functional BN-stat updates,
    bf16 convs on the MXU."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu.vision.models.resnet import resnet_train_step_factory

    paddle.seed(0)
    model = resnet50()
    if on_tpu:
        model.to(dtype="bfloat16")
    B, HW = (64, 224) if on_tpu else (4, 64)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    params, buffers, opt, step = resnet_train_step_factory(model, mesh)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (B, 3, HW, HW)),
                    jnp.bfloat16 if on_tpu else jnp.float32)
    y = jnp.asarray(rng.integers(0, 1000, B), jnp.int32)

    params, buffers, opt, loss = step(params, buffers, opt, x, y)
    float(loss)
    n = 10 if on_tpu else 3
    t0 = time.perf_counter()
    for _ in range(n):
        params, buffers, opt, loss = step(params, buffers, opt, x, y)
    lv = float(loss)
    dt = (time.perf_counter() - t0) / n
    return {"metric": "resnet50_train_images_per_sec",
            "value": round(B / dt, 1), "unit": "images/sec",
            "batch": B, "hw": HW, "loss": round(lv, 4)}


def bench_bert(on_tpu):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import (BertConfig, BertForPretraining,
                                       bert_pretrain_step_factory)

    paddle.seed(0)
    if on_tpu:
        cfg = BertConfig()  # base
        B, S, steps = 16, 512, 10
    else:
        cfg = BertConfig.tiny()
        B, S, steps = 4, 32, 3
    model = BertForPretraining(cfg)
    model.eval()
    if on_tpu:
        model.to(dtype="bfloat16")  # AMP-style pretrain: bf16 MXU rate
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    params, opt, step = bert_pretrain_step_factory(model, mesh)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    types = jnp.zeros((B, S), jnp.int32)
    mlm = jnp.asarray(np.where(rng.random((B, S)) < 0.15,
                               rng.integers(0, cfg.vocab_size, (B, S)),
                               -100), jnp.int32)
    nsp = jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32)
    params, opt, loss = step(params, opt, ids, types, mlm, nsp)  # compile
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, loss = step(params, opt, ids, types, mlm, nsp)
    lv = float(loss)
    dt = (time.perf_counter() - t0) / steps
    return {"metric": "bert_pretrain_tokens_per_sec",
            "value": round(B * S / dt, 1), "unit": "tokens/sec",
            "loss": round(lv, 4), "batch": B, "seq": S}


def bench_moe(on_tpu):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.nlp import MoEConfig, MoEForCausalLM

    paddle.seed(0)
    cfg = MoEConfig.tiny()
    model = MoEForCausalLM(cfg)
    model.eval()
    params = {k: v._value for k, v in model.state_dict().items()}

    def fwd(params, tokens):
        model.load_tree(params)
        return model(Tensor(tokens))._value

    B, S = (8, 256) if on_tpu else (2, 16)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    from paddle_tpu.core.sync import hard_sync
    jit_fwd = jax.jit(fwd)
    hard_sync(jit_fwd(params, tokens))
    n = 10 if on_tpu else 3
    t0 = time.perf_counter()
    for _ in range(n):
        out = jit_fwd(params, tokens)
    hard_sync(out)
    dt = (time.perf_counter() - t0) / n
    return {"metric": "moe_fwd_tokens_per_sec",
            "value": round(B * S / dt, 1), "unit": "tokens/sec"}


def bench_moe_train(on_tpu):
    """Config 8: full MoE TRAIN step (BASELINE config 5's real metric —
    the fwd-only row 5 understates the config). One-chip scale; expert
    parallelism itself is validated on the virtual mesh (dryrun) and the
    same factory shards 'expert' over ICI on a pod. MFU accounts
    ACTIVATED params only (top_k/num_experts of the routed experts)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from jax.sharding import Mesh
    from paddle_tpu.models.nlp import (MoEConfig, MoEForCausalLM,
                                       moe_train_step_factory)

    # repo root is already importable (paddle_tpu resolved above), and
    # bench.py lives at the same root
    from bench import peak_for

    paddle.seed(0)
    if on_tpu:
        cfg = MoEConfig(vocab_size=32000, hidden_size=1024,
                        intermediate_size=2816, num_hidden_layers=8,
                        num_attention_heads=16, num_key_value_heads=16,
                        num_experts=8, top_k=2, moe_every=2,
                        num_shared_experts=1)
        B, S = 8, 2048
    else:
        cfg = MoEConfig.deepseek_tiny()
        B, S = 2, 32
    model = MoEForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    n_act = model.activated_params()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    params, opt_state, step = moe_train_step_factory(model, mesh)
    rng = np.random.default_rng(0)
    seq = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)),
                      jnp.int32)
    # the factory scores position-aligned labels; callers shift —
    # unshifted tokens would report the degenerate copy-task loss
    tokens, labels = seq[:, :-1], seq[:, 1:]
    params, opt_state, loss = step(params, opt_state, tokens, labels)
    float(loss)  # warm + sync
    n = 10 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(n):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
    lv = float(loss)
    dt = (time.perf_counter() - t0) / n
    tok = B * S
    attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * S * tok
    mfu = (6 * n_act * tok + attn) / dt / peak_for(jax.devices()[0])
    return {"metric": "moe_train_tokens_per_sec",
            "value": round(tok / dt, 1), "unit": "tokens/sec",
            "mfu_activated": round(mfu, 4),
            "activated_params": n_act, "loss": lv}


def bench_decode(on_tpu):
    """Config 6 (exceeds the ladder): compiled KV-cache greedy decode
    throughput — the fused_multi_transformer serving analog."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import llama_decode_factory

    paddle.seed(0)
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                          intermediate_size=4096, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=2048, dtype=jnp.bfloat16)
        # serving batch override (B=8 default; B=64 for the wide row)
        B = int(os.environ.get("LADDER_DECODE_B", "8"))
        prompt_len, new = 128, 128
    else:
        cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                               kv_heads=2)
        B, prompt_len, new = 2, 8, 8
    model = LlamaForCausalLM(cfg)
    model.eval()
    if on_tpu:
        model.to(dtype="bfloat16")
    weight_dtype = os.environ.get("LADDER_DECODE_WEIGHTS") or None
    if weight_dtype == "bf16":  # the reported baseline label round-trips
        weight_dtype = None
    gen = llama_decode_factory(model, max_len=prompt_len + new,
                               weight_dtype=weight_dtype)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, prompt_len)),
                         jnp.int32)
    from paddle_tpu.core.sync import hard_sync
    out = gen(prompt, max_new_tokens=new)
    hard_sync(out)
    n = 3 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(n):
        out = gen(prompt, max_new_tokens=new)
    hard_sync(out)
    dt = (time.perf_counter() - t0) / n
    return {"metric": "llama_decode_tokens_per_sec",
            "value": round(B * new / dt, 1), "unit": "tokens/sec",
            "batch": B, "prompt": prompt_len, "new_tokens": new,
            "weights": weight_dtype or "bf16"}


def bench_vit(on_tpu):
    """Config 7 (exceeds the ladder): ViT-Base/16 training images/sec —
    the PaddleClas transformer-backbone analog; pure MXU matmuls."""
    import time

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.vision.models import (VisionTransformer,
                                          vit_base_patch16_224)

    paddle.seed(0)
    if on_tpu:
        model = vit_base_patch16_224()
        B, HW, steps = 64, 224, 10
        model.to(dtype="bfloat16")
    else:
        model = VisionTransformer(img_size=32, patch_size=8, class_num=10,
                                  embed_dim=48, depth=2, num_heads=4)
        B, HW, steps = 4, 32, 3
    model.train()
    params = model.tree_flatten_params()

    def loss_fn(params, x, y):
        model.load_tree(params)
        logits = model(Tensor(x))._value.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, y[:, None], -1).mean()

    @jax.jit
    def step(params, x, y, lr):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        return ({k: p - lr * g[k].astype(p.dtype)
                 for k, p in params.items()}, loss)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (B, 3, HW, HW)),
                    jnp.bfloat16 if on_tpu else jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, B), jnp.int32)
    params, loss = step(params, x, y, 1e-3)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, loss = step(params, x, y, 1e-3)
    lv = float(loss)
    dt = (time.perf_counter() - t0) / steps
    return {"metric": "vit_train_images_per_sec",
            "value": round(B / dt, 1), "unit": "images/sec",
            "batch": B, "hw": HW, "loss": round(lv, 4)}


def main():
    want = set(sys.argv[1:]) or {"1", "2", "3", "5", "6"}
    backend = _backend()
    on_tpu = backend != "cpu"
    runners = {"1": bench_lenet,
               "2": lambda: bench_resnet50(on_tpu),
               "3": lambda: bench_bert(on_tpu),
               "5": lambda: bench_moe(on_tpu),
               "6": lambda: bench_decode(on_tpu),
               "7": lambda: bench_vit(on_tpu),
               "8": lambda: bench_moe_train(on_tpu)}
    if "4" in want:
        print(json.dumps({"metric": "llama_train_mfu",
                          "note": "run bench.py (the driver entry)"}))
    for k in sorted(want & set(runners)):
        try:
            res = runners[k]()
            res["config"] = int(k)
            res["backend"] = backend
            print(json.dumps(res))
        except Exception as e:  # noqa: BLE001 — ladder keeps going
            print(json.dumps({"config": int(k), "error": repr(e)[-400:]}))


if __name__ == "__main__":
    main()
