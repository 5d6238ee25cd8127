"""The benchmark's family seam, from the tier-1 command (which reads
``tests/`` alone).

First, ``benchmark/tests/test_family.py``'s cases, collected as they are:
the same test functions and the same fixtures (``benchmark/tests/
conftest.py``), loaded by their paths.  That file imports its own
``conftest`` by name, so this directory's stands aside while it loads.

Then the same seam checks for the ``deepseek_v3`` family at toy size: its
keys, its leaves against the program's ``state_dict``, its operation counts
written down by hand, its readers, and a serving cell run on the CPU whose
served tokens the family's reference judges.
"""
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH_TESTS = REPO / "benchmark" / "tests"


def _load(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ours = sys.modules.get("conftest")
sys.path.insert(0, str(BENCH_TESTS))        # test_family's toy files are found beside it
try:
    sys.modules["conftest"] = _bench_conftest = _load("benchmark_tests_conftest",
                                                      BENCH_TESTS / "conftest.py")
    _family = _load("benchmark_tests_test_family", BENCH_TESTS / "test_family.py")
finally:
    sys.path.remove(str(BENCH_TESTS))
    if _ours is not None:
        sys.modules["conftest"] = _ours
    else:
        del sys.modules["conftest"]

tiny_root = _bench_conftest.tiny_root
tiny_spec = _bench_conftest.tiny_spec
globals().update({k: v for k, v in vars(_family).items() if k.startswith("test_")})

from benchmark.harness.spec import Spec  # noqa: E402
from benchmark.run import run_cell  # noqa: E402

CELL = "serve_latent_moe_docqa"
# Moonlight-16B-A3B's config.json as the catalog has it
# (model-configs/architectures.jsonl), written down here
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512,
    "max_position_embeddings": 8192, "model_type": "deepseek_v3",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 163840}
TOY = dict(vocab_size=512, hidden_size=64, intermediate_size=176, moe_intermediate_size=24,
           num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
           n_routed_experts=8, num_experts_per_tok=3, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16)


def test_the_configuration_keeps_every_published_key_but_its_depth():
    cfg = json.loads((REPO / "benchmark/configs/moonlight-16b-a3b-serve-l7.json").read_text())
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 27}
    assert {k: cfg[k] for k in PUBLISHED} == dict(PUBLISHED, num_hidden_layers=7)
    assert cfg["family"] == "deepseek_v3" and all(cfg.get(k) for k in ("stands_for", "assumed", "note"))
    assert cfg["engine"] == {"slots": 32, "max_len": 6208, "page_size": 64,
                             "n_pool_pages": 3617, "policy": "paged", "prefill_chunk_budget": 4}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "moonlight-16b-a3b-serve-l7")
    assert entry["source"] == cfg["source"] and len(entry["source"]) < 200
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1 and len(bench["workloads"]) == 8


def test_the_familys_leaves_are_the_programs_state_dict():
    from paddle_tpu.models.nlp import deepseek_v3 as program
    cell = Spec().cell(CELL)
    fam, model = cell["family"], cell["config_spec"]["model"]
    assert set(fam.MODEL_KEYS) <= set(PUBLISHED) and "family" not in model
    net = fam.serving_program(model, cell["config_spec"]["engine"])      # shapes only
    assert not net.materialized() and net.config.num_hidden_layers == 7
    shapes = fam.leaf_shapes(model)
    assert shapes == program.leaf_shapes(net.config) and list(shapes) == list(net.leaf_shapes())
    assert sum(fam.is_gain(n, s) for n, s in shapes.items()) == 7 * 3 + 1
    assert not fam.is_gain("model.layers.3.mlp.gate.e_score_correction_bias", (64,))
    toy = dict(model, **TOY)
    assert fam.leaf_shapes(toy) == program.leaf_shapes(fam.program_config(toy, 128))
    assert not hasattr(fam, "train_step") and not hasattr(fam, "training_program")


def test_the_familys_operation_counts_are_the_published_operations():
    cell = Spec().cell(CELL)
    fam, model = cell["family"], cell["config_spec"]["model"]
    attn = 2048 * 3072 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128 + 2048 * 2048
    sparse = 2048 * 64 + 8 * 3 * 2048 * 1408
    params = 7 * attn + 6 * sparse + 3 * 2048 * 11264
    assert fam.token_matmul_params(model) == params == 581_566_464
    pair = 2 * 16 * (192 + 128) * 7                 # as published: score over 192, sum over 128
    assert fam.forward_flops(model, 1, 4096) == 2 * params + 2 * 2048 * 163840 + pair * 4097
    assert fam.forward_flops(model, 64, 4096, 1) == (
        2 * params * 64 + 2 * 2048 * 163840 + pair * (64 * 4096 + 64 * 65 / 2))
    row = {"prompt_len": 4400, "cached": 4096, "token_times": [0.0, 0.1, 0.2]}
    assert fam.request_flops(model, row) == (fam.forward_flops(model, 304, 4096, 1),
                                             fam.forward_flops(model, 2, 4400))
    from benchmark import latent_moe_flops as F
    # the kernel's own work (its roofline) is the absorbed form's
    assert F.absorbed_pair_flops(model) == 34816 and F.expanded_pair_flops(model) == 10240
    assert F.latent_decode_flops(model, 1000) == 1000 * 34816 * 7
    assert F.expert_products_bytes(model, 64) == 64 * 3 * 2048 * 1408 * 2
    assert F.latent_decode_bytes(model, 1000) == 1000 * 576 * 2 * 7


def _observation(kinds, spans, interval=(1.0, 3.0)):
    n = len(kinds)
    return {"kind": "serve", "window_s": 4.0, "chips": 1, "model_flops": 1e13,
            "model": Spec().cell(CELL)["config_spec"]["model"],
            "peak": Spec().peak("TPU v5e"), "spans": spans, "trace_interval": interval,
            "requests": [{"prompt_len": 4400, "cached": 4096, "token_times": [1.0]},
                         {"prompt_len": 4000, "cached": 0, "token_times": [2.0]},
                         {"prompt_len": 100, "cached": 64, "token_times": []}],
            "overhead": {"model_counts": {
                "kind": kinds, "layer_calls": [6] * n, "pairs": [6 * 384] * n,
                "experts_hit": [6 * 61] * n, "max_expert_pairs": [6 * 12] * n,
                "cached_tokens_read": [0 if k == "prefill" else 150_000 for k in kinds]}},
            "device_trace": {"busy_s": 1.6, "window_s": 2.0, "chips_traced": 1, "ops": [
                {"name": "jit__decode_n/ragged-dot-none.3 custom-call bf16[192,1408]",
                 "seconds": 0.5, "count": 36},
                {"name": "jit__chunk_program/ragged-dot-none.7 custom-call bf16[384,1408]",
                 "seconds": 0.3, "count": 18},
                {"name": "jit__decode_n/latent_paged_attention.14 custom-call bf16[32,16,512]",
                 "seconds": 0.1, "count": 14},
                {"name": "jit__chunk_program/latent_paged_attention.2 custom-call bf16[1,1024,512]",
                 "seconds": 0.05, "count": 7},
                {"name": "jit__decode_n/fusion.4 fusion bf16[32,163840]", "seconds": 0.2,
                 "count": 2}]}}


def test_the_latent_moe_readers_read_the_counts_and_the_trace():
    spec = Spec()
    kinds = ["prefill", "prefill", "decode", "prefill", "decode"]
    spans = [("prefill", 0.1, 0.5, 2), ("decode", 1.1, 1.2, None),
             ("prefill", 1.3, 1.4, 1), ("decode", 2.9, 3.5, None)]
    obs = _observation(kinds, spans)
    mine = ("moe_share.docqa", "moe_expert_roofline.docqa", "expert_load_max_over_mean.docqa",
            "mla_attn_roofline.docqa", "mla_attn_share.docqa", "prefix_hit_share.docqa")
    assert set(mine) < {m["name"] for m in spec.per_layer(CELL)}
    read = {name: spec.reader(name)(obs) for name in mine}
    assert read["expert_load_max_over_mean.docqa"] == pytest.approx(64 * 12 / 384)
    assert read["moe_share.docqa"] == pytest.approx(100 * 0.8 / 1.6)
    assert read["mla_attn_share.docqa"] == pytest.approx(100 * 0.15 / 1.6)   # both programs' calls
    assert read["prefix_hit_share.docqa"] == pytest.approx(100 * 4096 / 8400)
    # inside (1.0, 3.0]: the decode call that ended at 1.2 and the chunk at 1.4
    byte_s = 2 * 6 * 61 * 3 * 2048 * 1408 * 2 / 819e9
    assert read["moe_expert_roofline.docqa"] == pytest.approx(100 * byte_s / 0.8)
    assert read["mla_attn_roofline.docqa"] == pytest.approx(
        100 * (150_000 * 576 * 2 * 7 / 819e9) / 0.1)
    assert 0 < read["moe_expert_roofline.docqa"] < 100 and 0 < read["mla_attn_roofline.docqa"] < 100
    # nothing to read: another model's run, the parent's run, or records that disagree
    for broken in (dict(obs, overhead={"calls": {}}), dict(obs, overhead=None),
                   dict(obs, spans=spans[:-1])):
        assert all(spec.reader(name)(broken) is None for name in mine[:5])
    untraced = {k: v for k, v in obs.items() if k not in ("device_trace", "trace_interval")}
    assert spec.reader("mla_attn_roofline.docqa")(untraced) is None
    assert spec.reader("moe_expert_roofline.docqa")(untraced) is None
    assert spec.reader("mla_attn_share.docqa")(untraced) is None
    assert spec.reader("expert_load_max_over_mean.docqa")(untraced) == pytest.approx(2.0)


@pytest.fixture(scope="module")
def toy_docqa_spec(tiny_root, tmp_path_factory):
    """The cell at toy size, added to a copy of the tests' benchmark as new
    files and entries, as a new configuration is."""
    top = tmp_path_factory.mktemp("docqa")
    root = top / "benchmark"
    shutil.copytree(tiny_root, root)
    cfg = json.loads((root / "configs/moonlight-16b-a3b-serve-l7.json").read_text())
    cfg.update(TOY, engine={"slots": 4, "max_len": 384, "page_size": 16, "n_pool_pages": 97,
                            "policy": "paged", "prefill_chunk_budget": 2})
    (root / "configs/toy_docqa.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "traffic/doc_reask.json").read_text())
    mix.update(rate_per_s=6.0,
               prompt={"dist": "lognormal", "median": 80, "sigma": 0.3, "min": 16, "max": 192},
               output={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
               shared_prefix={"share": 0.75, "groups": 2, "tokens": 64})
    (root / "traffic/toy_doc.json").write_text(json.dumps(mix))
    cell = json.loads((root / f"workloads/{CELL}.json").read_text())
    # logits of order 0.1 at these widths: float32 against float32
    cell.update(config="toy_docqa", traffic="toy_doc",
                limits=dict(cell["limits"], served_gap_max=1e-3, served_gap_mean=1e-4))
    (root / "workloads/toy_docqa.json").write_text(json.dumps(cell))
    bench = json.loads((tiny_root.parent / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy_docqa", "config": "toy_docqa", "traffic": "toy_doc",
                               "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("toy_docqa")
    (top / "BENCHMARK.json").write_text(json.dumps(bench))
    return Spec(root)


def test_the_cell_runs_at_toy_size_and_its_reference_judges_it(toy_docqa_spec, capsys):
    out = run_cell(toy_docqa_spec, "toy_docqa", 2147484001, 2.0, False, require_chip=False)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 12
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    info = next(l for l in capsys.readouterr().out.splitlines() if l.startswith("info "))
    assert json.loads(info[5:])["tokens_compared"] > 20
    broken = run_cell(toy_docqa_spec, "toy_docqa", 2147484001, 2.0, False,
                      require_chip=False, fault="token_altered")
    assert not broken["correct"] and not broken["checks"]["served_gap_max"]["ok"]


def test_the_router_flips_tool_reads_the_fault_and_every_sampled_position(toy_docqa_spec, tmp_path):
    """``benchmark/tools/router_flips.py`` (what the cell's ``served_gap_*``
    limits stand on, PERF.md section 2) rehearsed at toy size: the planted
    fault's reading, and one entry a sampled position in every list."""
    tool = _load("router_flips_tool", REPO / "benchmark/tools/router_flips.py")
    out = tmp_path / "flips.json"
    assert tool.main(["--workload", "toy_docqa", "--seed", "2147484001", "--seconds", "2",
                      "--out", str(out), "--rehearse", str(toy_docqa_spec.root)]) == 0
    got = json.loads(out.read_text())
    summary, per = got["summary"], got["positions"]
    assert summary["fault_token_altered"]["served_gap_max"] > 0.1 > summary["sound"]["served_gap_max"]
    assert summary["tokens"] > 20 and {len(v) for v in per.values()} == {summary["tokens"]}
    assert all(0 <= f <= 2 for f in per["program_flips"] + per["control_flips"])   # 2 sparse layers
    assert all(m >= 0 for m in per["margin_min"])
    assert sum(s["positions"] for s in summary["served_gap_by_reference_margin"].values()) \
        == summary["tokens"]
