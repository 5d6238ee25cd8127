"""The ``laguna`` family (Laguna-XS.2) added to the benchmark as new files:
its configuration against the published keys, its leaves against the
program's ``state_dict``, its operation and byte counts written down by
hand, the new readers on a hand-made ``obs``, and the cell at toy size on
the CPU, where the plain reference passes the program and fails the int8
control, the planted ``window_ignored`` and an altered token."""
import json
import shutil

import pytest

from benchmark.harness.spec import Spec
from benchmark.run import run_cell
from conftest import REPO

CELL = "serve_window_moe_codemix"
PERIOD = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]
# Laguna-XS.2's config.json as the catalog has it (model-configs/
# architectures.jsonl), written down here
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "gating": True,
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                           "original_max_position_embeddings": 4096, "beta_slow": 1,
                           "beta_fast": 64, "attention_factor": 1.4158883083359672,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10, "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5, "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
TOY = dict(vocab_size=512, hidden_size=32, intermediate_size=128, num_attention_heads=6,
           num_key_value_heads=2, head_dim=16, num_experts=16, num_experts_per_tok=4,
           moe_intermediate_size=8, shared_expert_intermediate_size=8, sliding_window=16,
           num_attention_heads_per_layer=[6, 8, 8, 8] * 10)
TOY_ENGINE = {"slots": 4, "max_len": 384, "page_size": 8, "n_pool_pages": 257,
              "n_window_pages": 33, "policy": "paged", "prefill_chunk_budget": 2}


def test_the_configuration_keeps_every_published_key_but_its_depth():
    cfg = json.loads((REPO / "benchmark/configs/laguna-xs.2-serve-l5.json").read_text())
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 40}
    assert {k: cfg[k] for k in PUBLISHED} == dict(PUBLISHED, num_hidden_layers=5)
    assert cfg["family"] == "laguna" and all(cfg.get(k) for k in ("stands_for", "note"))
    assert {"gating", "router", "router_bias", "qk_norm", "shared_expert_gate",
            "torch_dtype"} <= set(cfg["assumed"])
    assert cfg["engine"] == {"slots": 32, "max_len": 8768, "page_size": 64,
                             "n_pool_pages": 5409, "n_window_pages": 513, "policy": "paged",
                             "prefill_chunk_budget": 4}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = bench["configs"][-1]
    assert entry["name"] == "laguna-xs.2-serve-l5" and entry["source"] == cfg["source"]
    assert [w["name"] for w in bench["workloads"]][-2:] == [CELL, "serve_decode_heavy"]
    assert all(w["chips"] == 1 for w in bench["workloads"][-2:])
    tok_s = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert tok_s["workloads"][-2:] == [CELL, "serve_decode_heavy"] and tok_s["bound"] == 0.05


def test_the_cells_traffic_is_the_issues_letter_for_letter():
    mix = json.loads((REPO / "benchmark/traffic/code_mixed_len.json").read_text())
    assert {k: mix[k] for k in ("kind", "burst", "prompt", "output", "shared_prefix",
                                "greedy", "order")} == {
        "kind": "serve_open_loop", "burst": 1,
        "prompt": {"dist": "lognormal", "median": 1280, "sigma": 0.9, "min": 128, "max": 8192},
        "output": {"dist": "lognormal", "median": 160, "sigma": 0.6, "min": 32, "max": 512},
        "shared_prefix": {"share": 0.5, "groups": 4, "tokens": 1024},
        "greedy": True, "order": "fixed"}
    heavy = json.loads((REPO / "benchmark/traffic/decode_heavy.json").read_text())
    assert {k: heavy[k] for k in ("kind", "burst", "prompt", "output", "shared_prefix",
                                  "greedy", "order")} == {
        "kind": "serve_open_loop", "burst": 1,
        "prompt": {"dist": "uniform", "min": 192, "max": 320},
        "output": {"dist": "lognormal", "median": 1536, "sigma": 0.35, "min": 512, "max": 2048},
        "shared_prefix": None, "greedy": True, "order": "fixed"}
    cell = json.loads((REPO / "benchmark/workloads/serve_decode_heavy.json").read_text())
    steady = json.loads((REPO / "benchmark/workloads/serve_chat_steady.json").read_text())
    assert cell["limits"] == steady["limits"] and cell["config"] == steady["config"]
    # the longest request fits the engine's tables in both cells
    assert 8192 + 512 + 1 <= 8768 and 320 + 2048 + 64 <= 4224


def test_the_familys_leaves_are_the_programs_state_dict():
    from paddle_tpu.models.nlp import laguna as program
    cell = Spec().cell(CELL)
    fam, model = cell["family"], cell["config_spec"]["model"]
    assert set(fam.MODEL_KEYS) <= set(PUBLISHED) and "family" not in model
    net = fam.serving_program(model, cell["config_spec"]["engine"])      # shapes only
    assert not net.materialized() and net.config.num_hidden_layers == 5
    assert net.config.layer_types == PERIOD + PERIOD[:1]
    assert net.config.num_attention_heads_per_layer == [48, 64, 64, 64, 48]
    shapes = fam.leaf_shapes(model)
    assert shapes == program.leaf_shapes(net.config) and list(shapes) == list(net.leaf_shapes())
    assert sum(fam.is_gain(n, s) for n, s in shapes.items()) == 5 * 2 + 1
    assert not any("e_score_correction_bias" in n for n in shapes)      # no bias leaf
    params = sum(int(__import__("math").prod(s)) for s in shapes.values())
    assert round(params / 1e9, 2) == 3.87                               # 7.74 GB in bf16
    toy = dict(model, **TOY)
    assert fam.leaf_shapes(toy) == program.leaf_shapes(fam.program_config(toy, 128))
    assert not hasattr(fam, "train_step") and not hasattr(fam, "training_program")


def test_the_familys_operation_counts_written_down_by_hand():
    from benchmark import window_moe_flops as F
    cell = Spec().cell(CELL)
    fam, model = cell["family"], cell["config_spec"]["model"]
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    slide = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    sparse = 2048 * 256 + 9 * 3 * 2048 * 512         # router, 8 experts and the shared one
    params = 2 * full + 3 * slide + 4 * sparse + 3 * 2048 * 8192
    assert fam.token_matmul_params(model) == params
    head = 2 * 2048 * 100352
    # one decode token after 4096: 4097 keys in a full layer, 512 in a sliding one
    attn = 4 * 128 * (2 * 48 * 4097 + 3 * 64 * 512)
    assert fam.forward_flops(model, 1, 4096) == 2 * params + head + attn
    # a 64-token chunk from the start: every token under the window
    assert F.pairs(model, 64, 0) == {"full_attention": 64 * 65 / 2, "sliding_attention": 64 * 65 / 2}
    # 1000 tokens after 200: 311 tokens see 201..511 keys, the rest 512
    assert F.pairs(model, 1000, 200)["sliding_attention"] == \
        sum(min(200 + i + 1, 512) for i in range(1000))
    assert F.pairs(model, 1000, 200)["full_attention"] == 1000 * 200 + 1000 * 1001 / 2
    row = {"prompt_len": 4400, "cached": 4096, "token_times": [0.0, 0.1, 0.2]}
    assert fam.request_flops(model, row) == (fam.forward_flops(model, 304, 4096, 1),
                                             fam.forward_flops(model, 2, 4400))
    # the kernel's bytes: K and V of 8 heads x 128 in bf16 a counted position
    assert F.kv_read_bytes(model, 1000, 3000) == 4000 * 2 * 8 * 128 * 2
    assert F.kv_read_flops(model, 1000, 3000) == 4 * 128 * (1000 * 48 + 3000 * 64)
    assert F.held_bytes(10, 4, {"global": 524288, "window": 786432}) == 10 * 524288 + 4 * 786432


def _observation(kinds, spans, interval=(1.0, 3.0)):
    n = len(kinds)
    return {"kind": "serve", "window_s": 4.0, "chips": 1, "model_flops": 1e13,
            "model": Spec().cell(CELL)["config_spec"]["model"],
            "peak": Spec().peak("TPU v5e"), "spans": spans, "trace_interval": interval,
            "requests": [{"prompt_len": 4400, "cached": 1024, "token_times": [1.0]},
                         {"prompt_len": 4000, "cached": 0, "token_times": [2.0]},
                         {"prompt_len": 100, "cached": 64, "token_times": []}],
            "overhead": {
                "model_counts": {
                    "kind": kinds, "layer_calls": [4] * n, "pairs": [4 * 512] * n,
                    "experts_hit": [4 * 200] * n, "max_expert_pairs": [4 * 6] * n,
                    "kv_tokens_read_global": [9000 if k == "prefill" else 120_000 for k in kinds],
                    "kv_tokens_read_window": [1500 if k == "prefill" else 40_000 for k in kinds]},
                "kv_pages_held": {"global": 90_000, "window": 9_000, "turns": 100},
                "kv_pages_if_all_global": 90_000,
                "kv_page_bytes": {"global": 524288, "window": 786432},
                "window_pages_released": 700, "prefix_hits_cut_by_window": 2},
            "device_trace": {"busy_s": 1.6, "window_s": 2.0, "chips_traced": 1, "ops": [
                {"name": "jit__decode_n/ragged-dot-none.3 custom-call bf16[256,512]",
                 "seconds": 0.5, "count": 36},
                {"name": "jit__chunk_program/ragged-dot-none.7 custom-call bf16[512,512]",
                 "seconds": 0.3, "count": 18},
                {"name": "jit__decode_n/paged_attention.14 custom-call bf16[32,8,8,128]",
                 "seconds": 0.02, "count": 14},
                {"name": "jit__chunk_program/paged_attention.2 custom-call bf16[1,8,512,128]",
                 "seconds": 0.06, "count": 7},
                {"name": "jit__decode_n/fusion.4 fusion bf16[32,100352]", "seconds": 0.2,
                 "count": 2}]}}


def test_the_window_moe_readers_read_the_counts_the_census_and_the_trace():
    spec = Spec()
    kinds = ["prefill", "prefill", "decode", "prefill", "decode"]
    spans = [("prefill", 0.1, 0.5, 2), ("decode", 1.1, 1.2, None),
             ("prefill", 1.3, 1.4, 1), ("decode", 2.9, 3.5, None)]
    obs = _observation(kinds, spans)
    mine = ("moe_share.codemix", "moe_expert_roofline.codemix",
            "expert_load_max_over_mean.codemix", "paged_attn_roofline.codemix",
            "attn_share.codemix", "kv_held_vs_all_global.codemix", "prefix_hit_share.codemix")
    reported = {m["name"] for m in spec.per_layer(CELL)}
    assert set(mine) < reported and len(reported) == 15
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in spec.per_layer(CELL))
    read = {name: spec.reader(name)(obs) for name in mine}
    assert read["expert_load_max_over_mean.codemix"] == pytest.approx(256 * 6 / 512)
    assert read["moe_share.codemix"] == pytest.approx(100 * 0.8 / 1.6)
    assert read["attn_share.codemix"] == pytest.approx(100 * 0.08 / 1.6)   # both programs' calls
    assert read["prefix_hit_share.codemix"] == pytest.approx(100 * 1024 / 8400)
    # inside (1.0, 3.0]: the decode call that ended at 1.2 and the chunk at 1.4
    byte_s = 2 * 4 * 200 * 3 * 2048 * 512 * 2 / 819e9
    assert read["moe_expert_roofline.codemix"] == pytest.approx(100 * byte_s / 0.8)
    # the one traced decode call's walks: 160k positions x 4096 B over the
    # kernel's seconds in the decode program alone
    assert read["paged_attn_roofline.codemix"] == pytest.approx(
        100 * (160_000 * 4096 / 819e9) / 0.02)
    assert read["kv_held_vs_all_global.codemix"] == pytest.approx(
        100 * (90_000 * 524288 + 9_000 * 786432) / (90_000 * (524288 + 786432)))
    assert all(0 < read[k] < 100 for k in ("moe_expert_roofline.codemix",
                                           "paged_attn_roofline.codemix",
                                           "kv_held_vs_all_global.codemix"))
    # nothing to read: another model's run, the parent's run, or records that disagree
    latent = dict(obs, overhead={"model_counts": {
        k: v for k, v in obs["overhead"]["model_counts"].items() if not k.startswith("kv_")}})
    for broken in (dict(obs, overhead={"calls": {}}), dict(obs, overhead=None),
                   dict(obs, spans=spans[:-1]), latent):
        for name in ("paged_attn_roofline.codemix", "attn_share.codemix",
                     "kv_held_vs_all_global.codemix"):
            if "spans" in broken and broken["spans"] is not spans and "kv_held" in name:
                continue            # the census does not go by the calls
            assert spec.reader(name)(broken) is None, name
    untraced = {k: v for k, v in obs.items() if k not in ("device_trace", "trace_interval")}
    assert spec.reader("paged_attn_roofline.codemix")(untraced) is None
    assert spec.reader("attn_share.codemix")(untraced) is None
    assert spec.reader("kv_held_vs_all_global.codemix")(untraced) == pytest.approx(46.0)
    # the decode-heavy cell reads through the readers that were there
    heavy = {m["name"] for m in spec.per_layer("serve_decode_heavy")}
    assert heavy == {f"{n}.decode" for n in ("mfu", "paged_attn_roofline", "decode_step_ms",
                                             "decode_occupancy", "device_idle_share",
                                             "call_dispatch_share")}
    for name in heavy:
        assert not spec.metric_file(name)["reader"].startswith("window_moe")


@pytest.fixture(scope="module")
def toy_codemix_spec(tiny_root, tmp_path_factory):
    """The cell at toy size, added to a copy of the tests' benchmark as new
    files and entries, as a new configuration is."""
    top = tmp_path_factory.mktemp("codemix")
    root = top / "benchmark"
    shutil.copytree(tiny_root, root)
    cfg = json.loads((root / "configs/laguna-xs.2-serve-l5.json").read_text())
    cfg.update(TOY, engine=TOY_ENGINE)
    (root / "configs/toy_codemix.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "traffic/code_mixed_len.json").read_text())
    mix.update(rate_per_s=6.0, shape_seed=5,     # a schedule of its own, whatever the cell's is
               prompt={"dist": "lognormal", "median": 80, "sigma": 0.6, "min": 16, "max": 192},
               output={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
               shared_prefix={"share": 0.5, "groups": 2, "tokens": 64})
    (root / "traffic/toy_code.json").write_text(json.dumps(mix))
    cell = json.loads((root / f"workloads/{CELL}.json").read_text())
    # float32 against float32 at logits of order 0.1: a sound run's gap is
    # the order of the sums (1e-7); the control and the faults read 1e-4 and more
    cell.update(config="toy_codemix", traffic="toy_code",
                limits=dict(cell["limits"], served_gap_max=2e-5, served_gap_mean=2e-6))
    (root / "workloads/toy_codemix.json").write_text(json.dumps(cell))
    bench = json.loads((tiny_root.parent / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy_codemix", "config": "toy_codemix",
                               "traffic": "toy_code", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("toy_codemix")
    (top / "BENCHMARK.json").write_text(json.dumps(bench))
    return Spec(root)


def _info(capsys):
    line = next(l for l in capsys.readouterr().out.splitlines() if l.startswith("info "))
    return json.loads(line[5:])


def test_the_cell_runs_at_toy_size_and_its_reference_judges_it(toy_codemix_spec, capsys):
    """The program passes; the int8 control, the planted ``window_ignored``
    (sliding layers attend to everything) and an altered token each fail one
    of the cell's limits."""
    out = run_cell(toy_codemix_spec, "toy_codemix", 2147484001, 2.0, False,
                   require_chip=False, control="int8")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 12
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    info = _info(capsys)
    assert info["tokens_compared"] > 20
    checks = {k: out["checks"][k] for k in ("served_gap_max", "served_gap_mean")}
    limits = {k: c["limit"] for k, c in checks.items()}
    # room on both sides: the program a third of each limit at most, each
    # fault twice the limit it fails at least
    assert all(3 * c["value"] < c["limit"] for c in checks.values()), checks
    assert info["control"]["served_gap_mean"] > 2 * limits["served_gap_mean"], info["control"]
    out = run_cell(toy_codemix_spec, "toy_codemix", 2147484001, 2.0, False,
                   require_chip=False, control="window_ignored")
    ignored = _info(capsys)["control"]
    assert out["correct"] and ignored["served_gap_max"] > 2 * limits["served_gap_max"], ignored
    broken = run_cell(toy_codemix_spec, "toy_codemix", 2147484001, 2.0, False,
                      require_chip=False, fault="token_altered")
    assert not broken["correct"] and not broken["checks"]["served_gap_max"]["ok"]
