"""The ``kimi_linear`` family (Kimi-Linear-48B-A3B-Instruct) added to the
benchmark as new files: its configuration against the published keys, its
leaves against the program's ``state_dict``, its operation and byte counts
written down by hand, the new readers on a hand-made ``obs``, and the cell
at toy size on the CPU, where the plain reference passes the program and
fails the int8 control, the planted ``state_dropped`` and ``decay_ignored``
and an altered token."""
import json
import math
import shutil
import subprocess

import pytest

from benchmark.harness.spec import Spec
from benchmark.run import run_cell
from conftest import REPO

CELL = "serve_linear_latent_agent"
CONFIG = "kimi-linear-48b-a3b-serve-l9"
# Kimi-Linear-48B-A3B-Instruct's config.json as the catalog has it
# (model-configs/architectures.jsonl), written down here
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
CUT = {"num_hidden_layers": 9, "num_experts": 64, "vocab_size": 40960}
TOY = dict(vocab_size=512, hidden_size=32, intermediate_size=96, moe_intermediate_size=8,
           num_attention_heads=4, num_key_value_heads=4, head_dim=8, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, num_hidden_layers=5,
           num_experts=4, router_width=16, experts_held=[4, 5, 6, 7], num_experts_per_token=4,
           linear_attn_config={"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                               "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4})
TOY_ENGINE = {"slots": 4, "max_len": 384, "page_size": 8, "n_pool_pages": 257,
              "n_state_snapshots": 6, "state_snapshot_every": 32, "policy": "paged",
              "prefill_chunk_budget": 4}


def test_the_configuration_keeps_every_published_key_but_its_three_cuts():
    cfg = json.loads((REPO / f"benchmark/configs/{CONFIG}.json").read_text())
    assert cfg["reduced"] == list(CUT)
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    assert {k: cfg[k] for k in PUBLISHED} == dict(PUBLISHED, **CUT)
    assert cfg["family"] == "kimi_linear" and all(cfg.get(k) for k in ("stands_for", "note"))
    assert cfg["router_width"] == 256 and cfg["experts_held"] == list(range(64))
    assert {"gate_rank", "conv_activation", "qk_norm", "decay_form", "output_gate",
            "state_dtype", "torch_dtype", "e_score_correction_bias", "drawn_decay",
            "router_width"} <= set(cfg["assumed"])
    assert cfg["assumed"]["drawn_decay"]["dt_bias_shift"] == -5.5
    assert "four-chip host" in cfg["stands_for"] and "three such hosts" in cfg["stands_for"]
    assert cfg["engine"] == {"slots": 64, "max_len": 17984, "page_size": 64,
                             "n_pool_pages": 8193, "n_state_snapshots": 96,
                             "state_snapshot_every": 1024, "policy": "paged",
                             "prefill_chunk_budget": 4}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "9 of 27 layers, 64 of 256 experts, 1/4 vocabulary" in entry["why"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    tok_s = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in tok_s["workloads"] and tok_s["bound"] == 0.05


def test_the_cells_traffic_is_the_issues_letter_for_letter():
    mix = json.loads((REPO / "benchmark/traffic/agent_long_answers.json").read_text())
    assert {k: mix[k] for k in ("kind", "burst", "prompt", "output", "shared_prefix",
                                "greedy", "order")} == {
        "kind": "serve_open_loop", "burst": 1,
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 256, "max": 16384},
        "output": {"dist": "lognormal", "median": 640, "sigma": 0.6, "min": 128, "max": 1536},
        "shared_prefix": {"share": 0.5, "groups": 4, "tokens": 2048},
        "greedy": True, "order": "fixed"}
    # the longest request and the engine's slack fit the tables
    assert 16384 + 1536 + 64 <= 17984 and 17984 % 64 == 0


def test_no_benchmark_file_that_was_there_changed():
    """Against the parent commit, while this PR's tree stands on it:
    everything this family brings is a new file, and ``BENCHMARK.json`` only
    gains entries (``tiny_root`` checks its copy on every run besides)."""
    def git(*args):
        return subprocess.run(["git", *args], cwd=REPO, capture_output=True, text=True)
    base = "04b27dba8ed7e285125541dbaf706c358d996e06"
    if git("rev-parse", "HEAD").stdout.strip() != base:
        pytest.skip("not the working tree of the PR that added the family (its parent is not HEAD)")
    names = git("diff", "--name-status", base, "--", "benchmark").stdout.split("\n")
    assert not [n for n in names if n and not n.startswith("A")], names
    was = json.loads(git("show", f"{base}:BENCHMARK.json").stdout)
    now = json.loads((REPO / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "per_layer"):
        assert now[key][:len(was[key])] == was[key], key
    tok_s = lambda b: next(m for m in b["end_to_end"] if m["name"] == "serve_tok_s")  # noqa: E731
    assert tok_s(now)["workloads"][:len(tok_s(was)["workloads"])] == tok_s(was)["workloads"]
    assert [m for m in now["end_to_end"] if m["name"] != "serve_tok_s"] \
        == [m for m in was["end_to_end"] if m["name"] != "serve_tok_s"]
    assert {k: v for k, v in now.items() if k not in ("configs", "workloads", "per_layer",
                                                     "end_to_end")} \
        == {k: v for k, v in was.items() if k not in ("configs", "workloads", "per_layer",
                                                     "end_to_end")}


def test_the_familys_leaves_are_the_programs_state_dict():
    from paddle_tpu.models.nlp import kimi_linear as program
    cell = Spec().cell(CELL)
    fam, model = cell["family"], cell["config_spec"]["model"]
    assert set(fam.PUBLISHED_KEYS) <= set(PUBLISHED) and "family" not in model
    net = fam.serving_program(model, cell["config_spec"]["engine"])      # shapes only
    assert not net.materialized() and net.config.num_hidden_layers == 9
    assert [net.config.is_kda(i) for i in range(9)] == [1, 1, 1, 0, 1, 1, 1, 0, 1]
    assert net.config.num_experts == 256 and net.config.n_held == 64
    shapes = fam.leaf_shapes(model)
    assert list(shapes) == list(net.leaf_shapes()) and shapes == net.leaf_shapes()
    assert shapes["model.layers.1.mlp.gate.weight"] == (2304, 256)
    assert shapes["model.layers.1.mlp.experts.gate_proj"] == (64, 2304, 1024)
    # gains: two norms a layer, the final one, a KDA layer's o_norm, an MLA
    # layer's kv_a_layernorm; dt_bias, A_log and the selection bias are not
    assert sum(fam.is_gain(n, s) for n, s in shapes.items()) == 9 * 2 + 1 + 7 + 2
    params = sum(math.prod(s) for s in shapes.values())
    assert round(params / 1e9, 2) == 4.27                               # 8.55 GB in bf16
    kda = sum(math.prod(s) for n, s in shapes.items() if n.startswith("model.layers.0.self_attn"))
    mla = sum(math.prod(s) for n, s in shapes.items() if n.startswith("model.layers.3.self_attn"))
    assert round(kda / 1e6, 1) == 39.5 and round(mla / 1e6, 1) == 29.1
    toy = dict(model, **TOY)
    assert fam.leaf_shapes(toy) == program.leaf_shapes(fam.program_config(toy, 128))
    assert not hasattr(fam, "train_step") and not hasattr(fam, "training_program")


def test_the_decay_shift_is_the_references_and_the_familys_alike():
    import jax.numpy as jnp
    fam = Spec().family("kimi_linear")
    drawn = {"model.layers.0.self_attn.dt_bias": jnp.asarray([0.02, -0.01], jnp.bfloat16),
             "model.layers.0.self_attn.A_log": jnp.asarray([0.03], jnp.bfloat16)}
    w = fam.R.layer_weights(drawn, 0)
    assert w["self_attn.dt_bias"].dtype == jnp.bfloat16
    assert [float(v) for v in w["self_attn.dt_bias"]] == [-5.46875, -5.5]
    assert float(w["self_attn.A_log"][0]) == float(drawn["model.layers.0.self_attn.A_log"][0])

    class Net:
        def load_tree(self, tree):
            self.tree = dict(tree)
    net = Net()
    fam.load_weights(net, dict(drawn))
    assert all(bool((net.tree[f"model.layers.0.{k}"] == v).all()) for k, v in w.items())


def test_the_familys_operation_counts_written_down_by_hand():
    from benchmark import linear_latent_flops as K
    cell = Spec().cell(CELL)
    fam, model = cell["family"], cell["config_spec"]["model"]
    assert K.kda_layers(model) == 7 and K.mla_layers(model) == 2
    kda = 4 * 2304 * 4096 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    mla = 2304 * 32 * 192 + 2304 * 576 + 32 * 128 * 512 + 32 * 512 * 128 + 32 * 128 * 2304
    sparse = 2304 * 256 + (2 + 1) * 3 * 2304 * 1024      # router, 2 held pairs, the shared one
    params = 7 * kda + 2 * mla + 8 * sparse + 3 * 2304 * 9216
    assert fam.token_matmul_params(model) == params
    head = 2 * 2304 * 40960
    state = 7 * 6 * 32 * 128 * 128
    # one decode token after 4096: 4097 keys in each of the 2 latent layers
    assert fam.forward_flops(model, 1, 4096) == 2 * params + state + head \
        + 2 * 2 * 32 * 320 * 4097
    row = {"prompt_len": 2400, "cached": 2048, "token_times": [0.0, 0.1, 0.2]}
    assert fam.request_flops(model, row) == (fam.forward_flops(model, 352, 2048, 1),
                                             fam.forward_flops(model, 2, 2400))
    # the kernel's bytes: a (32, 128, 128) float32 state read and written
    assert K.kda_step_bytes(model, 7 * 48) == 7 * 48 * 2 * 4 * 32 * 128 * 128
    assert K.kda_step_flops(model, 7 * 48) == 7 * 48 * 6 * 32 * 128 * 128
    assert K.state_entry_bytes(model) == 7 * (4 * 32 * 128 * 128 + 2 * 3 * 3 * 4096)
    assert round(K.state_entry_bytes(model) / 1e6, 1) == 15.2
    assert K.latent_decode_bytes(model, 1000) == 1000 * 576 * 2


def _observation(kinds, spans, interval=(1.0, 3.0)):
    n = len(kinds)
    return {"kind": "serve", "window_s": 4.0, "chips": 1, "model_flops": 1e13,
            "prefill_tokens": 3 * 256, "model": Spec().cell(CELL)["config_spec"]["model"],
            "peak": Spec().peak("TPU v5e"), "spans": spans, "trace_interval": interval,
            "requests": [{"prompt_len": 2400, "cached": 2048, "token_times": [1.0]},
                         {"prompt_len": 4000, "cached": 0, "token_times": [2.0]},
                         {"prompt_len": 100, "cached": 64, "token_times": []}],
            "overhead": {
                "slots": 64,
                "calls": {"decode": {"n": 2, "rows": 96, "dispatch_s": [0.001, 0.003]},
                          "prefill": {"n": 3, "rows": 3, "dispatch_s": [0.002, 0.002, 0.002]}},
                "phases": {"state.snapshot": {"n": 30, "self_s": 0.03, "max_s": 0.002},
                           "state.restore": {"n": 10, "self_s": 0.01, "max_s": 0.002},
                           "admit": {"n": 20, "self_s": 0.2, "max_s": 0.02}},
                "unaccounted_s": 0.1,
                "model_counts": {
                    "kind": kinds, "layer_calls": [8] * n, "pairs": [8 * 100] * n,
                    "experts_hit": [8 * 50] * n, "max_expert_pairs": [8 * 4] * n,
                    "latent_tokens_read": [0 if k == "prefill" else 2 * 150_000 for k in kinds],
                    "kda_rows_stepped": [0 if k == "prefill" else 7 * 48 for k in kinds],
                    "kda_chunk_positions": [7 * 256 if k == "prefill" else 0 for k in kinds]},
                "kv_pages_held": {"latent": 200_000, "state": 5_000, "turns": 100},
                "kv_page_bytes": {"latent": 163840, "state": 15_237_120,
                                  "latent_all_layers": 737280},
                "prefix_tokens_matched": 40_960, "prefix_tokens_cut_by_snapshot": 1024,
                "state_snapshots_taken": 30, "state_snapshots_evicted": 2,
                "prefix_hits_cut_by_snapshot": 1},
            "device_trace": {"busy_s": 1.6, "window_s": 2.0, "chips_traced": 1, "ops": [
                {"name": "jit__decode_n/ragged-dot-none.3 custom-call bf16[512,1024]",
                 "seconds": 0.5, "count": 36},
                {"name": "jit__decode_n/kda_decode_step.14 custom-call f32[64,32,128]",
                 "seconds": 0.004, "count": 14},
                {"name": "jit__decode_n/latent_paged_attention.5 custom-call bf16[64,32,512]",
                 "seconds": 0.002, "count": 4},
                {"name": "jit__chunk_program/latent_paged_attention.2 custom-call bf16[1,4096,512]",
                 "seconds": 0.03, "count": 7},
                {"name": "jit__chunk_program/fusion.9 fusion f32[1,64,64,32,128]",
                 "seconds": 0.05, "count": 28},
                {"name": "jit__chunk_program/fusion.11 fusion f32[1,32,128,128]",
                 "seconds": 0.01, "count": 28},
                {"name": "jit__chunk_program/fusion.12 fusion bf16[1,256,2304]",
                 "seconds": 0.2, "count": 9},
                {"name": "jit__decode_n/fusion.4 fusion bf16[64,40960]", "seconds": 0.2,
                 "count": 2}]}}


def test_the_linear_latent_readers_read_the_counts_the_census_and_the_trace():
    spec = Spec()
    kinds = ["prefill", "prefill", "decode", "prefill", "decode"]
    spans = [("prefill", 0.1, 0.5, 2), ("decode", 1.1, 1.2, None),
             ("prefill", 1.3, 1.4, 1), ("decode", 2.9, 3.5, None)]
    obs = _observation(kinds, spans)
    mine = ("kda_state_roofline.agent", "kda_share.agent", "mla_attn_roofline.agent",
            "cache_held_vs_all_latent.agent", "prefix_cut_by_snapshot_share.agent",
            "state_host_share.agent")
    reported = {m["name"] for m in spec.per_layer(CELL)}
    assert set(mine) < reported and len(reported) == 19
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in spec.per_layer(CELL))
    read = {name: spec.reader(name)(obs) for name in reported}
    assert all(v is not None and 0 < v for v in read.values()), read
    # inside (1.0, 3.0]: the decode call that ended at 1.2 alone of its kind:
    # 7 x 48 states of 2.1 MB read and written, over the kernel's seconds
    assert read["kda_state_roofline.agent"] == pytest.approx(
        100 * (7 * 48 * 2 * 4 * 32 * 128 * 128 / 819e9) / 0.004)
    assert read["mla_attn_roofline.agent"] == pytest.approx(
        100 * (300_000 * 576 * 2 / 819e9) / 0.002)
    # the decode kernel and the chunk program's two KDA-shaped operations
    assert read["kda_share.agent"] == pytest.approx(100 * (0.004 + 0.05 + 0.01) / 1.6)
    assert read["mla_attn_share.agent"] == pytest.approx(100 * 0.032 / 1.6)
    assert read["cache_held_vs_all_latent.agent"] == pytest.approx(
        100 * (200_000 * 163840 + 5_000 * 15_237_120) / (200_000 * 737280))
    assert read["prefix_cut_by_snapshot_share.agent"] == pytest.approx(2.5)
    assert read["state_host_share.agent"] == pytest.approx(1.0)
    assert read["expert_load_max_over_mean.agent"] == pytest.approx(64 * 4 / 100)
    assert read["prefix_hit_share.agent"] == pytest.approx(100 * 2048 / 6400)
    assert all(read[k] < 100 for k in ("kda_state_roofline.agent", "mla_attn_roofline.agent",
                                       "moe_expert_roofline.agent", "mfu.agent"))
    # nothing to read: another model's run, the parent's run, records that disagree
    other = dict(obs, overhead={"model_counts": {
        k: v for k, v in obs["overhead"]["model_counts"].items() if not k.startswith("kda_")}})
    for broken in (dict(obs, overhead={"calls": {}}), dict(obs, overhead=None),
                   dict(obs, spans=spans[:-1]), other):
        for name in ("kda_state_roofline.agent", "kda_share.agent", "mla_attn_roofline.agent"):
            assert spec.reader(name)(broken) is None, name
    for name in ("cache_held_vs_all_latent.agent", "prefix_cut_by_snapshot_share.agent"):
        assert spec.reader(name)(dict(obs, overhead={"calls": {}})) is None
        assert spec.reader(name)(dict(obs, overhead=None)) is None
    untraced = {k: v for k, v in obs.items() if k not in ("device_trace", "trace_interval")}
    assert spec.reader("kda_state_roofline.agent")(untraced) is None
    assert spec.reader("kda_share.agent")(untraced) is None


@pytest.fixture(scope="module")
def toy_agent_spec(tiny_root, tmp_path_factory):
    """The cell at toy size, added to a copy of the tests' benchmark as new
    files and entries, as a new configuration is."""
    top = tmp_path_factory.mktemp("agent")
    root = top / "benchmark"
    shutil.copytree(tiny_root, root)
    cfg = json.loads((root / f"configs/{CONFIG}.json").read_text())
    cfg.update(TOY, engine=TOY_ENGINE)
    (root / "configs/toy_agent.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "traffic/agent_long_answers.json").read_text())
    mix.update(rate_per_s=6.0, shape_seed=5,     # a schedule of its own, whatever the cell's is
               prompt={"dist": "lognormal", "median": 80, "sigma": 0.6, "min": 16, "max": 192},
               output={"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 48},
               shared_prefix={"share": 0.5, "groups": 2, "tokens": 64})
    (root / "traffic/toy_agent.json").write_text(json.dumps(mix))
    cell = json.loads((root / f"workloads/{CELL}.json").read_text())
    # float32 against float32 at logits of order 0.1: a sound run's gap is
    # the order of the sums; the control and the faults read far more
    cell.update(config="toy_agent", traffic="toy_agent",
                limits=dict(cell["limits"], served_gap_max=2e-5, served_gap_mean=2e-6))
    (root / "workloads/toy_agent.json").write_text(json.dumps(cell))
    bench = json.loads((tiny_root.parent / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy_agent", "config": "toy_agent",
                               "traffic": "toy_agent", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("toy_agent")
    (top / "BENCHMARK.json").write_text(json.dumps(bench))
    return Spec(root)


def _info(capsys):
    line = next(l for l in capsys.readouterr().out.splitlines() if l.startswith("info "))
    return json.loads(line[5:])


def test_the_cell_runs_at_toy_size_and_its_reference_judges_it(toy_agent_spec, capsys):
    """The program passes; the int8 control, the planted ``state_dropped``
    (every KDA state zeroed each 1024 positions: here each 32) and
    ``decay_ignored`` and an altered token each fail one of the cell's
    limits."""
    fam = toy_agent_spec.family("kimi_linear")
    fam.R.STATE_DROP_EVERY, every = 32, fam.R.STATE_DROP_EVERY
    # the toy program in float32 (the drawn bfloat16 values, widened): at a
    # hidden size of 32 a bfloat16 program's own rounding reads as much as
    # the int8 control's, and the test is of the comparison, not of bfloat16
    load = fam.load_weights
    fam.load_weights = lambda net, w: net.load_tree(
        {k: fam.R.shift_decay(k, v).astype("float32") for k, v in w.items()})
    try:
        out = run_cell(toy_agent_spec, "toy_agent", 2147484001, 2.0, False,
                       require_chip=False, control="int8")
        assert out["correct"] and out["failed"] == 0 and out["attempted"] == 12
        assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
        info = _info(capsys)
        assert info["tokens_compared"] > 20
        checks = {k: out["checks"][k] for k in ("served_gap_max", "served_gap_mean")}
        limits = {k: c["limit"] for k, c in checks.items()}
        assert all(3 * c["value"] < c["limit"] for c in checks.values()), checks
        assert info["control"]["served_gap_mean"] > 2 * limits["served_gap_mean"], info["control"]
        for fault in ("state_dropped", "decay_ignored"):
            out = run_cell(toy_agent_spec, "toy_agent", 2147484001, 2.0, False,
                           require_chip=False, control=fault)
            read = _info(capsys)["control"]
            assert out["correct"] and read["served_gap_max"] > 2 * limits["served_gap_max"], \
                (fault, read)
        broken = run_cell(toy_agent_spec, "toy_agent", 2147484001, 2.0, False,
                          require_chip=False, fault="token_altered")
        assert not broken["correct"] and not broken["checks"]["served_gap_max"]["ok"]
    finally:
        fam.R.STATE_DROP_EVERY, fam.load_weights = every, load
