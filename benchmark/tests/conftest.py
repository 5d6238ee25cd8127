"""The benchmark's own tests: CPU, toy sizes, eight virtual devices.

Run them with ``python -m pytest benchmark/tests -q`` from the repo's root.
"""
import json
import os
import shutil
import sys
from pathlib import Path

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

TINY_MODEL = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=64)
TINY_ENGINE = {"slots": 4, "max_len": 384, "page_size": 16, "n_pool_pages": 97,
               "policy": "paged", "prefill_chunk_budget": 2}


def _dump(path: Path, obj):
    path.write_text(json.dumps(obj, indent=2) + "\n")


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A copy of the benchmark elsewhere with three toy cells of the family
    that is there, and a second family with a serving and a training cell,
    ADDED to it as files and as entries: nothing that was there is edited."""
    top = tmp_path_factory.mktemp("bench")
    root = top / "benchmark"
    shutil.copytree(REPO / "benchmark", root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "configs/mistral-7b-v0.3-serve-l8.json").read_text())
    cfg.update(TINY_MODEL, engine=TINY_ENGINE)
    _dump(root / "configs/tiny.json", cfg)
    mix = json.loads((root / "traffic/chat_steady.json").read_text())
    mix.update(rate_per_s=6.0,
               prompt={"dist": "lognormal", "median": 48, "sigma": 0.6, "min": 16, "max": 192},
               output={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
               shared_prefix={"share": 0.4, "groups": 2, "tokens": 32})
    _dump(root / "traffic/tiny_chat.json", mix)
    for name, like in (("tiny_job", "s4096_b2"), ("tiny_job4", "s4096_b4_2x2")):
        job = json.loads((root / f"traffic/{like}.json").read_text())
        job.update(seq=512)
        _dump(root / f"traffic/{name}.json", job)
    here = Path(__file__).parent
    shutil.copy(here / "toy_family.py", root / "families/toy.py")
    shutil.copy(here / "toy_reference.py", root / "reference/toy.py")
    toy = dict(cfg, family="toy", final_gain=True, engine=dict(TINY_ENGINE, decode_chunk=2))
    _dump(root / "configs/toy.json", toy)
    _dump(root / "configs/toy_wide.json", dict(toy, num_attention_heads=6))   # 6 x 64 != 256
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, config, traffic, like, chips in (
            ("tiny_serve", "tiny", "tiny_chat", "serve_chat_steady", 1),
            ("tiny_train", "tiny", "tiny_job", "train_s4096", 1),
            ("tiny_train4", "tiny", "tiny_job4", "train_2x2_s4096", 4),
            ("toy_serve", "toy", "tiny_chat", "serve_chat_steady", 1),
            ("toy_train", "toy", "tiny_job", "train_s4096", 1),
            ("toy_wide_serve", "toy_wide", "tiny_chat", "serve_chat_steady", 1)):
        cell = json.loads((root / f"workloads/{like}.json").read_text())
        cell.update(config=config, traffic=traffic, chips=chips)
        _dump(root / f"workloads/{name}.json", cell)
        bench["workloads"].append({"name": name, "config": config, "traffic": traffic,
                                   "chips": chips, "why": "toy"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    _dump(top / "BENCHMARK.json", bench)
    assert all(p.read_bytes() == b for p, b in before.items()), "a file that was there changed"
    return root


@pytest.fixture(scope="session")
def tiny_spec(tiny_root):
    from benchmark.harness.spec import Spec
    return Spec(tiny_root)
