"""The traffic generator: deterministic in the seed, the stated means, and
the same work for every seed in another order."""
import json

import numpy as np

from benchmark.harness import traffic
from conftest import REPO


def _mix(name):
    return json.loads((REPO / "benchmark" / "traffic" / f"{name}.json").read_text())


def test_same_seed_same_requests_other_seed_same_work():
    mix = _mix("chat_steady")
    a = traffic.serve_requests(mix, 40.0, 2147484001, 32768)
    b = traffic.serve_requests(mix, 40.0, 2147484001, 32768)
    c = traffic.serve_requests(mix, 40.0, 7, 32768)
    assert a == b and a != c
    assert len(a) == len(c) == 88
    size = lambda rs: sorted((len(r["prompt"]), r["max_new_tokens"],
                              r["prefix_group"] is not None) for r in rs)
    assert size(a) == size(c)
    gaps = lambda rs: np.sort(np.diff([0.0] + sorted(r["arrival"] for r in rs)))
    np.testing.assert_allclose(gaps(a), gaps(c), rtol=1e-9)
    assert 0 < a[0]["arrival"] and max(r["arrival"] for r in a) < 40.0
    # chat_steady keeps one schedule and draws only the ids from the seed
    assert [(len(r["prompt"]), r["arrival"]) for r in a] == \
        [(len(r["prompt"]), r["arrival"]) for r in c]
    for how in ("shuffle", "rotate"):
        d = traffic.serve_requests(dict(mix, order=how), 40.0, 7, 32768)
        assert size(d) == size(a)
        np.testing.assert_allclose(gaps(d), gaps(a), rtol=1e-9)
        assert [len(r["prompt"]) for r in d] != [len(r["prompt"]) for r in a]


def test_stated_means_and_sharing():
    chat = traffic.summary(traffic.serve_requests(_mix("chat_steady"), 2000.0, 1, 32768))
    assert 400 < chat["prompt_mean"] < 520 and chat["prompt_max"] <= 2048
    assert 105 < chat["output_mean"] < 135 and chat["output_max"] <= 384
    assert abs(chat["in_prefix_groups"] / chat["requests"] - 0.4) < 0.01
    burst_reqs = traffic.serve_requests(_mix("prefill_burst"), 2000.0, 1, 32768)
    burst = traffic.summary(burst_reqs)
    assert 1350 < burst["prompt_mean"] < 1550 and 35 < burst["output_mean"] < 45
    assert burst["in_prefix_groups"] == 0
    times = sorted(r["arrival"] for r in burst_reqs)
    assert all(times[i] == times[i + 7] for i in range(0, len(times), 8))


def test_sessions_open_with_their_system_prompt():
    reqs = traffic.serve_requests(_mix("chat_steady"), 40.0, 5, 32768)
    by_group = {}
    for r in reqs:
        if r["prefix_group"] is not None:
            by_group.setdefault(r["prefix_group"], set()).add(r["prompt"][:256])
    assert len(by_group) == 4 and all(len(v) == 1 for v in by_group.values())
