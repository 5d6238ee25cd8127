"""What only a two-kind (window and global) cache has under the fused lane
call, beside ``test_serving_lane_fused_windowed.py`` (a file of their own,
so that another worker runs them: each builds a factory of its own): pages
published before they are given back, and the window pool's floor.
"""
import numpy as np
import pytest

from paddle_tpu.ops.pallas.paged_attention import window_ring
from paddle_tpu.serving import Request, ServingEngine

from test_serving_lane_fused import COSTS, _windowed


def test_a_four_chunk_call_publishes_its_window_pages_before_they_go_back():
    """A two-kind cache under a factory that states 4 (the tiny kernel runs
    any width in interpret mode): window 8 over pages of 4 is TWO pages, so
    a four-page call writes pages that are behind the window when it
    returns.  They are keyed before they are given back (a later ask of
    the same document resumes from them), the census holds every turn, and
    after each call the row holds no more than its ring."""
    net = _windowed()
    page, window = 4, net.config.sliding_window
    serving = net.serving_decode_factory(
        max_len=192, page_size=page, n_pool_pages=8 * 48 + 1, batch_capacity=8,
        chunked_prefill=page, n_window_pages=8 * 7 + 1 + 40, window_slack=3 * page)
    serving.chunked_prefill_widest_ = 4
    eng = ServingEngine(serving=serving, slots=8, policy="paged",
                        prefill_chunk_budget=4, clock="fixed", fixed_costs=COSTS)
    assert eng._lane_widest == 4 and eng._window_slack == 3 * page
    ring = window_ring(window, page, eng._window_slack)
    assert ring == window // page + 4                    # the window and the call's pages
    log = []
    give_back = eng._window_give_back

    def spy(book, sid, next_pos):
        held = sum(1 for p in book.window_table(sid) if p)
        published = book._pub.get(sid, (0, 0))[0]
        n = give_back(book, sid, next_pos)
        after = sum(1 for p in book.window_table(sid) if p)
        log.append((sid, next_pos, held, published, after))
        assert book.census_ok()
        return n
    eng._window_give_back = spy
    rng = np.random.default_rng(11)
    doc = tuple(int(t) for t in rng.integers(1, 250, 12 * page))
    tail = lambda n: tuple(int(t) for t in rng.integers(1, 250, n))  # noqa: E731
    reqs = [Request(rid="a", arrival=0.0, prompt=doc + tail(5), max_new_tokens=4,
                    prefix_group=None),
            Request(rid="b", arrival=60.0, prompt=doc + tail(9), max_new_tokens=4,
                    prefix_group=None)]
    res = eng.run(reqs)
    calls = [(sid, pos, held, pub, after) for sid, pos, held, pub, after in log
             if sid == "a" and pos % page == 0 and pos <= 12 * page]
    assert [pos for _, pos, *_ in calls] == [4 * page, 8 * page, 12 * page]
    for _, pos, held, published, after in calls:
        assert held <= ring                               # window + width, in the call
        assert published == pos // page                   # every page of the call keyed first
        assert after <= window // page                    # back inside the window
    assert res.cache_stats["invariant_ok"]
    assert res.prefix_cached["b"] == 12 * page            # both kinds of page were there
    assert res.cache_stats["prefix_hits_cut_by_window"] == 0
    # what a chunk a call serves
    eng1 = ServingEngine(serving=serving, slots=8, policy="paged",
                         prefill_chunk_budget=4, clock="fixed", fixed_costs=COSTS)
    eng1._lane_widest = 1
    assert eng1.run(reqs).outputs == res.outputs


def test_the_window_pools_floor_takes_the_lane_calls_pages():
    net = _windowed()
    page, window = 4, net.config.sliding_window
    # a budget of 4: the factory states 2, a decode step's ring holds such a
    # call's pages too and the floor is what it was (tests/test_laguna.py
    # holds the budget of 2 to it); a pool under slots x ring is refused
    eng = ServingEngine(net, slots=4, max_len=96, page_size=page, n_pool_pages=120,
                        policy="paged", prefill_chunk_budget=4, clock="fixed",
                        n_window_pages=4 * 4 + 1)
    assert eng._lane_widest == 2
    assert window_ring(window, page, eng._window_slack) == 4
    with pytest.raises(ValueError, match="slots x ring"):
        ServingEngine(net, slots=4, max_len=96, page_size=page, n_pool_pages=120,
                      policy="paged", prefill_chunk_budget=4, clock="fixed",
                      n_window_pages=4 * 4)
