"""The fused lane call's cases over the two-kind (window and global) model:
those of ``test_serving_lane_fused.py``, collected here under this file's
``factory`` (a file of their own, so that another worker runs them: the
interpreted kernel makes each factory of this model cost tens of seconds).
What only a two-kind cache has is in ``test_serving_lane_fused_window_pool.py``.
"""
import pytest

from test_serving_lane_fused import (  # noqa: F401  (tests and fixtures are collected by name)
    FACTORIES, pair,
    test_a_budget_of_four_serves_the_tokens_of_one_chunk_a_call,
    test_every_width_is_compiled_when_the_engine_is_built,
    test_one_span_and_one_count_a_program_call,
    test_the_request_chunk_order_is_unchanged_and_the_aging_rule_trips,
    test_the_virtual_time_of_the_trace_is_unchanged)


@pytest.fixture(scope="module", params=["windowed"])
def factory(request):
    build, page, long_chunks, widest, kw = FACTORIES[request.param]
    return request.param, build(), page, long_chunks, widest, kw
