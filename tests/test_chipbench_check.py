"""The benchmark's serve check, collected by tier-1.

`chipbench/check.py` `serve_check` drives `models/decoding_paged.py` directly
(`init_paged_state`, `insert_sequence_paged`, `decode_step_paged_ragged`, and
its `kv_page_zeroed` fault writes `state["kp"].at[:, page]`), and tier-1
collects `tests/` only: a change of those signatures or of the pool's stored
layout has to fail here, on the CPU, and not in a chip run. The cases are
`chipbench/tests/test_check_search.py`'s own, imported and not copied."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench", "tests"))

from test_check_search import (  # noqa: E402,F401
    conf, weights,
    test_a_run_that_settles_at_once_is_not_searched,
    test_a_token_routed_the_other_way_in_two_layers_settles,
    test_a_wrong_tree_fails_with_every_tie_free,
    test_an_answer_cut_short_by_eos_is_compared_where_it_is,
    test_no_flip_is_taken_over_router_tie,
    test_route_by_depth,
    test_sound_tree_passes_and_the_control_fails,
    test_the_search_ends_on_its_time_limit,
    test_the_search_settles_what_the_search_of_pr23_left_out,
    test_the_search_works_on_the_served_token_where_that_is_what_is_out,
)
