"""The benchmark's trace reduction for serve cells, collected by tier-1.

`chipbench/kinds/serve.py` traces a slice of a run's window through a hook
inside the replica and reads `TPUEngine.stats()` at the slice's two ends
(`loop.thread_s`, the `cache` counters the kernels' roofline readers divide
by), and `chipbench/trace_reduce.py` names the gaps of a device trace by the
engine's `ray_tpu:engine:*` spans; tier-1 collects `tests/` only, so a change
of those counters or span names has to fail here, on the CPU, and not in a
traced chip run. The cases are `chipbench/tests/test_trace_serve.py`'s own,
imported and not copied (as `tests/test_chipbench_check.py` imports the
check's)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench", "tests"))

from test_trace_serve import (  # noqa: E402,F401
    test_a_cut_or_missing_device_trace_is_an_error_on_the_chip,
    test_a_hook_that_never_starts_is_an_error,
    test_a_kernel_below_the_ten_largest_ops_keeps_its_metric,
    test_a_late_answer_does_not_hold_the_slice_open,
    test_a_serve_traffic_file_without_a_trace_group_fails_a_traced_run,
    test_a_stop_trace_that_never_returns_is_an_error,
    test_every_serve_traffic_file_names_its_slice,
    test_gaps_are_named_by_the_engines_spans_too,
    test_recorded_slice_marks_its_kernels,
    test_the_slice_reads_the_counters_at_its_two_ends,
    test_the_work_is_counted_over_the_slice_and_not_the_window,
)
