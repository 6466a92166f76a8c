"""The yardstick's own tests (benchmark/tests/) as part of tier-1: a
re-export, so that a change under benchmark/ — or to what the benchmark
reads of the program — is tested with everything else. The tests live with
the benchmark and run alone with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib.util
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "tests")


def _is_fixture(obj) -> bool:
    return (type(obj).__name__ == "FixtureFunctionDefinition"  # pytest >= 8.4
            or hasattr(obj, "_pytestfixturefunction"))


def _reexport(filename: str) -> None:
    """The tests of benchmark/tests/<filename>, and the fixtures they ask
    for, under this module's name."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + filename[:-3], os.path.join(_DIR, filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name, obj in vars(mod).items():
        if name.startswith("test_") or _is_fixture(obj):
            assert name not in globals(), f"{name}: in two files"
            globals()[name] = obj


for _f in sorted(os.listdir(_DIR)):
    if _f.startswith("test_") and _f.endswith(".py"):
        _reexport(_f)

# pins the EXACT `workloads` lists of PR 24's eight metrics and their place
# at the end of BENCHMARK.json: true until a PR appends a cell or a metric,
# which the benchmark's contract allows and this PR does. The file is the
# benchmark's own (no PR but a `benchmark` one edits it);
# test_cells_of_this_pr_are_declared_and_only_appended holds what still
# has to hold.
del test_every_new_metric_is_declared_with_its_cells  # noqa: F821

# pins the Xing4.0 configuration and cell to the LAST place of BENCHMARK.json's
# lists (`configs[-1]`, `workloads[-1]`, the cell LAST in every metric's list):
# true until a later PR appends a configuration and a cell, which the
# benchmark's contract allows and PR 35 does (the file is the benchmark's own).
# Every other assertion of it is held, with Xing4.0 found by name, by
# test_the_xing4_cell_is_declared_as_pr33_left_it (test_olmo_hybrid_block.py).
del test_the_cell_is_declared_and_only_appended  # noqa: F821

# pin what PR 39 appends to: `len(names) == 54` per-layer metrics, and the
# EXACT set of metrics that list the hybrid cell — true until a PR appends a
# metric that lists it, which the benchmark's contract allows and PR 39 does
# (five serve-front readers behind the 54; the file is the benchmark's own).
# Every other assertion of both is held, with the 54 names in their order as
# a prefix and the cell's set as "PR 35's plus the five", by
# test_benchmark_json_is_the_parents_plus_the_five_of_pr39 and
# test_the_hybrid_cell_keeps_its_metrics_and_gains_the_five_of_pr39
# (test_request_clock_readers.py).
del test_benchmark_json_is_the_parents_plus_appended_entries  # noqa: F821
del test_the_hybrid_cell_is_declared_with_its_metrics  # noqa: F821

# pin a LAST place that PR 42's appended configuration, cell and thirteen
# readers take: the exact list of configurations and cells and
# `names == accepted + the five` (the first), "behind Xing4.0's cell only the
# hybrid cell" (`serve_tokens_per_s` now ends with PR 42's cell: the second),
# and `per_layer[54:]` read as "PR 39's five" (the third). The files are the
# benchmark's own. Every other assertion of the three is held by
# test_benchmark_json_is_the_parents_plus_appended_entries_pr42,
# test_the_xing4_cell_is_declared_as_pr33_left_it_pr42 and
# test_the_hybrid_cell_keeps_its_metrics_pr42 (test_deepseek_v2_block.py).
del test_benchmark_json_is_the_parents_plus_the_five_of_pr39  # noqa: F821
del test_the_xing4_cell_is_declared_as_pr33_left_it  # noqa: F821
del test_the_hybrid_cell_keeps_its_metrics_and_gains_the_five_of_pr39  # noqa: F821
