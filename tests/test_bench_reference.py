"""The benchmark's reference child under tier-1 (PERF.md section 7 ii, ISSUE 28).

``bench/tests/test_reference_child.py`` holds the checks (the child as a
subprocess on probes of an in-process tiny engine, the references against the
program's exact mixture, the child's tree bit-equal to a serving engine's, the
stopgap's refusals); a ``benchmark`` PR may add no file under ``tests/``, so
they ran by hand alone. Here they are collected as they stand, and each new
reference (``moe_early_router_gqa``, PR 28; ``sambay_diff``, PR 32) is a further
case of the first. PR 32's file checks (``bench/tests/test_phi4flash_files.py``)
are collected the same way.
"""

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "bench"
sys.path.insert(0, str(BENCH))  # the checks import ``reference`` as run.py's children do

_spec = importlib.util.spec_from_file_location("bench_test_reference_child",
                                               BENCH / "tests" / "test_reference_child.py")
child = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(child)

# The nine checks, collected here under their own names and parameters.
globals().update({name: fn for name, fn in vars(child).items() if name.startswith("test_")})

_spec = importlib.util.spec_from_file_location("bench_test_phi4flash_files",
                                               BENCH / "tests" / "test_phi4flash_files.py")
files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(files)
globals().update({name: fn for name, fn in vars(files).items() if name.startswith("test_")})

child.CONFIGS["moe_early_router_gqa"] = BENCH / "configs" / "smallthinker-21b-a3b-int8.json"
child.CONFIGS["sambay_diff"] = BENCH / "configs" / "phi-4-mini-flash-int8.json"


@pytest.mark.parametrize("name", ["moe_early_router_gqa", "sambay_diff"])
def test_the_new_references_child_says_ok_on_probes_of_a_tiny_engine(name, tmp_path):
    """SmallThinker's tiny preset (one period, window 16 under probes of 40 to
    225 prompt tokens, 8 experts top-3), and Phi-4-mini-flash's (eight layers of
    all five kinds, window 16, the state store beside the pages), each served
    through the paged engine, then ``bench/reference.py --rehearse-cpu`` on the
    configuration's own file."""
    child.test_reference_child_says_ok_on_probes_of_a_tiny_engine(name, tmp_path)


@pytest.mark.parametrize("preset", ["smallthinker-test-tiny", "phi4flash-test-tiny"])
def test_the_new_configurations_child_builds_the_sidecars_tree(preset):
    child.test_the_childs_weights_are_the_serving_engines_bit_for_bit(preset, "int8")
