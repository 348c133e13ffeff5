"""The benchmark's reference child under tier-1 (PERF.md section 7 ii, ISSUE 28).

``bench/tests/test_reference_child.py`` holds the checks (the child as a
subprocess on probes of an in-process tiny engine, the references against the
program's exact mixture, the child's tree bit-equal to a serving engine's, the
stopgap's refusals); a ``benchmark`` PR may add no file under ``tests/``, so
they ran by hand alone. Here they are collected as they stand, and each new
reference (``moe_early_router_gqa``, PR 28; ``sambay_diff``, PR 32) is a further
case of the first. PR 32's file checks (``bench/tests/test_phi4flash_files.py``)
are collected the same way, and PR 36's (``bench/tests/test_keye_files.py``),
PR 41's (``bench/tests/test_trinity_files.py``), PR 43's
(``bench/tests/test_lfm2_files.py``), PR 47's
(``bench/tests/test_xing4_files.py``), PR 49's reader check
(``bench/tests/test_post_warmup_compile_reader.py``), PR 50's
(``bench/tests/test_granite4_files.py``) and PR 54's
(``bench/tests/test_kimi_linear_files.py``), PR 56's
(``bench/tests/test_openpangu_files.py``) and PR 61's
(``bench/tests/test_evabyte_files.py``).
"""

import importlib.util
import json
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

sys.modules.setdefault("test_reference_child", child)  # PR 36's checks import its helpers by that name
_spec = importlib.util.spec_from_file_location("bench_test_keye_files", BENCH / "tests" / "test_keye_files.py")
keye_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(keye_files)
# Its names differ from the other files' but for two, kept apart here.
globals().update({name if name not in globals() else f"{name}_keye": fn
                  for name, fn in vars(keye_files).items() if name.startswith("test_")})

_spec = importlib.util.spec_from_file_location("bench_test_trinity_files", BENCH / "tests" / "test_trinity_files.py")
trinity_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trinity_files)
globals().update({name if name not in globals() else f"{name}_trinity": fn
                  for name, fn in vars(trinity_files).items() if name.startswith("test_")})

_spec = importlib.util.spec_from_file_location("bench_test_lfm2_files", BENCH / "tests" / "test_lfm2_files.py")
lfm2_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lfm2_files)
globals().update({name if name not in globals() else f"{name}_lfm2": fn
                  for name, fn in vars(lfm2_files).items() if name.startswith("test_")})


_spec = importlib.util.spec_from_file_location("bench_test_xing4_files", BENCH / "tests" / "test_xing4_files.py")
xing4_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(xing4_files)
globals().update({name if name not in globals() else f"{name}_xing4": fn
                  for name, fn in vars(xing4_files).items() if name.startswith("test_")})


_spec = importlib.util.spec_from_file_location("bench_test_post_warmup_compile_reader",
                                               BENCH / "tests" / "test_post_warmup_compile_reader.py")
post_warmup = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(post_warmup)
globals().update({name if name not in globals() else f"{name}_post_warmup": fn
                  for name, fn in vars(post_warmup).items() if name.startswith("test_")})

_spec = importlib.util.spec_from_file_location("bench_test_granite4_files", BENCH / "tests" / "test_granite4_files.py")
granite4_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(granite4_files)
globals().update({name if name not in globals() else f"{name}_granite4": fn
                  for name, fn in vars(granite4_files).items() if name.startswith("test_")})

_spec = importlib.util.spec_from_file_location("bench_test_kimi_linear_files", BENCH / "tests" / "test_kimi_linear_files.py")
kimi_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kimi_files)
globals().update({name if name not in globals() else f"{name}_kimi": fn
                  for name, fn in vars(kimi_files).items() if name.startswith("test_")})

_spec = importlib.util.spec_from_file_location("bench_test_openpangu_files", BENCH / "tests" / "test_openpangu_files.py")
openpangu_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(openpangu_files)
globals().update({name if name not in globals() else f"{name}_openpangu": fn
                  for name, fn in vars(openpangu_files).items() if name.startswith("test_")})


_spec = importlib.util.spec_from_file_location("bench_test_evabyte_files", BENCH / "tests" / "test_evabyte_files.py")
evabyte_files = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(evabyte_files)
globals().update({name if name not in globals() else f"{name}_evabyte": fn
                  for name, fn in vars(evabyte_files).items() if name.startswith("test_")})


def test_the_benchmark_gains_one_configuration_one_cell_and_three_readers(tmp_path, monkeypatch):
    """A STOPGAP, stated as one (PERF.md section 7 (00): the repair is the first
    item of the next ``benchmark`` issue). PR 32's check holds its entries to be
    the LAST of their lists, which the next configuration ended (PR 36's did).
    ``bench/`` is the benchmark's and is not edited from here, so the check reads
    ``BENCHMARK.json`` cut back to where PR 32 left it: everything up to and
    including its own entries, FOUND BY NAME (since PR 61: the list of every
    later PR's names that stood here had to be extended by each of them, eight
    times; what a later PR appends is held by its own file's check, by name)."""
    bench = json.loads((files.ROOT / "BENCHMARK.json").read_text())
    last = {"configs": "phi-4-mini-flash-int8", "workloads": files.CELL, "per_layer": "window_skip_share"}
    for section, name in last.items():
        names = [e["name"] for e in bench[section]]
        bench[section] = bench[section][:names.index(name) + 1]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(files, "ROOT", tmp_path)
    files.test_the_benchmark_gains_one_configuration_one_cell_and_three_readers()


def test_the_benchmark_gains_one_configuration_one_cell_and_two_readers_after_keyes(tmp_path, monkeypatch):
    """The same STOPGAP for one assertion of PR 41's check: it holds
    ``decode_step_ms`` and ``decode_batch_mean`` to list the seven cells its
    parent had and NO other, which the next cell that makes ``decode`` records
    ended (PR 43's, PR 56's and PR 61's do). The check reads ``BENCHMARK.json``
    with those two lists held to the benchmark's first seven cells, by name (a
    later cell needs no edit here), and nothing else changed; every other
    assertion of it sees the file as it is."""
    bench = json.loads((lfm2_files.ROOT / "BENCHMARK.json").read_text())
    first_seven = [w["name"] for w in bench["workloads"]][:7]
    for metric in bench["per_layer"]:
        if metric["name"] in ("decode_step_ms", "decode_batch_mean"):
            assert metric["workloads"][:7] == first_seven, metric
            metric["workloads"] = [cell for cell in metric["workloads"] if cell in first_seven]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(trinity_files, "ROOT", tmp_path)
    trinity_files.test_the_benchmark_gains_one_configuration_one_cell_and_two_readers_after_keyes()


child.CONFIGS["moe_early_router_gqa"] = BENCH / "configs" / "smallthinker-21b-a3b-int8.json"
child.CONFIGS["sambay_diff"] = BENCH / "configs" / "phi-4-mini-flash-int8.json"


# (PR 43's ``lfm2_moe`` is not a third case of this one: the check holds a tiny
# engine to 0.05 / 0.01 nats, which fixes the logit scale a tiny preset may have
# (PERF.md section 7 (j)); LFM2's is seeded with logits that spread by 2, as the
# published size is, and reads 0.06-0.35 / 0.02-0.06. Its own file's check
# serves probes across chunk boundaries and holds the child to the benchmark's
# limits, with a control: ``test_lfm2_files``, collected above.)
@pytest.mark.parametrize("name", ["moe_early_router_gqa", "sambay_diff"])
def test_the_new_references_child_says_ok_on_probes_of_a_tiny_engine(name, tmp_path):
    """SmallThinker's tiny preset (one period, window 16 under probes of 40 to
    225 prompt tokens, 8 experts top-3), and Phi-4-mini-flash's (eight layers of
    all five kinds, window 16, the state store beside the pages), each served
    through the paged engine, then ``bench/reference.py --rehearse-cpu`` on the
    configuration's own file."""
    child.test_reference_child_says_ok_on_probes_of_a_tiny_engine(name, tmp_path)


@pytest.mark.parametrize("preset", ["smallthinker-test-tiny", "phi4flash-test-tiny", "lfm2-test-tiny",
                                    "xing4-test-tiny", "granite4-test-tiny"])
def test_the_new_configurations_child_builds_the_sidecars_tree(preset):
    child.test_the_childs_weights_are_the_serving_engines_bit_for_bit(preset, "int8")


def test_evabytes_child_builds_the_sidecars_tree():
    """(ISSUE 61) The same check for a family whose pool is laid out for ONE page size
    (the shared case asks for pages of 16): the rehearsal's own sidecar arguments, pool
    and all, against the child's one-row dense cache, which this family builds and
    refuses to serve through. The two EVA vectors and the head's two heads are leaves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.serving.engine import Engine

    run_cfg = evabyte_files.CONFIG["rehearsal"]
    served = child.serving_engine(run_cfg).params
    built = Engine(child.reference.engine_config(run_cfg["preset"], run_cfg["sidecar_args"])).params
    a, b = jax.tree.flatten(served), jax.tree.flatten(built)
    assert a[1] == b[1] and {"eva_mu", "eva_phi"} <= set(served["layers"])
    for x, y in zip(a[0], b[0]):
        assert x.dtype == y.dtype and x.shape == y.shape and x.sharding == y.sharding
        assert np.array_equal(np.asarray(x.astype(jnp.float32)), np.asarray(y.astype(jnp.float32)))
