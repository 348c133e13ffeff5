"""Device time by named scope (ISSUE 52): the vocabulary is one closed list in
three places (``bench/scope_classes.json``, ``docs/observability.md``, the
package's ``jax.named_scope`` literals), and ``bench/scope_times.py`` reads a
capture's own HLO with a protobuf wire reader of its own. The step programs'
coverage by the vocabulary is held beside the census that traces them
(``tests/test_chip_compile.py``)."""

import importlib.util
import json
import re
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import scope_times  # noqa: E402

CLASSES = json.loads((BENCH / "scope_classes.json").read_text())["classes"]
VOCAB = scope_times.load_vocabulary()
PATTERNS = json.loads((BENCH / "trace_classes.json").read_text())["modules"]  # which programs are step programs
READERS = {"scope_attention_share": "attention", "scope_mlp_share": "mlp", "scope_moe_share": "moe",
           "scope_state_share": "state", "scope_head_sampling_share": "head_sampling",
           "scope_unscoped_share": "unscoped"}


# ---------------------------------------------------------------------------
# (b) one closed list, three places
# ---------------------------------------------------------------------------
def _documented() -> dict[str, list[str]]:
    """The table under "Device time by named scope" in docs/observability.md:
    class -> scopes, in the file's order."""
    text = (ROOT / "docs" / "observability.md").read_text()
    section = text.split("#### Device time by named scope", 1)[1].split("\n#", 1)[0]
    rows = re.findall(r"^\| `([\w.]+)` \| ((?:`[\w.]+`(?:, )?)+) \|", section, re.M)
    return {cls: re.findall(r"`([\w.]+)`", scopes) for cls, scopes in rows}


def _literals() -> dict[str, list[str]]:
    """Every ``jax.named_scope("...")`` literal of the package -> the files that hold it."""
    found: dict[str, list[str]] = {}
    for path in sorted((ROOT / "inference_gateway_tpu").rglob("*.py")):
        for name in re.findall(r"named_scope\(\s*[\"']([^\"']+)[\"']", path.read_text()):
            found.setdefault(name, []).append(path.name)
    return found


def test_the_data_file_and_the_document_hold_the_same_classes_and_scopes():
    assert _documented() == CLASSES
    doc = (ROOT / "docs" / "observability.md").read_text()
    assert "`unscoped`" in doc and "`other_programs`" in doc
    scopes = [s for names in CLASSES.values() for s in names]
    assert len(scopes) == len(set(scopes)), "a scope stands in two classes"
    assert not {"unscoped", "other_programs"} & (set(scopes) | set(CLASSES))


def test_every_named_scope_literal_of_the_package_is_in_the_vocabulary_and_none_is_idle():
    literals = _literals()
    assert set(literals) == set(VOCAB["scope_class"]), (
        set(literals) ^ set(VOCAB["scope_class"]), "a scope of the program outside the list, or a name no site uses")
    # no name is built at run time: a scope the census cannot read off the source is not in the closed list
    for path in (ROOT / "inference_gateway_tpu").rglob("*.py"):
        assert not re.search(r"named_scope\(\s*[^\"'\s)]", path.read_text()), path


@pytest.mark.parametrize("op_name,scope,cls", [
    ("jit(_mixed_step_fn)/jit(main)/while/body/mlp/moe.experts/pallas_call", "moe.experts", "moe"),
    ("jit(_mixed_step_fn)/jit(main)/while/body/attention/ssm.scan/mul", "ssm.scan", "state"),
    ("jit(_decode_chunk_fn_paged_ee)/jit(main)/while/body/while/body/attention/dsa.index/dot_general", "dsa.index",
     "attention"),
    ("jit(f)/attention/mamba/ssm.gate/mul", "ssm.gate", "state"),
    ("jit(f)/carry/while/body/attention/add", "attention", "attention"),
    ("jit(f)/jit(main)/while/body/closed_call/attentions/add", None, "unscoped"),
    ("", None, "unscoped"),
])
def test_the_innermost_known_scope_decides_the_class(op_name, scope, cls):
    got = scope_times.scope_of(op_name, VOCAB["scope_class"])
    assert got == scope
    assert (VOCAB["scope_class"][got] if got else VOCAB["unscoped"]) == cls


# ---------------------------------------------------------------------------
# (c) the wire reader on a capture this test makes, and the arithmetic on hand-built events
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """A CPU capture of one small jitted function with two scopes, one inside
    the other, and what ``compiled.as_text()`` says of the same program."""
    @jax.jit
    def two_scopes(x, w):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ w)
            with jax.named_scope("ssm.scan"):
                y = jnp.cumsum(y * 2.0, axis=0)
        with jax.named_scope("moe.combine"):
            return (y * 3.0 + 1.0).sum(axis=1), jnp.sort(x[:, 0])

    x = jnp.ones((64, 64), jnp.float32)
    jax.block_until_ready(two_scopes(x, x))
    out = tmp_path_factory.mktemp("capture")
    jax.profiler.start_trace(str(out))
    jax.block_until_ready(two_scopes(x, x))
    jax.profiler.stop_trace()
    (path,) = out.rglob("*.xplane.pb")
    return path, two_scopes.lower(x, x).compile().as_text()


def _as_text_scopes(text: str) -> dict[str, str]:
    """{instruction: op_name} as the compiled module's text has them."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if m:
            op = re.search(r"metadata=\{[^\n]*?op_name=\"([^\"]*)\"", line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def test_the_wire_reader_finds_every_instructions_op_name_as_the_compiled_text_has_it(capture):
    path, text = capture
    hlo = scope_times.hlo_scopes(path.read_bytes())
    (module,) = [name for name in hlo if name.startswith("jit_two_scopes")]
    want = _as_text_scopes(text)
    assert hlo[module] == want
    scoped = {scope_times.scope_of(op, VOCAB["scope_class"]) for op in want.values()}
    assert {"attention", "ssm.scan", "moe.combine"} <= scoped
    assert "tensorflow" not in sys.modules, "the reader must not need it"


def test_a_file_that_is_no_capture_is_refused_and_one_without_hlo_reads_empty():
    with pytest.raises((ValueError, IndexError)):
        list(scope_times.fields(b"\x0f\x01"))
    plane = b"\x12\x04/dev"  # an XPlane with a name and nothing else
    assert scope_times.hlo_scopes(b"\x0a" + bytes([len(plane)]) + plane) == {}


def _events():
    """One device: two mixed steps, one decode chunk whose loop encloses its
    body's operations, and a small program of neither kind. Times in seconds."""
    mixed, chunk = "jit__mixed_step_fn(77)", "jit__decode_chunk_fn_paged_ee(78)"
    modules = [(mixed, 0.0, 1.0), (mixed, 2.0, 1.0), (chunk, 4.0, 2.0), ("jit__set_row(5)", 7.0, 0.5)]
    ops = []
    for at in (0.0, 2.0):
        ops += [("%fusion.1 = bf16[8]{0} fusion(%p)", at, 0.4), ("%moe_gate_up_gmm.3 = bf16[8] custom-call(%fusion.1)", at + 0.4, 0.3),
                ("%copy.9 = bf16[8] copy(%fusion.1)", at + 0.7, 0.1), ("%fusion.2 = f32[8] fusion(%copy.9)", at + 0.8, 0.2)]
    # the chunk: a while of 2.0 s whose children cover 1.6 s; the same names mean other instructions here
    ops += [("%while.4 = (s32[]) while(%tuple)", 4.0, 2.0), ("%fusion.1 = f32[4] fusion(%p)", 4.1, 0.9),
            ("%fusion.7 = f32[4] fusion(%p)", 5.2, 0.7)]
    ops += [("%fusion.1 = f32[2] fusion(%p)", 7.0, 0.5)]
    hlo = {mixed: {"fusion.1": "jit(_mixed_step_fn)/jit(main)/while/body/attention/dot_general",
                   "moe_gate_up_gmm.3": "jit(_mixed_step_fn)/jit(main)/while/body/mlp/moe.experts/pallas_call",
                   "copy.9": "", "fusion.2": "jit(_mixed_step_fn)/jit(main)/sampling/sort"},
           "jit__decode_chunk_fn_paged_ee(99)": {  # found by the name before the id
               "while.4": "jit(_decode_chunk_fn_paged_ee)/jit(main)/while",
               "fusion.1": "jit(_decode_chunk_fn_paged_ee)/jit(main)/while/body/attention/ssm.scan/mul",
               "fusion.7": "jit(_decode_chunk_fn_paged_ee)/jit(main)/while/body/carry/select_n"},
           "jit__set_row(5)": {"fusion.1": "jit(_set_row)/attention/scatter"}}
    return ops, modules, hlo


def test_self_time_goes_to_the_enclosing_programs_instruction_and_its_innermost_scope():
    ops, modules, hlo = _events()
    got = scope_times.attribute(ops, modules, hlo, VOCAB, PATTERNS)
    mixed, chunk, other = got["mixed_step"], got["decode_chunk"], got["other_programs"]
    assert (mixed["programs"], chunk["programs"], other["programs"]) == (2, 1, 1)
    assert mixed["class_s"] == pytest.approx({"attention": 0.8, "moe": 0.6, "unscoped": 0.2, "head_sampling": 0.4})
    assert mixed["scope_s"] == pytest.approx({"attention": 0.8, "moe.experts": 0.6, "unscoped": 0.2, "sampling": 0.4})
    assert list(mixed["unscoped_ops"]) == ["%copy.9 = bf16[8] copy(%fusion.1)"]
    # the loop keeps what its children do not cover, and the same instruction name is another program's
    assert chunk["class_s"] == pytest.approx({"unscoped": 0.4, "state": 0.9, "step": 0.7})
    assert chunk["scope_s"] == pytest.approx({"unscoped": 0.4, "ssm.scan": 0.9, "carry": 0.7})
    # a program that is no step program: never classed, whatever its instructions say
    assert other["class_s"] == pytest.approx({"other_programs": 0.5}) and other["events"] == 1
    total = sum(slot["seconds"] for slot in got.values())
    assert total == pytest.approx(2 * 1.0 + 2.0 + 0.5)  # the union of the operations: busy


def test_the_devices_are_averaged_and_the_five_longest_unscoped_operations_kept():
    ops, modules, hlo = _events()
    one = scope_times.attribute(ops, modules, hlo, VOCAB, PATTERNS)
    idle = scope_times.attribute([], [], hlo, VOCAB, PATTERNS)
    merged = scope_times.merge([one, idle])
    assert merged["mixed_step"]["class_s"]["moe"] == pytest.approx(0.3)
    assert merged["decode_chunk"]["unscoped_top"] == [["%while.4 = (s32[]) while(%tuple)", pytest.approx(0.2)]]
    assert "unscoped_ops" not in merged["mixed_step"]


def _sources(by_scope, busy_s=4.0):
    return {"trace": {"busy_s": busy_s, "by_scope": by_scope}}


def test_a_share_is_the_class_in_both_step_programs_over_busy_and_nothing_where_it_has_no_event():
    ops, modules, hlo = _events()
    account = {"hlo_from": "capture", "modules": scope_times.merge([scope_times.attribute(ops, modules, hlo, VOCAB, PATTERNS)])}
    src = _sources(account, busy_s=4.5)
    assert scope_times.class_share(src, "attention") == pytest.approx(100 * 0.8 / 4.5)
    assert scope_times.class_share(src, "state") == pytest.approx(100 * 0.9 / 4.5)
    assert scope_times.class_share(src, "unscoped") == pytest.approx(100 * 0.6 / 4.5)
    assert scope_times.class_share(src, "mlp") is None          # no event of the class
    assert scope_times.class_share(src, "other_programs") is None  # not a step program's
    # no trace, a trace with no busy time, a capture whose HLO could not be had: nothing, and no raise
    assert scope_times.class_share({"trace": None}, "attention") is None
    assert scope_times.class_share({"trace": {"busy_s": 0.0}}, "attention") is None
    assert scope_times.class_share(_sources({"hlo_from": None, "modules": account["modules"]}), "attention") is None


# ---------------------------------------------------------------------------
# (d) each reader against its entry, found by name
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(READERS))
def test_each_reader_agrees_with_its_entry_found_by_name(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    spec = importlib.util.spec_from_file_location(f"bench_layers_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE)
    assert entry["unit"] == "%" and entry["better"] == "lower" and entry["source"] == "device_trace"
    cells = [w["name"] for w in bench["workloads"]]
    assert set(entry["workloads"]) <= set(cells) and len(set(entry["workloads"])) == len(entry["workloads"])
    reports = [m["name"] for m in bench["end_to_end"] if "workloads" not in m]
    assert entry["moves"] in reports   # every cell reports what the share should move
    assert "jax" not in mod.__dict__   # the readers stay stdlib: the parent never holds the chip
    cls = READERS[name]
    account = {"hlo_from": "capture", "modules": {"mixed_step": {"class_s": {cls: 0.5}}, "decode_chunk": {"class_s": {cls: 0.25}}}}
    assert mod.compute(_sources(account, busy_s=3.0)) == pytest.approx(25.0)
    assert mod.compute({"trace": None}) is None


def test_the_pool_write_reader_is_one_scopes_seconds_in_both_step_programs_over_busy():
    """``kv_write_busy_share`` (ISSUE 62): the scope ``kv.write`` alone, by ``scope_s``;
    nothing where no operation of either step program stands under it."""
    name = "kv_write_busy_share"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    spec = importlib.util.spec_from_file_location(f"bench_layers_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE)
    # (ten cells at ISSUE 62; ISSUE 64's cell, whose two attention layers write the pool, is the eleventh)
    assert entry["better"] == "lower" and len(entry["workloads"]) == 11 and "jax" not in mod.__dict__
    assert mod.SCOPE in CLASSES["attention"]
    modules = {"mixed_step": {"scope_s": {"kv.write": 0.5, "attention": 2.0}}, "decode_chunk": {"scope_s": {"kv.write": 0.25}},
               "other_programs": {"scope_s": {"kv.write": 9.0}}}
    assert mod.compute(_sources({"hlo_from": "capture", "modules": modules}, busy_s=3.0)) == pytest.approx(25.0)
    modules = {"mixed_step": {"scope_s": {"attention": 2.0}}, "decode_chunk": {"scope_s": {}}}
    assert mod.compute(_sources({"hlo_from": "capture", "modules": modules}, busy_s=3.0)) is None
    assert mod.compute(_sources({"hlo_from": None, "modules": modules}, busy_s=3.0)) is None
    assert mod.compute({"trace": None}) is None and mod.compute({"trace": {"busy_s": 0.0}}) is None
