"""Packed step descriptors (ISSUE 25): one upload and one dispatch per
host-stepped engine submit.

Pins what the change rests on:

1. the host pack and the in-program unpack round-trip every field of both
   packed buffers (random rows, seeded and unseeded, prefill and decode
   kinds, ``mask_state`` set), at both benchmark shape families scaled
   down (S=24/T=536 and S=48/T=560, by eight);
2. the key folded inside the program is ``fold_in(engine._rng, k)``, bit
   for bit;
3. tokens and logprobs of eight mixed steps, a fresh decode chunk and two
   chained chunks equal, byte for byte, those of a reference that uploads
   the twelve arrays one by one and calls the program body with them (the
   parent's submit, kept here as a test helper and nowhere in the engine).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.serving.engine import (
    STOP_TABLE_WIDTH,
    Engine,
    EngineConfig,
    MixedRow,
    build_stop_row,
)
from inference_gateway_tpu.serving.packed import PackedLayout

# (max_slots, mixed budget): the two configurations' shapes over eight.
FAMILIES = {"mistral-like": (3, 67), "qwen-like": (6, 70)}


def _engine(family: str, **over) -> Engine:
    S, T = FAMILIES[family]
    kw = dict(model="test-tiny", max_slots=S, max_seq_len=256, dtype="float32",
              max_prefill_batch=2, use_mesh=False, prefill_buckets=(16, 32, 64),
              decode_chunk=4, attention="paged", page_size=16, mixed_step=True,
              mixed_step_tokens=T, prefix_cache=False)
    kw.update(over)
    return Engine(EngineConfig(**kw))


def _random_rows(engine: Engine, rng: np.random.Generator, with_mask_state: bool) -> list[MixedRow]:
    """One step's rows: a prefill chunk and decode rows on distinct
    slots, some seeded, some not, inside the mixed budget."""
    S, T = engine.config.max_slots, engine.mixed_budget
    slots = list(rng.permutation(S))
    rows = []
    left = T
    for i, slot in enumerate(slots):
        prefill = i == 0 or rng.random() < 0.3
        n = int(rng.integers(2, max(3, min(left - (S - i), 40)))) if prefill else 1
        start = 0 if prefill else int(rng.integers(1, 60))
        rows.append(MixedRow(
            slot=int(slot), token_ids=[int(t) for t in rng.integers(1, 250, n)], start=start,
            kind="prefill" if prefill else "decode",
            temp=float(rng.choice([0.0, 0.7, 1.3])), top_p=float(rng.choice([1.0, 0.9])),
            seed=int(rng.integers(0, 2**31 - 1)) if rng.random() < 0.5 else None,
            mask_state=int(rng.integers(0, 7)) if with_mask_state else 0,
            from_block=not prefill and rng.random() < 0.5))  # its token is still on the device
        left -= n
    return rows


def _twelve_arrays(engine: Engine, rows: list[MixedRow]) -> dict[str, np.ndarray]:
    """The parent's host assembly of a mixed step: twelve separate
    arrays in the dtypes they had before the upload."""
    S, T = engine.config.max_slots, engine.mixed_budget
    a = dict(
        tokens=np.zeros((1, T), np.int32), positions=np.zeros((1, T), np.int32),
        write_idx=np.full((1, T), engine._flat_size, np.int64),
        q_starts=np.zeros((S,), np.int32), q_lens=np.zeros((S,), np.int32),
        kv_lens=np.zeros((S,), np.int32), temps=np.zeros((S,), np.float32),
        top_ps=np.ones((S,), np.float32), seeds=np.zeros((S,), np.int32),
        use_seed=np.zeros((S,), bool), mstates=np.zeros((S,), np.int32),
        from_block=np.zeros((S,), bool))  # ISSUE 40's flag: a thirteenth field
    off = 0
    for r in rows:
        n = len(r.token_ids)
        engine._ensure_with_evict(r.slot, r.start + n)
        a["write_idx"][0, off:off + n] = engine.allocator.flat_write_indices(r.slot, r.start, n)
        a["tokens"][0, off:off + n] = r.token_ids
        a["positions"][0, off:off + n] = r.start + np.arange(n, dtype=np.int32)
        a["q_starts"][r.slot], a["q_lens"][r.slot], a["kv_lens"][r.slot] = off, n, r.start + n
        a["temps"][r.slot], a["top_ps"][r.slot] = r.temp, r.top_p
        if r.seed is not None:
            a["seeds"][r.slot], a["use_seed"][r.slot] = int(r.seed), True
        a["mstates"][r.slot] = r.mask_state
        a["from_block"][r.slot] = r.from_block
        off += n
    a["page_table"] = engine.allocator.page_table().copy()
    return a


class _Captured(Exception):
    pass


def _capture_packed(engine: Engine, name: str, submit) -> np.ndarray:
    """Run ``submit`` with the jitted entry point ``name`` replaced by a
    spy; returns the packed buffer it was handed, as uploaded."""
    seen = {}

    def spy(*args, **kw):
        # (params, cache, packed, base_rng, block) for the mixed step,
        # (packed, base_rng, block) for the fresh unpack program
        seen["packed"], seen["base"], seen["block"] = args[-3:]
        raise _Captured

    setattr(engine, name, spy)
    try:
        with pytest.raises(_Captured):
            submit()
    finally:
        delattr(engine, name)  # the class's jitted method shows again
    assert seen["base"] is engine._rng
    # the operand that is always passed: the mixed step's block of last time
    assert seen["block"] is engine._last_block and seen["block"].dtype == jnp.float32
    assert seen["packed"].dtype == jnp.int32 and seen["packed"].ndim == 1
    return seen["packed"]


# ---------------------------------------------------------------------------
# 1. Round trips
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("trial", range(3))
def test_mixed_pack_round_trips_every_field(family, trial):
    engine = _engine(family)
    rng = np.random.default_rng(100 + trial)
    rows = _random_rows(engine, rng, with_mask_state=True)
    packed = _capture_packed(engine, "_mixed_step_fn", lambda: engine.mixed_step_submit(rows))
    assert packed.shape == (engine._mixed_pack.size,)
    got = jax.jit(engine._mixed_pack.unpack)(packed)
    want = _twelve_arrays(engine, rows)
    assert int(got.pop("counter")[0]) == engine._step_counter == 1
    assert set(got) == set(want)
    for name, w in want.items():
        g = np.asarray(got[name])
        # the device dtypes the twelve uploads had (int64 write indices
        # land as int32 there too: x64 is off)
        assert g.dtype == jnp.asarray(w).dtype, name
        np.testing.assert_array_equal(g, np.asarray(jnp.asarray(w)), err_msg=name)


@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("defaults", [False, True], ids=["explicit", "defaults"])
def test_fresh_pack_round_trips_every_field(family, defaults):
    engine = _engine(family)
    S = engine.config.max_slots
    rng = np.random.default_rng(7)
    active = rng.random(S) < 0.6
    active[0] = True
    positions = np.where(active, rng.integers(1, 50, S), 0).astype(np.int32)
    for s in np.nonzero(active)[0]:
        engine._ensure_with_evict(int(s), int(positions[s]))
    args = dict(
        tokens=rng.integers(1, 250, S).astype(np.int32), positions=positions, active=active,
        temps=rng.choice([0.0, 0.7], S).astype(np.float32),
        top_ps=rng.choice([1.0, 0.9], S).astype(np.float32),
        seeds=rng.integers(0, 2**31 - 1, S).astype(np.int32), use_seed=rng.random(S) < 0.5)
    if not defaults:
        args.update(
            mstates=rng.integers(0, 7, S).astype(np.int32),
            stop_tables=np.stack([build_stop_row(engine._eos_id, [int(t) for t in rng.integers(1, 250, 3)])
                                  for _ in range(S)]),
            budgets=rng.integers(1, 90, S).astype(np.int64),
            from_block=rng.random(S) < 0.5)
    packed = _capture_packed(engine, "_fresh_unpack_fn",
                             lambda: engine.decode_chunk_submit(**args))
    assert packed.shape == (engine._fresh_pack.size,)
    got = {k: np.array(v) for k, v in jax.jit(engine._fresh_pack.unpack)(packed).items()}
    assert int(got["counter"][0]) == engine._step_counter == 1
    for name in ("tokens", "positions", "temps", "top_ps", "seeds", "use_seed", "from_block")[:7 - defaults]:
        np.testing.assert_array_equal(got[name], args[name], err_msg=name)
        assert got[name].dtype == args[name].dtype, name
    np.testing.assert_array_equal(got["done"], ~active)
    np.testing.assert_array_equal(got["page_table"], engine.allocator.page_table())
    np.testing.assert_array_equal(got["reserved"], engine._reserved)
    assert (got["reserved"][active] >= positions[active] + engine.config.decode_chunk).all()
    if defaults:
        assert not got["from_block"].any()  # every token from the host
        np.testing.assert_array_equal(got["mstates"], np.zeros((S,), np.int32))
        np.testing.assert_array_equal(got["budgets"], np.full((S,), 1 << 30, np.int32))
        np.testing.assert_array_equal(
            got["stop_table"], np.broadcast_to(engine._eos_stop_row, (S, STOP_TABLE_WIDTH)))
    else:
        for name, key in (("mstates", "mstates"), ("budgets", "budgets"), ("stop_table", "stop_tables")):
            np.testing.assert_array_equal(got[name], args[key], err_msg=name)
    # A failed fresh submit leaves nothing a chained submit could ride.
    assert engine._dev_carry is None
    # The unpack program hands the chained dispatch its state in the
    # order it reads it, with the chunk key the host used to fold.
    # A flagged row's token is the block's for its slot (here a block the test
    # makes up; one of them is a stop id of its row, which then starts done).
    block = np.zeros(engine._last_block.shape, np.float32)
    block[0] = rng.integers(1, 250, S)
    flagged = np.nonzero(got["from_block"])[0]
    if len(flagged):
        block[0, flagged[0]] = got["stop_table"][flagged[0], 0]
    carry, sampling, tables = engine._fresh_unpack_fn(packed, engine._rng, jnp.asarray(block))
    got["tokens"] = np.where(got["from_block"], block[0].astype(np.int32), got["tokens"])
    if len(flagged):
        assert not got["done"][flagged[0]] or not active[flagged[0]]
        got["done"][flagged[0]] = True
    for dev, name in zip(carry[:5], ("tokens", "positions", "mstates", "done", "budgets")):
        np.testing.assert_array_equal(np.asarray(dev), got[name], err_msg=name)
    np.testing.assert_array_equal(np.asarray(carry[5]), np.asarray(jax.random.fold_in(engine._rng, 1)))
    for dev, name in zip(sampling, ("temps", "top_ps", "seeds", "use_seed", "stop_table")):
        np.testing.assert_array_equal(np.asarray(dev), got[name], err_msg=name)
    for dev, name in zip(tables, ("page_table", "reserved")):
        np.testing.assert_array_equal(np.asarray(dev), got[name], err_msg=name)


def test_layout_is_back_to_back_int32_words_and_resets_to_its_fills():
    lay = PackedLayout([("a", (1,), "i32", 0), ("f", (2, 3), "f32", 1.5),
                        ("b", (4,), "bool", 1), ("none", (0, 5), "i32", 0), ("z", (2,), "i32", -1)])
    assert lay.size == 1 + 6 + 4 + 0 + 2 and lay.host.dtype == np.int32
    assert [lay.fields[k][:2] for k in ("a", "f", "b", "none", "z")] == [(0, 1), (1, 7), (7, 11), (11, 11), (11, 13)]
    v = lay.begin()
    v["f"][1, 2] = -0.3
    v["b"][2] = False
    v["z"][:] = 9
    snap = lay.snapshot()
    lay.begin()  # the next submit's reset must not reach the staged copy
    got = jax.jit(lay.unpack)(jnp.asarray(snap))
    assert np.asarray(got["f"]).tolist() == [[1.5, 1.5, 1.5], [1.5, 1.5, np.float32(-0.3)]]
    assert np.asarray(got["b"]).tolist() == [True, True, False, True] and got["b"].dtype == jnp.bool_
    assert np.asarray(got["z"]).tolist() == [9, 9] and got["none"].shape == (0, 5)
    assert lay.views["z"].tolist() == [-1, -1] and lay.views["f"][1, 2] == 1.5


# ---------------------------------------------------------------------------
# 2. The key drawn in the program
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def key_engine():
    return _engine("mistral-like")


@pytest.fixture(scope="module")
def program_key(key_engine):
    pack = key_engine._mixed_pack
    return jax.jit(lambda buf, base: jax.random.fold_in(base, pack.unpack(buf)["counter"][0]))


@pytest.mark.parametrize("k", range(1, 17))
def test_program_key_is_the_hosts_fold_in(key_engine, program_key, k):
    pack = key_engine._mixed_pack
    pack.begin()["counter"][0] = k
    got = program_key(jnp.asarray(pack.snapshot()), key_engine._rng)
    want = jax.random.fold_in(key_engine._rng, k)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# 3. Byte identity against the twelve-array reference
# ---------------------------------------------------------------------------
class _TwelveArrayReference:
    """The parent's two host-stepped submits: each descriptor uploaded
    on its own, the key folded on the host, the fetch block stacked by a
    dispatch of its own. Drives an engine's program BODIES, so what it
    computes is what the packed programs must compute."""

    def __init__(self, engine: Engine) -> None:
        self.e = engine
        self.mixed = jax.jit(partial(Engine._mixed_step_body, engine),
                             static_argnames=("automaton",), donate_argnums=(1,))

    def mixed_step(self, rows):
        e = self.e
        a = _twelve_arrays(e, rows)
        automaton, mnext, mbits, mbias = e._mask_args()
        toks, lps, e.cache = self.mixed(
            e.params, e.cache, *(jnp.asarray(a[k]) for k in (
                "tokens", "positions", "write_idx", "page_table", "q_starts", "q_lens", "kv_lens",
                "temps", "top_ps", "seeds", "use_seed")), e._next_rng(),
            mstates=jnp.asarray(a["mstates"]), mnext=mnext, mbits=mbits, mbias=mbias, automaton=automaton)
        e._dev_carry = None
        return np.asarray(toks), np.asarray(lps)

    def fresh_chunk(self, tokens, positions, active, temps, top_ps, seeds, use_seed, budgets):
        e = self.e
        S, n = e.config.max_slots, e.config.decode_chunk
        stop_d = jnp.asarray(np.broadcast_to(e._eos_stop_row, (S, STOP_TABLE_WIDTH)))
        e._dev_sampling = (jnp.asarray(temps), jnp.asarray(top_ps), jnp.asarray(seeds),
                           jnp.asarray(use_seed), stop_d)
        e._chain_active = active.copy()
        e._pred_pos = np.asarray(positions, np.int64).copy()
        rng = e._next_rng()
        automaton, mnext, mbits, mterm, mbias = e._mask_args_ee()
        e._reserved[:] = 0
        e._reserve_chain_horizon(active, n)
        toks, lps, *carry, e.cache = e._decode_chunk_fn_paged_ee(
            e.params, e.cache, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(~active),
            jnp.asarray(np.asarray(budgets, np.int32)), stop_d, e._dev_page_table, e._dev_reserved,
            *e._dev_sampling[:4], rng, mstates=jnp.asarray(e._zero_mstates), mnext=mnext,
            mbits=mbits, mterm=mterm, mbias=mbias, n_steps=n, automaton=automaton)
        e._pred_pos = e._pred_pos + n * e._chain_active
        e._dev_carry = tuple(carry)
        return np.asarray(toks), np.asarray(lps)  # (the engine's own fetch splits one block)


def _drive(engine: Engine, reference: bool, biased: bool, ahead: bool = False):
    """Eight mixed steps (three admissions beside decode rows), a fresh
    decode chunk, two chained chunks. Returns every (tokens, logprobs)
    block in order. ``ahead`` (ISSUE 40): every program behind a mixed
    step is dispatched BEFORE that step is fetched, its decode rows'
    tokens read on the device from that step's block (``from_block``)."""
    ref = _TwelveArrayReference(engine) if reference else None
    S = engine.config.max_slots
    if biased:
        for slot in range(S):
            engine.structured_register(slot, None, {7: 3.0, 40 + slot: -2.0})
        # ISSUE 49: rows of the table every program already takes; no
        # grammar flag, no automaton tables, so no second lowering.
        assert not engine._mask_args()[0] and not engine.structured.live
        assert engine._mask_args()[3] is engine.structured.bias_dev
    rng = np.random.default_rng(5)
    # slot -> (temp, top_p, seed): greedy, seeded and unseeded sampling
    sampling = {0: (0.0, 1.0, None), 1: (0.8, 0.9, 1234567), 2: (1.1, 1.0, None)}
    state: dict[int, list[int]] = {}  # slot -> [pending token, position]
    prompts = {s: [int(t) for t in rng.integers(1, 250, int(rng.integers(20, 50)))] for s in sampling}
    out = []

    unfetched: list = []  # ahead: (handle, rows) of the step dispatched last

    def fetch_last():
        handle, rows = unfetched.pop()
        toks, lps = engine.mixed_step_fetch(handle)
        live = [r.slot for r in rows]
        out.append((toks[live].astype(np.int32), lps[live]))

    def step(rows):
        if ahead:
            handle = engine.mixed_step_submit(rows)
            if unfetched:
                fetch_last()
            unfetched.append((handle, rows))
            return np.zeros((S,), np.int32)  # nobody reads a token on the host
        if ref is not None:
            toks, lps = ref.mixed_step(rows)
        else:
            toks, lps = engine.mixed_step_fetch(engine.mixed_step_submit(rows))
        live = [r.slot for r in rows]
        out.append((toks[live].astype(np.int32), lps[live]))
        return toks

    admit_at = {0: 0, 2: 1, 5: 2}  # mixed step -> slot whose prompt starts there
    progress: dict[int, int] = {}
    for i in range(8):
        if i in admit_at:
            progress[admit_at[i]] = 0
        rows = []
        for slot, (tok, pos) in sorted(state.items()):
            t, p, sd = sampling[slot]
            rows.append(MixedRow(slot=slot, token_ids=[tok], start=pos, kind="decode",
                                 temp=t, top_p=p, seed=sd, from_block=ahead))
        for slot, done in sorted(progress.items()):
            t, p, sd = sampling[slot]
            piece = prompts[slot][done:done + 24]  # chunked: a prompt takes two steps
            rows.append(MixedRow(slot=slot, token_ids=piece, start=done, kind="prefill",
                                 temp=t, top_p=p, seed=sd))
        toks = step(rows)
        for slot in list(state):
            state[slot] = [int(toks[slot]), state[slot][1] + 1]
        for slot in list(progress):
            progress[slot] += 24
            if progress[slot] >= len(prompts[slot]):
                state[slot] = [int(toks[slot]), len(prompts[slot])]
                del progress[slot]
    assert len(state) == 3 and not progress
    tokens = np.zeros((S,), np.int32)
    positions = np.zeros((S,), np.int32)
    active = np.zeros((S,), bool)
    temps = np.zeros((S,), np.float32)
    top_ps = np.ones((S,), np.float32)
    seeds = np.zeros((S,), np.int32)
    use_seed = np.zeros((S,), bool)
    budgets = np.full((S,), 64, np.int64)
    for slot, (tok, pos) in state.items():
        tokens[slot], positions[slot], active[slot] = tok, pos, True
        temps[slot], top_ps[slot], sd = sampling[slot]
        if sd is not None:
            seeds[slot], use_seed[slot] = sd, True
    if ref is not None:
        out.append(ref.fresh_chunk(tokens, positions, active, temps, top_ps, seeds, use_seed, budgets))
    elif ahead:
        assert not tokens.any()
        h = engine.decode_chunk_submit(tokens, positions, active, temps, top_ps, seeds=seeds,
                                       use_seed=use_seed, budgets=budgets, from_block=active)
        fetch_last()
        out.append(engine.decode_chunk_fetch(h))
    else:
        out.append(engine.decode_chunk_fetch(engine.decode_chunk_submit(
            tokens, positions, active, temps, top_ps, seeds=seeds, use_seed=use_seed, budgets=budgets)))
    for _ in range(2):
        with jax.transfer_guard_host_to_device("disallow"):
            h = engine.decode_chunk_submit(None, None, None, None, None, chain=True)
        out.append(engine.decode_chunk_fetch(h))
    return [(np.asarray(t), np.asarray(l)) for t, l in out]


@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("biased", [False, True], ids=["unbiased", "biased"])
def test_packed_submits_equal_the_twelve_array_reference_byte_for_byte(family, biased):
    got = _drive(_engine(family), reference=False, biased=biased)
    want = _drive(_engine(family), reference=True, biased=biased)
    assert len(got) == len(want) == 11
    for i, ((gt, gl), (wt, wl)) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(gt, wt, err_msg=f"tokens of block {i}")
        assert gl.tobytes() == wl.tobytes(), f"logprobs of block {i}"
    # unseeded sampling rows drew from the step key: the streams are not all greedy
    assert any(len(np.unique(t)) > 1 for t, _ in got)


@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("biased", [False, True], ids=["unbiased", "biased"])
def test_programs_dispatched_before_the_last_mixed_step_is_fetched_equal_the_reference(family, biased):
    """ISSUE 40's operand: seven mixed steps and the fresh decode chunk
    each dispatched behind an UNFETCHED mixed step, every decode row's
    token taken from that step's block on the device. Same rows, same
    seeds, same step counter: the blocks are the host-stepped reference's."""
    got = _drive(_engine(family), reference=False, biased=biased, ahead=True)
    want = _drive(_engine(family), reference=True, biased=biased)
    assert len(got) == len(want) == 11
    for i, ((gt, gl), (wt, wl)) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(gt, wt, err_msg=f"tokens of block {i}")
        assert gl.tobytes() == wl.tobytes(), f"logprobs of block {i}"


def test_dense_fresh_submit_is_packed_too_and_matches_the_chained_programs_inputs():
    """A dense early-exit engine packs its fresh submit too (no page
    table in its buffer); fresh-then-chained equals one engine asked for
    the chunks one by one from host state."""
    cfg = dict(model="test-tiny", max_slots=3, max_seq_len=128, dtype="float32", use_mesh=False,
               attention="dense", decode_chunk=4, prefill_buckets=(16, 32))
    a, b = Engine(EngineConfig(**cfg)), Engine(EngineConfig(**cfg))
    assert a._mixed_pack is None and a._fresh_pack.fields["page_table"][:2] == a._fresh_pack.fields["reserved"][:2]
    S = 3
    outs = []
    for eng, chained in ((a, True), (b, False)):
        first = eng.prefill([[5, 6, 7, 8]], [0], [0.0], [1.0])[0].first_token
        tokens = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        tokens[0], positions[0], active[0] = first, 4, True
        temps, top_ps = np.zeros((S,), np.float32), np.ones((S,), np.float32)
        t1, _ = eng.decode_chunk_fetch(eng.decode_chunk_submit(tokens, positions, active, temps, top_ps))
        if chained:
            t2, _ = eng.decode_chunk_fetch(eng.decode_chunk_submit(None, None, None, None, None, chain=True))
        else:
            tokens[0], positions[0] = t1[-1, 0], 8
            t2, _ = eng.decode_chunk_fetch(eng.decode_chunk_submit(tokens, positions, active, temps, top_ps))
        outs.append(np.concatenate([t1[:, 0], t2[:, 0]]))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_mesh_engine_replicates_the_packed_buffer():
    """Under a mesh the one packed buffer is replicated as the twelve
    arrays were: greedy tokens of a mixed step, a fresh chunk and a
    chained chunk equal the single-device engine's."""
    outs = []
    for mesh in (None, {"dp": 1, "sp": 1, "tp": 2}):
        eng = _engine("mistral-like", use_mesh=mesh is not None, mesh_shape=mesh)
        assert (eng.mesh is not None) == (mesh is not None)
        prompt = [int(t) for t in np.random.default_rng(2).integers(1, 250, 30)]
        toks, _ = eng.mixed_step_fetch(eng.mixed_step_submit(
            [MixedRow(slot=1, token_ids=prompt, start=0, kind="prefill")]))
        S = eng.config.max_slots
        tokens = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        tokens[1], positions[1], active[1] = toks[1], len(prompt), True
        t1, _ = eng.decode_chunk_fetch(eng.decode_chunk_submit(
            tokens, positions, active, np.zeros((S,), np.float32), np.ones((S,), np.float32)))
        t2, _ = eng.decode_chunk_fetch(eng.decode_chunk_submit(None, None, None, None, None, chain=True))
        outs.append([int(toks[1])] + t1[:, 1].tolist() + t2[:, 1].tolist())
    assert outs[0] == outs[1]
