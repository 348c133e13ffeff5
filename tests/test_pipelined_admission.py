"""The admission train one step ahead of the host (ISSUE 40), on the CPU with
the tiny presets: the program behind a mixed step is dispatched before that
step is fetched and reads its decode rows' tokens on the device.

What is held here: a request's tokens are what they are with every step
host-stepped (all four kinds of model, seeded sampling, log-probabilities to
1e-4); a finish the host cannot foresee (a stop id) costs one discarded row,
counted and never delivered, one release and no page; a finish by the budget
is decided when the step is dispatched (the row is absent from the next step,
its slot serves that step's own admission, its last token arrives once);
``cancel``, ``abort_all``, a disconnect and an ``OutOfPagesError`` with a step
unfetched lose nobody; a grammar row makes its step host-stepped; the fresh
decode chunk behind a train reads its tokens from the block. The host-stepped
arm is forced by the tests' own seam, ``Scheduler._runs_ahead`` patched to
False: the order the parent ran. No number here is a speed.
"""

import queue
import time

import jax
import numpy as np
import pytest

from inference_gateway_tpu.otel.profiling import StepTimeline
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow, build_stop_row
from inference_gateway_tpu.serving.kv_cache import OutOfPagesError
from inference_gateway_tpu.serving.scheduler import _TOKEN_PENDING, GenRequest, Scheduler

PRESETS = {"dense": "test-tiny", "mixture": "mixtral-test-tiny", "hybrid": "phi4flash-test-tiny",
           "indexer": "keye-test-tiny"}


def _engine(model: str = "test-tiny", **over) -> Engine:
    kw = dict(model=model, attention="paged", page_size=8 if model.startswith("keye") else 16, max_slots=4,
              max_seq_len=256, dtype="float32", use_mesh=False, mixed_step=True, decode_chunk=4,
              prefix_cache=False)
    kw.update(over)
    return Engine(EngineConfig(**kw))


class Client:
    """One request and everything its callback saw."""

    def __init__(self, i: int, prompt_len: int, done: "queue.Queue | None" = None, **kw) -> None:
        rng = np.random.default_rng(1000 + i)
        self.i, self.seen, self.done = i, [], done
        kw.setdefault("temperature", 0.7)
        kw.setdefault("seed", 100 + i)
        self.req = GenRequest(prompt_ids=[int(t) for t in rng.integers(1, 250, prompt_len)],
                              callback=self.cb, **kw)

    def cb(self, token, logprob, finished, reason):
        self.seen.append((token, logprob, finished, reason))
        if finished and self.done is not None:
            self.done.put(self.i)

    @property
    def tokens(self):
        return [t for t, _, _, _ in self.seen]

    @property
    def ends(self):
        return [r for _, _, fin, r in self.seen if fin]


def _closed_loop(model: str, ahead: bool, n: int = 14, clients: int = 6, timeline: bool = False):
    """``n`` seeded requests from ``clients`` clients on four slots, through a
    running scheduler. Returns (clients, scheduler, engine)."""
    engine = _engine(model)
    sched = Scheduler(engine)
    if not ahead:
        sched._runs_ahead = lambda step: False  # the tests' seam: every step host-stepped
    if timeline:
        sched.timeline = StepTimeline(4096)
    done: queue.Queue = queue.Queue()
    cs = [Client(i, 10 + (i * 7) % 40, done, max_tokens=5 + i % 9) for i in range(n)]
    sched.start()
    try:
        for c in cs[:clients]:
            sched.submit(c.req)
        for c in cs[clients:]:
            done.get(timeout=180)
            sched.submit(c.req)
        for _ in range(clients):
            done.get(timeout=180)
        time.sleep(0.2)
    finally:
        sched.stop()
    return cs, sched, engine


# ---------------------------------------------------------------------------
# (a) Same seed, same request: the same tokens, for every kind of model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", list(PRESETS))
def test_a_closed_loop_yields_each_request_the_tokens_of_the_host_stepped_loop(kind):
    got, sched, engine = _closed_loop(PRESETS[kind], ahead=True)
    want, host, _ = _closed_loop(PRESETS[kind], ahead=False)
    assert sched.pipelined_submits >= 5 and host.pipelined_submits == 0
    for g, w in zip(got, want):
        assert g.tokens == w.tokens and g.ends == w.ends == ["length"], g.i
        assert len(g.seen) == g.req.max_tokens
        np.testing.assert_allclose([lp for _, lp, _, _ in g.seen], [lp for _, lp, _, _ in w.seen], atol=1e-4)
    assert sorted(sched._free) == [0, 1, 2, 3] and not sched._slots and not sched._retiring
    assert sched._mixed_inflight is None and not sched._admitting
    assert engine.allocator.free_page_count() == engine.allocator.num_pages


# ---------------------------------------------------------------------------
# Stepping the loop by hand: one pass at a time, on the test's thread
# ---------------------------------------------------------------------------
def _spy_rows(engine: Engine) -> list:
    """Every mixed step's rows as (slot, kind, from_block), in dispatch order,
    with ("fetch",) where a mixed step was fetched."""
    log: list = []
    submit, fetch = engine.mixed_step_submit, engine.mixed_step_fetch

    def _submit(rows, **kw):
        handle = submit(rows, **kw)
        log.append([(r.slot, r.kind, r.from_block) for r in rows])
        return handle

    engine.mixed_step_submit = _submit
    engine.mixed_step_fetch = lambda h: (fetch(h), log.append(("fetch",)))[0]
    return log


def _train(sched: Scheduler, a: Client, b: Client, tokens_of_a_first: int = 1) -> None:
    """A decodes alone; then B's long prompt is admitted in a train of mixed
    steps that A's row rides. Leaves the scheduler inside no pass."""
    sched.submit(a.req)
    while len(a.seen) < tokens_of_a_first:
        sched._pass()
    sched._drain_all()                # the host has every token dispatched so far: the train starts clean
    sched.submit(b.req)


def _run_out(sched: Scheduler, limit: int = 400) -> None:
    for _ in range(limit):
        if not (sched._waiting or sched._slots or sched._handles or sched._mixed_inflight):
            return
        sched._pass()
    raise AssertionError("the scheduler never ran out of work")


def test_a_stop_id_drawn_inside_a_train_costs_one_discarded_row_and_one_release():
    """(b) The finish the host cannot foresee: A's stop id is sampled by step
    N of B's train while step N+1, with A's row in it, is already dispatched."""
    ref = Client(0, 8, max_tokens=40)
    probe = Scheduler(_engine(mixed_step_tokens=24))
    probe.submit(ref.req)
    _run_out(probe)
    stream = ref.tokens
    assert len(stream) == 40

    engine = _engine(mixed_step_tokens=24)
    log = _spy_rows(engine)
    sched = Scheduler(engine)
    a_probe = Client(0, 8, max_tokens=40)
    sched.submit(a_probe.req)
    sched._pass()
    sched._drain_all()
    had = len(a_probe.seen)           # A's tokens before B's train: the first and the pass's chunk
    # A's stop id: the token its third row of the train samples, a value new to the stream.
    k = next(j for j in range(had + 2, 40) if stream[j] not in stream[:j])
    assert k < had + 5, (had, k)      # inside B's six-step train
    a_probe.req.stop_token_ids = frozenset({stream[k]})
    a_slot = next(iter(sched._slots))
    b = Client(1, 130, max_tokens=3)
    sched.submit(b.req)
    released: list = []
    release = engine.release_slot
    engine.release_slot = lambda slot, frozen=False: (released.append(slot), release(slot, frozen=frozen))[1]
    _run_out(sched)
    assert a_probe.tokens == stream[:k + 1] and a_probe.ends == ["stop"]  # nothing after the stop id
    assert sched.wasted["overshoot"] == 1
    assert b.ends == ["length"] and len(b.seen) == 3
    assert len(released) == 2 and a_slot in released and sorted(sched._free) == [0, 1, 2, 3]  # each once
    assert engine.allocator.free_page_count() == engine.allocator.num_pages
    # A's rows of the train: the first built from the host's token, then one a
    # step read on the device, the last of them behind the step that drew the
    # stop id (its token is the one discarded); none after that.
    steps = [e for e in log[2:] if e != ("fetch",)]
    assert [(a_slot, "decode", True) in rows or (a_slot, "decode", False) in rows for rows in steps] == \
        [True] * (k - had + 2) + [False] * (len(steps) - (k - had + 2))
    assert [(a_slot, "decode", True) in rows for rows in steps[:k - had + 2]] == [False] + [True] * (k - had + 1)


def test_a_finish_by_the_budget_is_decided_at_dispatch_and_its_slot_serves_the_next_step():
    """(c) A's last token is sampled by step N: A has no row in step N+1, its
    slot holds C's prompt there, and the token is delivered once."""
    engine = _engine(mixed_step_tokens=24, max_slots=2, max_prefill_batch=1)
    log = _spy_rows(engine)
    sched = Scheduler(engine)
    a, b, c = Client(0, 8, max_tokens=7), Client(1, 60, max_tokens=4), Client(2, 20, max_tokens=2)
    _train(sched, a, b)
    assert len(a.seen) == 5           # the first token and the pass's chunk
    sched.submit(c.req)               # waits: both slots are taken until A's goes back
    a_slot = next(s for s, st in sched._slots.items() if st.req is a.req)
    sched._pass()                     # B's train: 60 tokens at 23 a step beside A's row; its last step stays
    assert sched._mixed_inflight is not None and len(a.seen) == 7  # ... unfetched: C's admission is due
    assert a_slot in sched._free and a.ends == ["length"]
    sched._pass()                     # C's admission, behind it
    steps = [e for e in log[2:] if e != ("fetch",)]  # log[0:2]: A's own admission and its fetch
    with_a = [i for i, rows in enumerate(steps) if (a_slot, "decode", False) in rows or (a_slot, "decode", True) in rows]
    assert with_a == [0, 1]           # tokens 6 and 7: the second is A's last by max_tokens, and no row follows
    # A's slot went back when that step was dispatched: B's last chunk rode the
    # next step without A, and C's prompt sits in A's slot in the step after,
    # dispatched before B's last step was fetched.
    assert steps[2] == [(1 - a_slot, "prefill", False)]
    assert (a_slot, "prefill", False) in steps[3] and (1 - a_slot, "decode", True) in steps[3], steps
    i3 = [i for i, e in enumerate(log) if e != ("fetch",)][4]
    assert log[:i3].count(("fetch",)) == 3  # of the four steps dispatched before it
    _run_out(sched)
    assert a.ends == ["length"] and len(a.seen) == 7 and len(set(map(id, a.seen))) == 7
    assert [len(x.seen) for x in (b, c)] == [4, 2] and b.ends == c.ends == ["length"]
    assert sorted(sched._free) == [0, 1] and not sched._retiring
    assert engine.allocator.free_page_count() == engine.allocator.num_pages


@pytest.mark.parametrize("way", ["cancel", "disconnect", "abort_all"])
def test_nobody_is_lost_or_answered_twice_with_a_step_unfetched(way):
    """(d) The pass ends with B's last step unfetched (C waits and a slot is
    free, so the chunk stood back): A's row is in it, with A's LAST token in
    the abort's case (A's slot is back already, its state in ``_retiring``)."""
    engine = _engine(mixed_step_tokens=24, max_prefill_batch=1)
    sched = Scheduler(engine)
    a = Client(0, 8, max_tokens=8 if way == "abort_all" else 30)
    b, c = Client(1, 60, max_tokens=6), Client(2, 12, max_tokens=3)
    _train(sched, a, b)
    sched.submit(c.req)
    sched._pass()
    step = sched._mixed_inflight
    assert step is not None and c.req in sched._waiting and not sched._admitting
    if way == "abort_all":
        assert [st.req for st in sched._retiring] == [a.req]
        assert sched.abort_all() == 3  # C waiting, B in its slot, A retiring
        assert a.ends == b.ends == c.ends == ["error"]
        return
    if way == "cancel":
        sched.cancel(a.req)
        sched.cancel(c.req)           # still queued: dropped before it prefills
    else:
        a.req.disconnected = True
    _run_out(sched)
    assert a.ends == ["disconnected"] and b.ends == ["length"] and len(b.seen) == 6
    assert c.ends == ([] if way == "cancel" else ["length"])
    assert not sched._admitting and sched._mixed_inflight is None and not sched._retiring
    assert sorted(sched._free) == [0, 1, 2, 3]
    assert engine.allocator.free_page_count() == engine.allocator.num_pages


def test_out_of_pages_at_a_submit_settles_the_unfetched_step_then_requeues():
    """(e) The third step of B's train cannot get its pages: the second,
    unfetched, is fetched and processed first, and the step is built again
    from what the host then has (the emit may have freed what it lacked);
    refused again with nothing unfetched, B goes back to the head of the queue
    as on a host-stepped train, and is served once pages free."""
    engine = _engine(mixed_step_tokens=24)
    sched = Scheduler(engine, preempt_max=1)
    a, b = Client(0, 8, max_tokens=30), Client(1, 100, max_tokens=4)
    _train(sched, a, b)
    calls, order = [], []
    submit, fetch = engine.mixed_step_submit, engine.mixed_step_fetch

    def failing(rows, **kw):
        calls.append(len(calls))
        if len(calls) in (3, 4):
            order.append(("refused", sched._mixed_inflight is not None))
            raise OutOfPagesError("no pages (the test's)")
        return submit(rows, **kw)

    engine.mixed_step_submit = failing
    engine.mixed_step_fetch = lambda h: (fetch(h), order.append(("fetch", len(a.seen))))[0]
    before = len(a.seen)
    sched._pass()
    # refused with a step unfetched; that step's fetch follows the refusal, and A has its token
    assert order == [("fetch", before), ("refused", True), ("fetch", before + 1), ("refused", False)]
    assert len(a.seen) >= before + 2 and not b.seen
    assert list(sched._waiting) == [b.req] and sched._mixed_inflight is None
    engine.mixed_step_submit = submit
    _run_out(sched)
    assert b.ends == ["length"] and len(b.seen) == 4 and a.ends == ["length"] and len(a.seen) == 30
    assert sorted(sched._free) == [0, 1, 2, 3]
    assert engine.allocator.free_page_count() == engine.allocator.num_pages


def test_a_grammar_row_makes_its_step_host_stepped_and_its_output_is_what_it_was():
    """(f) A JSON grammar among the rows: that step is fetched before anything
    is dispatched behind it, and the record says the tokens came from the host."""
    outs = []
    for ahead in (True, False):
        engine = _engine()
        sched = Scheduler(engine)
        sched.timeline = StepTimeline(512)
        if not ahead:
            sched._runs_ahead = lambda step: False
        g = Client(0, 12, max_tokens=24, temperature=0.0, seed=None,
                   grammar=engine.structured.session_for({"type": "json_object"}))
        other = Client(1, 50, max_tokens=10)
        log = _spy_rows(engine)
        sched.submit(g.req)
        sched._pass()
        sched.submit(other.req)
        _run_out(sched)
        outs.append((g.tokens, g.ends, other.tokens))
        records = [r for r in sched.timeline.tail() if r["kind"] == "mixed" or r.get("h2d_arrays") == 1]
        if ahead:
            # every program behind a step that sampled for the grammar's row was built from the host's tokens
            assert all(not flag for e in log if e != ("fetch",) for slot, _, flag in e if slot == 0)
            assert all(r["tokens_from"] == "host" for r in records) and sched.pipelined_submits == 0
    assert outs[0] == outs[1] and outs[0][1] and outs[0][1][0] in ("stop", "length")


def test_the_fresh_chunk_behind_a_train_reads_its_tokens_from_the_block():
    """(h) Engine level: a fresh decode submit dispatched before the mixed step
    in front of it is fetched equals one built from that step's fetched tokens;
    a row whose token is one of its stop ids starts done."""
    outs = []
    for ahead in (True, False):
        engine = _engine()
        rows = [MixedRow(slot=s, token_ids=[int(t) for t in np.random.default_rng(s).integers(1, 250, 20 + s)],
                         start=0, kind="prefill", temp=0.7, seed=11 + s) for s in (0, 2)]
        handle = engine.mixed_step_submit(rows)
        S = engine.config.max_slots
        active = np.isin(np.arange(S), (0, 2))
        positions = np.where(active, [20, 0, 22, 0], 0).astype(np.int32)
        args = dict(temps=np.full((S,), 0.7, np.float32), top_ps=np.ones((S,), np.float32),
                    seeds=np.array([11, 0, 13, 0], np.int32), use_seed=active, budgets=np.full((S,), 9, np.int64))
        if ahead:
            chunk = engine.decode_chunk_submit(np.zeros((S,), np.int32), positions, active, from_block=active, **args)
            first, _ = engine.mixed_step_fetch(handle)
        else:
            first, _ = engine.mixed_step_fetch(handle)
            chunk = engine.decode_chunk_submit(np.where(active, first, 0).astype(np.int32), positions, active, **args)
        toks, lps = engine.decode_chunk_fetch(chunk)
        outs.append((first[active], toks[:, active], lps[:, active]))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    np.testing.assert_allclose(outs[0][2], outs[1][2], atol=1e-4)
    # the same submit with slot 2's first token among its stop ids: the row starts done
    stop_tables = np.stack([build_stop_row(engine._eos_id, [int(outs[0][0][1])] if s == 2 else []) for s in range(S)])
    engine.decode_chunk_submit(np.zeros((S,), np.int32), positions, active, from_block=active,
                               stop_tables=stop_tables, **args)
    packed = engine._fresh_pack.snapshot()
    carry, _, _ = engine._fresh_unpack_fn(jax.numpy.asarray(packed), engine._rng, engine._last_block)
    assert np.asarray(carry[3]).tolist() == [False, True, True, True]
    assert np.asarray(carry[0])[[0, 2]].tolist() == outs[0][0].tolist()


def test_one_executable_a_step_program_whatever_the_block_and_the_flags():
    """The operand is always passed and the flags ride the packed buffer: a
    train's first step (a block of zeros, no flag), its later steps (the block
    of the step before) and both kinds of fresh submit are ONE compiled form of
    each step program (a second would cost every warm start 1.3-2.7 s at 7B,
    ``PERF.md`` section 7), and a ``logit_bias`` row is an operand of that same
    form (ISSUE 49: the table is there from the first trace)."""
    mixed0, fresh0 = Engine._mixed_step_fn._cache_size(), Engine._fresh_unpack_fn._cache_size()
    engine = _engine(mixed_step_tokens=24)
    engine.warmup()
    assert (Engine._mixed_step_fn._cache_size(), Engine._fresh_unpack_fn._cache_size()) == (mixed0 + 1, fresh0 + 1)
    sched = Scheduler(engine)
    a, b = Client(0, 8, max_tokens=20), Client(1, 90, max_tokens=4)
    _train(sched, a, b)
    _run_out(sched)
    assert sched.pipelined_submits >= 3 and sched.host_stepped_submits >= 2
    assert (Engine._mixed_step_fn._cache_size(), Engine._fresh_unpack_fn._cache_size()) == (mixed0 + 1, fresh0 + 1)
    c, d = Client(2, 8, max_tokens=20, logit_bias={7: 2.0}), Client(3, 90, max_tokens=4)
    _train(sched, c, d)               # a bias row: biased, no recompile
    _run_out(sched)
    assert not engine._mask_args()[0] and engine.structured._slot_biased == set()
    assert (Engine._mixed_step_fn._cache_size(), Engine._fresh_unpack_fn._cache_size()) == (mixed0 + 1, fresh0 + 1)
    assert all(x.ends == ["length"] for x in (a, b, c, d))


def test_the_token_on_the_device_is_never_read_by_the_builder():
    """A slot whose token the unfetched step holds keeps ``_TOKEN_PENDING`` (a
    first token) or a token one step old in its state: the rows built from it
    carry a placeholder and the flag, at the position the slot will have."""
    engine = _engine(mixed_step_tokens=24)
    sched = Scheduler(engine)
    a, b = Client(0, 8, max_tokens=30), Client(1, 30, max_tokens=30)
    sched.submit(a.req)
    sched._pass()
    sched.submit(b.req)
    sched.submit(Client(2, 9, max_tokens=2).req)  # keeps an admission due: the pass leaves its last step unfetched
    engine.config.max_prefill_batch = 1
    sched._pass()
    step = sched._mixed_inflight
    assert step is not None and [st.req for _, _, st in step.first] == [b.req]
    st_b = step.first[0][2]
    assert st_b.pending_token == _TOKEN_PENDING and sched._ahead(step.first[0][1], st_b) == 0
    slot_a, st_a = next((s, st) for s, st in sched._slots.items() if st.req is a.req)
    assert sched._ahead(slot_a, st_a) == 1
    rows, n_decode, *_ = sched._build_mixed_rows([])
    assert n_decode == 2 and all(r.from_block and r.token_ids == [0] for r in rows)
    assert {r.slot: r.start for r in rows} == {slot_a: st_a.pos + 1, step.first[0][1]: len(b.req.prompt_ids)}
    _run_out(sched)
    assert a.ends == b.ends == ["length"] and len(a.seen) == len(b.seen) == 30
