"""The logit bias as an operand of the one step program (ISSUE 49).

The per-slot ``logit_bias`` table exists from the engine's construction and
every step program adds its rows from its first trace, so the executables
``Engine.warmup`` builds are the ones a biased request runs: no second
lowering of the mixed step and the decode chunk at the first biased request
(3.7-8 s of every warm ``setup_s`` on the chip: ``PERF.md`` section 6, PR 49).
The trace-static flag that is left stands for a GRAMMAR alone: the automaton's
tables stay lazy, and the first grammar request still lowers each program it
runs once more.

Four tiny families (dense, a mixture, the hybrid, the latent-attention
mixture), each through the scheduler: a mixed step admits the prompt, decode
chunks finish it.
"""

import numpy as np
import pytest

import inference_gateway_tpu.serving.engine as engine_mod
from inference_gateway_tpu.otel.device_observatory import JIT_ENTRY_POINTS, DeviceObservatory
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler

FAMILIES = {"dense": "test-tiny", "mixture": "smallthinker-test-tiny",
            "hybrid": "phi4flash-test-tiny", "latent": "xing4-test-tiny"}
# The programs a request of these engines runs that carry the static ``automaton`` flag.
STEP_PROGRAMS = {"_mixed_step_fn", "_decode_chunk_fn_paged_ee"}
PROMPT = [int(t) for t in np.random.default_rng(49).integers(1, 250, 21)]
TOKENS = 10  # the first from the mixed step, the rest from three decode chunks of four
# Large enough to move a greedy stream of random weights off its own path,
# on ids a byte tokenizer renders; one id pushed down.
BIAS = {65: 4.0, 66: 3.5, 90: 2.0, 97: -3.0}


def _engine(family: str, **over) -> Engine:
    kw = dict(model=FAMILIES[family], attention="paged", page_size=8, max_slots=4, max_seq_len=128,
              dtype="float32", use_mesh=False, mixed_step=True, mixed_step_tokens=40, decode_chunk=4,
              prefix_cache=False)
    kw.update(over)
    return Engine(EngineConfig(**kw))


def _serve(engine: Engine, **kw) -> tuple[list[int], bytes]:
    """One greedy request through a scheduler of its own; (tokens, the
    logprobs' float32 bytes)."""
    seen: list[tuple[int, float]] = []
    sched = Scheduler(engine)
    sched.submit(GenRequest(prompt_ids=list(PROMPT), max_tokens=TOKENS, temperature=0.0,
                            callback=lambda tok, lp, fin, why: seen.append((tok, lp)), **kw))
    for _ in range(200):
        if not (sched._waiting or sched._slots or sched._handles or sched._mixed_inflight):
            break
        sched._pass()
    else:
        raise AssertionError("the scheduler never ran out of work")
    return [t for t, _ in seen], np.asarray([lp for _, lp in seen], np.float32).tobytes()


def _cache_sizes() -> dict[str, int]:
    return {name: getattr(Engine, name)._cache_size() for name in JIT_ENTRY_POINTS}


def _bias_at_the_sampler(monkeypatch, vocab: int) -> None:
    """The reference's seam: a program traced without the table (an engine
    built without the structured runtime adds nothing) hands its logits to
    the samplers, and the bias row is added THERE, a constant of the trace."""
    row = np.zeros((vocab,), np.float32)
    for token_id, bias in BIAS.items():
        row[token_id] = bias
    for name in ("sample_tokens", "sample_tokens_pregumbel", "compute_logprobs"):
        real = getattr(engine_mod, name)
        monkeypatch.setattr(engine_mod, name,
                            lambda logits, *a, _real=real, **k: _real(logits + row, *a, **k))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_biased_request_runs_the_executables_warm_up_built(family, monkeypatch):
    engine = _engine(family)
    vocab = engine.model_cfg.vocab_size
    assert engine.mixed_ok
    observatory = DeviceObservatory()
    observatory.attach(engine)
    engine.warmup()
    ledger = observatory.ledger
    assert ledger.recompile_count() == 0 and ledger.snapshot()["warmed"]
    table = engine.structured.bias_dev
    assert table.shape == (4 + 1, vocab) and not engine.structured.live
    warm = _cache_sizes()

    # -- a bias: rows of the table, nothing lowered --------------------------
    unbiased = _serve(engine)
    biased = _serve(engine, logit_bias=dict(BIAS))
    assert _cache_sizes() == warm
    assert ledger.recompile_count() == 0 and ledger.snapshot()["recompile_events"] == []
    assert not engine.structured.live and not engine._mask_args()[0]
    assert engine.structured.bias_dev.shape == table.shape
    assert not np.asarray(engine.structured.bias_dev).any()  # the release cleared the row
    assert len(biased[0]) == len(unbiased[0]) == TOKENS
    assert biased[0] != unbiased[0]  # the bias is large enough to be seen
    assert _serve(engine) == unbiased  # and leaves nothing behind

    # -- a grammar: each program it runs lowered once more, then never again --
    def constrained():
        return _serve(engine, grammar=engine.structured.session_for({"type": "json_object"}))

    first = constrained()
    assert engine.structured.live and engine._mask_args()[0]
    grown = {name: n - warm[name] for name, n in _cache_sizes().items() if n != warm[name]}
    assert grown == {name: 1 for name in STEP_PROGRAMS}
    events = ledger.snapshot()["recompile_events"]
    assert sorted(e["program"] for e in events) == sorted(n.lstrip("_") for n in STEP_PROGRAMS)
    assert all(any("automaton=False -> automaton=True" in d for d in e["diff"]) for e in events)
    with_grammar = _cache_sizes()
    assert constrained() == first
    assert _serve(engine, grammar=engine.structured.session_for({"type": "json_object"}),
                  logit_bias=dict(BIAS))[0] != []
    assert _cache_sizes() == with_grammar and ledger.recompile_count() == len(STEP_PROGRAMS)
    # an unconstrained row of the grammar's programs: the free state masks nothing
    assert _serve(engine) == unbiased
    assert _serve(engine, logit_bias=dict(BIAS)) == biased

    # -- the parent's programs: no table, nothing added -----------------------
    parent = _engine(family, structured=False)
    assert parent.structured is None and parent._mask_args()[3] is None
    assert _serve(parent) == unbiased
    # -- and their logits plus the bias row, to the bit ------------------------
    reference = _engine(family, structured=False)
    _bias_at_the_sampler(monkeypatch, vocab)
    assert _serve(reference) == biased
