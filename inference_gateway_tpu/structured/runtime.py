"""Device-resident mask tables: the accelerator half of structured outputs.

Grammar-constrained decoding must not host-sync mid-chunk (the fused
decode scan advances many tokens per host round trip), so the automaton
lives ON DEVICE: a ``(states_budget, V)`` transition table and a
``(states_budget, W)`` packed-mask table, into which each compiled
grammar is scattered once as a contiguous state SPAN. A slot's mask
state is then just an int32 riding the chained decode carry — every
step gathers its mask row, applies it as an additive −inf bias before
top-k/top-p, samples, and advances the state with one more gather.

Spans are shared across requests by schema hash (the same cache
discipline as the PrefixCache): acquire bumps a refcount, release
drops it, and zero-ref spans stay resident until allocation pressure
evicts them. Global state 0 is the FREE state — all tokens allowed,
self-loop — so unconstrained rows ride the same program at zero
semantic cost.

The per-slot additive logit-bias buffer (OpenAI ``logit_bias``) lives
here too: one ``(max_slots + 1, V)`` float32 row set at admission and
cleared at release (the +1 row is the all-zero OOB target padding rows
gather). Unlike the automaton it exists from construction (ISSUE 49):
every step program takes it as an operand from its first trace, so the
first biased request meets the executables warm-up built. Everything in
this module dispatches asynchronously — no
``.item()`` / ``np.asarray`` on device values (graftlint jax-hot-path
pins the acquire/register/release path).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import threading

import numpy as np

from inference_gateway_tpu.structured.automaton import token_byte_table
from inference_gateway_tpu.structured.compiler import GrammarCompiler, GrammarSession


class StructuredCapacityError(RuntimeError):
    """No device-table span available for a new grammar (budget full of
    still-referenced spans). Admission fails the request cleanly."""

    def __init__(self, needed: int, budget: int) -> None:
        super().__init__(
            f"no contiguous span of {needed} automaton states free in the "
            f"{budget}-state device table (STRUCTURED_MAX_STATES)")
        self.needed = needed
        self.budget = budget


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(table: jax.Array, rows: jax.Array, base: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice(table, rows, (base, 0))


@partial(jax.jit, donate_argnums=(0,))
def _set_row(table: jax.Array, row: jax.Array, index: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice(table, row[None, :], (index, 0))


@partial(jax.jit, donate_argnums=(0,))
def _scatter_span(table: jax.Array, vec: jax.Array, base: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice(table, vec, (base,))


class StructuredRuntime:
    """Owns the compiler cache, the device tables, and span bookkeeping.

    Construction allocates the logit-bias table alone, zeros (29 MB at
    48 slots of a 152k vocabulary): it is an operand of every step
    program, so ``register_slot`` / ``release_slot`` write rows and no
    program is lowered again for a bias. No vocab walk here; the token
    byte table and the AUTOMATON's device tables (4,096 states of that
    vocabulary are 2.5 GB) materialize on the first grammar, so engines
    that never see one keep the programs traced without the mask
    gathers (``live`` stays False)."""

    def __init__(self, tokenizer: Any, vocab_size: int, max_slots: int, *,
                 states_budget: int = 1024, cache_size: int = 64,
                 max_schema_bytes: int = 65536) -> None:
        self.tokenizer = tokenizer
        self.vocab_size = vocab_size
        self.max_slots = max_slots
        self.states_budget = states_budget
        self.cache_size = cache_size
        self.max_schema_bytes = max_schema_bytes
        self.words = (vocab_size + 31) // 32
        self._compiler: GrammarCompiler | None = None
        # session_for runs on serving-edge executor threads: the lock
        # makes the one-time compiler construction (the full-vocab token
        # byte walk) happen exactly once.
        self._compiler_lock = threading.Lock()
        # Sticky device activation of the automaton: flips True on the
        # first grammar-constrained admission and never back — the
        # engine's jitted programs are lowered ONCE more, with the mask
        # gathers (a logit_bias alone flips nothing).
        self.live = False
        self.next_dev: jax.Array | None = None
        self.bits_dev: jax.Array | None = None
        self.bias_dev: jax.Array = jnp.zeros((max_slots + 1, vocab_size), jnp.float32)
        # Per-GLOBAL-state terminal flags (ISSUE 14): True where the
        # grammar is complete (accepting, nothing but EOS left to say) —
        # gathered by the early-exit chunk carry so constrained rows
        # freeze on device the moment their document closes. State 0
        # (the free state) is never terminal.
        self.term_dev: jax.Array | None = None
        # schema hash -> [base, n_states, refcount]
        self._spans: dict[str, list[int]] = {}
        self._free: list[tuple[int, int]] = [(1, states_budget - 1)]
        self._slot_sessions: dict[int, GrammarSession] = {}
        self._slot_biased: set[int] = set()
        # Last compile verdict for the serving edge's metrics
        # (seconds, cache_hit) — read right after session_for.
        self.last_compile: tuple[float, bool] = (0.0, True)

    # -- compilation ---------------------------------------------------
    def compiler(self) -> GrammarCompiler:
        with self._compiler_lock:
            if self._compiler is None:
                eos = getattr(self.tokenizer, "eos_token_id", -1)
                self._compiler = GrammarCompiler(
                    token_byte_table(self.tokenizer, self.vocab_size),
                    self.vocab_size, eos if isinstance(eos, int) else -1,
                    max_states=self.states_budget - 1,
                    cache_size=self.cache_size,
                    max_schema_bytes=self.max_schema_bytes)
            return self._compiler

    def session_for(self, response_format: Any) -> GrammarSession | None:
        """Compile (or cache-hit) a response_format into a per-request
        session; None for text/absent. Raises UnsupportedSchemaError."""
        compiler = self.compiler()
        compiled = compiler.compile_response_format(response_format)
        self.last_compile = (compiler.last_compile_seconds,
                             compiler.last_compile_seconds == 0.0)
        if compiled is None:
            return None
        return GrammarSession(compiled)

    # -- device tables (caller holds the engine lock) ------------------
    def _ensure_live(self) -> None:
        if self.live:
            return
        free_bits = np.zeros((self.states_budget, self.words), np.uint32)
        free_bits[0, :] = np.uint32(0xFFFFFFFF)  # state 0: everything allowed
        self.next_dev = jnp.zeros((self.states_budget, self.vocab_size), jnp.int32)
        self.bits_dev = jnp.asarray(free_bits)
        self.term_dev = jnp.zeros((self.states_budget,), bool)
        self.live = True

    def _alloc(self, n: int) -> int:
        for i, (start, length) in enumerate(self._free):
            if length >= n:
                self._free[i] = (start + n, length - n)
                if self._free[i][1] == 0:
                    del self._free[i]
                return start
        # Evict zero-ref spans (cached grammars no active request uses)
        # and retry once with a coalesced free list.
        evicted = [h for h, span in self._spans.items() if span[2] <= 0]
        if evicted:
            for h in evicted:
                base, length, _refs = self._spans.pop(h)
                self._free.append((base, length))
            self._coalesce()
            for i, (start, length) in enumerate(self._free):
                if length >= n:
                    self._free[i] = (start + n, length - n)
                    if self._free[i][1] == 0:
                        del self._free[i]
                    return start
        raise StructuredCapacityError(n, self.states_budget)

    def _coalesce(self) -> None:
        self._free.sort()
        merged: list[tuple[int, int]] = []
        for start, length in self._free:
            if merged and merged[-1][0] + merged[-1][1] == start:
                merged[-1] = (merged[-1][0], merged[-1][1] + length)
            else:
                merged.append((start, length))
        self._free = merged

    def acquire(self, session: GrammarSession) -> int:
        """Make the session's grammar resident (refcounted span), set its
        span base, and return it. Caller holds the engine lock."""
        self._ensure_live()
        schema_hash = session.compiled.schema_hash
        span = self._spans.get(schema_hash)
        if span is None:
            auto = session.compiled.automaton
            base = self._alloc(auto.n_states)
            # Global rows: allowed transitions offset by the span base
            # (dead entries were collapsed to local 0 at build; they are
            # unreachable through sampling, any in-range value is fine).
            rows = (auto.next_state.astype(np.int64) + base).astype(np.int32)
            assert self.next_dev is not None and self.bits_dev is not None
            self.next_dev = _scatter_rows(self.next_dev, jnp.asarray(rows),
                                          jnp.int32(base))
            self.bits_dev = _scatter_rows(self.bits_dev,
                                          jnp.asarray(auto.mask_bits),
                                          jnp.int32(base))
            assert self.term_dev is not None
            self.term_dev = _scatter_span(
                self.term_dev, jnp.asarray(auto.terminal_states()),
                jnp.int32(base))
            span = [base, auto.n_states, 0]
            self._spans[schema_hash] = span
        span[2] += 1
        session.base = span[0]
        return span[0]

    def register_slot(self, slot: int, session: GrammarSession | None,
                      logit_bias: dict[int, float] | None) -> None:
        """Admission hook: pin the request's grammar span and scatter its
        logit-bias row. Idempotent per (slot, session) — nested prefill
        dispatch paths may register the same admission twice. Caller
        holds the engine lock."""
        if session is not None and self._slot_sessions.get(slot) is not session:
            self.acquire(session)
            self._slot_sessions[slot] = session
        if logit_bias and slot not in self._slot_biased:
            row = np.zeros((self.vocab_size,), np.float32)
            for token_id, bias in logit_bias.items():
                row[token_id] = bias
            self.bias_dev = _set_row(self.bias_dev, jnp.asarray(row),
                                     jnp.int32(slot))
            self._slot_biased.add(slot)

    def release_slot(self, slot: int) -> None:
        """Release hook (engine.release_slot): drop the span refcount and
        zero the bias row. Caller holds the engine lock."""
        session = self._slot_sessions.pop(slot, None)
        if session is not None:
            span = self._spans.get(session.compiled.schema_hash)
            if span is not None and span[2] > 0:
                span[2] -= 1
        if slot in self._slot_biased:
            self._slot_biased.discard(slot)
            self.bias_dev = _set_row(
                self.bias_dev, jnp.zeros((self.vocab_size,), jnp.float32),
                jnp.int32(slot))

    # -- introspection -------------------------------------------------
    def stats(self) -> dict[str, Any]:
        out: dict[str, Any] = {
            "enabled": True,
            "live": self.live,
            "states_budget": self.states_budget,
            "states_resident": sum(s[1] for s in self._spans.values()),
            "spans_resident": len(self._spans),
            "constrained_slots": len(self._slot_sessions),
            "biased_slots": len(self._slot_biased),
        }
        if self._compiler is not None:
            out.update(self._compiler.stats())
        return out
