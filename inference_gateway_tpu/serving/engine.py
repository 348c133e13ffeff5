"""The TPU model engine: weights, a cache and the jitted step programs over them.

Continuous-batching substrate (SURVEY.md §7 stage 5), static shapes throughout:

- ``max_slots`` rows over a PAGED pool (``--attention paged``: the serving
  path) or a dense slot cache (the benchmark's reference child, pp, tests).
- A paged engine steps through two programs: the ragged MIXED step (any mix of
  prompt chunks and decode rows in one launch, packed to ``mixed_budget``
  tokens) and the early-exit DECODE CHUNK (up to ``decode_chunk`` steps a
  launch, sampling and stopping on the device, chained without a host round
  trip). Bucketed prefill, prefix-tail, ring, multimodal and the speculative
  rounds are programs of their own beside them.
- Which model module the programs trace, what the pool holds, which rows a
  step counts and what a configuration may not ask are the model family's to
  say: one record, ``models/family.py``'s ``family_of(model_cfg)``.

Weights/caches are bf16 by default, sharded over a (dp, sp, tp) mesh when
more than one device is visible (``choose_mesh``).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from inference_gateway_tpu.models import llama
from inference_gateway_tpu.models.family import StatusView, family_of, preset_config
from inference_gateway_tpu.ops.sampling import (
    chunk_gumbels,
    chunk_row_keys,
    effective_top_k,
    compute_logprobs,
    packed_mask_bias,
    per_row_keys,
    sample_tokens,
    sample_tokens_pregumbel,
    top_k_plan,
)
from inference_gateway_tpu.otel.profiling import cause_span, phase_span, stamp_since
from inference_gateway_tpu.parallel.mesh import create_mesh, default_mesh_shape
from inference_gateway_tpu.parallel.sharding import (
    check_divisibility,
    named,
    shard_params,
)
from inference_gateway_tpu.serving.packed import PackedLayout
from inference_gateway_tpu.serving.tokenizer import load_tokenizer
from inference_gateway_tpu.utils.platform import device_info


@dataclass
class EngineConfig:
    model: str = "test-tiny"  # preset name (models/llama.py PRESETS) or HF path
    tokenizer: str | None = None
    max_slots: int = 8
    max_seq_len: int = 512
    prefill_buckets: tuple[int, ...] = (16, 32, 64, 128, 256, 512)
    max_prefill_batch: int = 4
    dtype: str = "bfloat16"
    top_k: int = 64
    seed: int = 0
    use_mesh: bool = True  # shard over all visible devices when >1
    # Explicit mesh factoring {"dp":1,"sp":4,"tp":2}; None = auto
    # (default_mesh_shape). sp>1 turns on ring-attention prefill for
    # prompts beyond the largest bucket (SURVEY.md §2.4 SP row).
    mesh_shape: dict | None = None
    checkpoint_path: str | None = None  # orbax checkpoint dir (serving/checkpoint.py)
    vision_model: str | None = None  # vision preset (models/vision.py) for multimodal
    attention: str = "dense"  # "dense" (contiguous cache) | "paged" (Pallas kernel)
    page_size: int = 32
    num_pages: int = 0  # 0 = full reservation
    quantize: str | None = None  # "int8" | "int4" weight-only quantization (ops/quant.py)
    quant_group: int = 0  # int4 group size; 0 = auto (tp-aware, ≤128)
    prefix_cache: bool = True  # share full prefix KV pages across requests (paged mode)
    # Decode steps fused into one jitted lax.while_loop per host
    # roundtrip (ISSUE 14). Sampling feeds back on-device, and so does
    # stopping: per-slot stop token tables (EOS + stop_token_ids),
    # max_tokens budgets and the grammar accept-state ride the carry, a
    # finished slot freezes (no further sampling, KV writes masked) and
    # the loop exits as soon as every active slot is done. The host
    # reads a (chunk, slots) token block once per chunk; its stop
    # detection stays authoritative (the device criterion is a strict
    # subset — stop STRINGS and disconnects are host-side backstops),
    # so a larger chunk wastes steps only on those finishes.
    decode_chunk: int = 8
    # Decode chunks the scheduler leaves dispatched and unfetched when a
    # pass ends (Scheduler._process_handles). The next pass submits one
    # more BEFORE it fetches the oldest, so at any depth the device has
    # the next program queued when a chunk ends, and while the thread
    # blocks in that fetch the device holds the running chunk and `depth`
    # more behind it. What a queued chunk costs: it cannot be cancelled,
    # an arrival is seen only when the fetch returns, and its admission
    # drains whatever is queued then, so a request that finds a slot free
    # waits out up to depth+1 chunks in front of its prefill: 8-16 decode
    # steps at depth 1, 16-24 at depth 2 (~55 ms more of a 260 ms TTFT at
    # 7B shapes). A row's finish is seen `depth` chunks late (one only the
    # host sees, a stop string or a client gone, also wastes up to
    # depth*decode_chunk steps). What depth 2 buys: a chunk of cushion
    # against a host stall longer than a chunk's device time (the host's
    # work a chunk is 2-4 ms against 85-110), and a closed loop's
    # admissions gather a chunk longer (fewer, longer trains: 2-4% of
    # its per-token time). _reserve_horizon_pages follows the same depth.
    pipeline_depth: int = 1
    # Speculative decoding: spec_draft names a llama-family draft model
    # (preset name or HF path, same vocab as the target) that proposes
    # spec_k tokens per round, or the special value "ngram" for
    # prompt-lookup drafting — proposals come from matching the
    # request's own trailing n-gram against its earlier tokens (host-
    # side, zero weights, provably >0 acceptance on repetitive text).
    # The target verifies all proposals in one forward
    # (serving/speculative.py). None = disabled.
    spec_draft: str | None = None
    spec_k: int = 4
    # Acceptance-adaptive n-gram speculation (opt-in): when the rolling
    # tokens-per-slot-round falls below spec_min_tokens_per_round the
    # scheduler falls back to the pipelined non-spec decode loop (a
    # verify forward that mostly rejects costs ~a decode step and emits
    # ~1 token — pure overhead), then re-probes speculation every
    # spec_probe_every engine steps for spec_probe_rounds rounds.
    # GREEDY streams are token-identical across every switch (rejection
    # sampling accepts exactly the target argmax; tests pin parity).
    # Seeded temperature>0 streams stay within the request's sampling
    # DISTRIBUTION but the sample path depends on which mode served
    # each position (the two paths derive their seeded randomness
    # differently), so per-seed byte-reproducibility holds only while
    # the mode doesn't switch mid-stream — the tradeoff this flag opts
    # into. Model-draft spec ignores these knobs (its draft cache
    # cannot rejoin after falling arbitrarily behind).
    spec_adaptive: bool = False
    spec_min_tokens_per_round: float = 1.3
    spec_probe_rounds: int = 8
    spec_probe_every: int = 128
    # Ragged mixed-step serving (ISSUE 12): ONE jitted program computes
    # prefill-chunk rows and decode rows of the same engine step in a
    # single ragged launch (ops/paged_attention ragged kernel), so the
    # scheduler can interleave a long prompt's chunked prefill with
    # active decode streams — no prefill head-of-line blocking — and
    # paged engines gain a long-prompt path (chunked ragged prefill up
    # to the context window). Paged, non-speculative, non-MoE dense
    # engines only; ignored elsewhere. mixed_step_tokens is the packed
    # query budget per step (the ONE compiled shape); 0 = auto (largest
    # prefill bucket + max_slots, floored at max_slots + 8).
    mixed_step: bool = False
    mixed_step_tokens: int = 0
    # Structured outputs (ISSUE 13): grammar-constrained decoding via
    # device-resident token-mask automaton tables. structured_states is
    # the shared table budget in automaton states — device memory is
    # budget x vocab x 4 bytes for the transition table (size it
    # consciously for 100k-vocab models); those tables only materialize
    # on the first grammar-constrained request. The per-slot logit_bias
    # table ((max_slots + 1) x vocab float32) is built with the engine
    # and every step program adds its rows (ISSUE 49); structured=False
    # builds neither, and its programs add nothing.
    structured: bool = True
    structured_states: int = 4096
    structured_cache: int = 64
    structured_max_schema_bytes: int = 65536


# Width of the per-slot on-device stop-token table (ISSUE 14): EOS plus
# up to STOP_TABLE_WIDTH-1 request stop ids, padded with -1 (never a
# vocab id). Requests with more stop ids than fit keep the overflow
# host-side only — the device stops later (or not at all) and the host
# finish check truncates exactly as before, so truncation is always
# safe, never wrong.
STOP_TABLE_WIDTH = 8


def build_stop_row(eos_id: int | None, stop_ids=()) -> np.ndarray:
    """One slot's padded device stop row: EOS first, then sorted stop
    ids, truncated to the table width."""
    row = np.full((STOP_TABLE_WIDTH,), -1, np.int32)
    ids: list[int] = []
    if eos_id is not None and eos_id >= 0:
        ids.append(int(eos_id))
    ids.extend(t for t in sorted(stop_ids) if t not in ids)
    ids = ids[:STOP_TABLE_WIDTH]
    row[: len(ids)] = ids
    return row


class PromptTooLongError(ValueError):
    """A prompt above the engine's admittable limit (ISSUE 12 satellite):
    carries the structured fields the serving edge's ``prompt_too_long``
    400 body reports, so a prompt that slips past the edge check (direct
    scheduler users, drifting limits) still fails with attribution
    instead of a bare ValueError. Subclasses ValueError for existing
    callers that catch the old shape."""

    def __init__(self, prompt_tokens: int, max_prompt_tokens: int) -> None:
        super().__init__(
            f"prompt of {prompt_tokens} tokens exceeds the largest admittable "
            f"prompt ({max_prompt_tokens} tokens) for this engine configuration")
        self.prompt_tokens = prompt_tokens
        self.max_prompt_tokens = max_prompt_tokens


@dataclass
class PrefillResult:
    slot: int
    first_token: int
    logprob: float


@dataclass
class PrefillHandle:
    """An in-flight (or already-materialized) batch prefill: toks and
    logprobs may be device futures; fetch with prefill_fetch. When the
    engine had chained decode state at submit time, the results were
    also scattered into it on-device (``scattered``), so decode chunks
    keep chaining across the admission with no host sync."""

    toks: object  # (Bp,) device array or np array
    logprobs: object
    slots: list
    scattered: bool = False


@dataclass
class _DecodeChunkHandle:
    """An in-flight fused decode chunk: ``toks_lp`` is a (2·n_steps, S)
    device-array future (tokens stacked atop logprobs) that materializes
    when the chunk finishes on device; fetch with decode_chunk_fetch."""

    toks_lp: jax.Array
    n_steps: int
    # Host phases of the submit (ms), stamped when the caller asked for
    # them (ISSUE 24); the fetch adds its own to the dict the caller holds.
    phases: dict | None = None
    # Host arrays the submit staged for upload (ISSUE 25): 1 for a fresh
    # submit (the packed buffer), 0 for a chained one.
    h2d_arrays: int = 0
    # What the program counted and the fetch block carried beside the
    # tokens (ISSUE 28), filled by the fetch: a mixture's ``experts_read`` and ``expert_visits``.
    # Rides the step's timeline record.
    stats: dict | None = None
    # A drafting model's chunk (ISSUE 56): how many of each round's two tokens a
    # row emitted, (n_steps, S), filled by the fetch; the fresh submit it chains
    # from and its place among the chunks dispatched, by which the fetch tightens
    # the page horizon.
    counts: object = None
    epoch: int = 0
    sent: int = 0


@dataclass
class MixedRow:
    """One row of a ragged mixed step (ISSUE 12): ``token_ids`` are the
    new tokens this step writes+attends for ``slot`` — a decode row's
    single pending token, or a prefill chunk — starting at cache
    position ``start``. ``kind`` is accounting/metrics attribution only;
    the engine computes both identically (that's the point)."""

    slot: int
    token_ids: list
    start: int
    kind: str = "decode"  # "decode" | "prefill"
    temp: float = 0.0
    top_p: float = 1.0
    seed: int | None = None
    # Grammar-constrained rows (ISSUE 13): the slot's GLOBAL automaton
    # state in the device mask tables; 0 = the free (unconstrained) row.
    mask_state: int = 0
    # The row's one token is what the mixed step dispatched last sampled
    # for this slot, still on the device (ISSUE 40): ``token_ids`` holds a
    # placeholder and the program reads the token from that step's block.
    from_block: bool = False
    # A prompt's chunk in a model that drafts for itself (ISSUE 56): the prompt's
    # token behind this chunk's last, which the next-token module reads beside it;
    # None where the prompt ends with this chunk (the token the step samples).
    next_token: int | None = None


@dataclass
class MixedStepHandle:
    """An in-flight mixed step: ``toks_lp`` is a (2, R) device future
    (per-row sampled token atop its logprob); fetch with
    mixed_step_fetch. Row index == slot id (the page table is
    slot-aligned); rows without queries this step carry garbage."""

    toks_lp: jax.Array
    rows: list
    # Host phases of the submit (ms): lock_wait, pages, assemble,
    # upload_dispatch; mixed_step_fetch adds fetch_wait and fetch_copy.
    # None unless the caller passed a dict to stamp (ISSUE 24).
    phases: dict | None = None
    # Host arrays the submit staged for upload (ISSUE 25): 1, the packed
    # descriptor buffer.
    h2d_arrays: int = 0
    # The fetch block's rows past the tokens and logprobs (ISSUE 28): a
    # mixture's ``expert_tokens_max`` / ``expert_tokens_mean`` /
    # ``experts_read``, filled by the fetch for the timeline record.
    stats: dict | None = None
    # A drafting model's step (ISSUE 56): how many tokens each row emitted, filled by the fetch.
    counts: object = None


def _fetch_blocks(phases: dict | None, path: str, *blocks) -> list[np.ndarray]:
    """A step's result blocks on the host, under the fetch's two stamps
    (ISSUE 38): ``fetch_wait`` until the blocks are ready on the device
    (span ``engine.<path>.fetch``), ``fetch_copy`` for the rest of their
    way to the host (``engine.<path>.fetch_copy``). The copies are queued
    before the wait, as ``np.asarray`` of an unready array queues its own:
    left to the second stamp they would start a device round trip late
    (0.1-0.25 ms a fetch on the chip, ``PERF.md`` section 6, PR 38). A
    block that is on the host already (a prefix-cache hit's prefill) passes
    through."""
    with phase_span(phases, "fetch_wait", f"engine.{path}.fetch"):
        for block in blocks:
            if isinstance(block, jax.Array):
                block.copy_to_host_async()
        jax.block_until_ready(blocks)
    with phase_span(phases, "fetch_copy", f"engine.{path}.fetch_copy"):
        return [np.asarray(block) for block in blocks]


def _experts_read(rows: jnp.ndarray, m: int) -> jnp.ndarray:
    """rows (L, E): how many rows each expert of each layer multiplied in
    one step of ``m`` rows a layer -> (the distinct experts a layer read,
    the visits the expert kernels made a layer (ISSUE 42: by the tile rule
    ``ops/moe.py`` exports; visits / experts read is the passes an expert's
    block cost)), each a mean over the layers."""
    from inference_gateway_tpu.ops.moe import visit_count

    return jnp.stack([jnp.mean(jnp.sum(rows > 0, axis=1).astype(jnp.float32)),
                      jnp.mean(visit_count(rows, m).astype(jnp.float32))])


def choose_mesh(config: EngineConfig, model_cfg, n_dev: int):
    """The mesh an engine shards over, and whether it is the pipeline
    layout: ``(mesh or None, pp)``. One device, or ``use_mesh`` off: none.
    ``mesh_shape`` with pp > 1: layers AND the KV cache shard by stage
    over "pp", tp within a stage (dense-cache llama family only). A
    mixture without a ``mesh_shape``: experts on a dedicated ``ep`` axis,
    tp inside each expert. A family whose record refuses "mesh" (a state
    store, an indexer, a next-token module, two stacks): none, and a
    ``mesh_shape`` is refused by name. Everything else: ``mesh_shape``'s dp/sp/tp (a
    mixture's ep too) or the default factoring, tp degraded toward dp
    until it tiles the model."""
    family = family_of(model_cfg)
    shape = config.mesh_shape or {}
    if not config.use_mesh or n_dev <= 1:
        return None, False
    if "mesh" in family.refuses:
        # Asked for by name it is refused; left to the default, the model is served
        # on one device (``mesh_axes`` and /debug/status say {"tp": 1}).
        if shape:
            raise ValueError(f"{config.model}: " + family.refuses["mesh"].format(shape=shape))
        return None, False
    if shape.get("pp", 1) > 1:
        # Pipeline-parallel serving (SURVEY §2.4 PP row). Only the
        # dense-cache llama family for now — the paged pool, MoE
        # dispatch, and the draft model would each need their own
        # stage-sharded layout.
        assert not family.experts, "pp serving: MoE not supported"
        assert config.attention == "dense", "pp serving requires dense cache"
        assert config.spec_draft is None, "pp serving: speculative not supported"
        assert config.vision_model is None, "pp serving: multimodal not supported"
        if model_cfg.num_layers % shape["pp"]:
            raise ValueError(f"num_layers={model_cfg.num_layers} not divisible by pp={shape['pp']}")
        from inference_gateway_tpu.parallel.mesh import create_pp_mesh

        mesh = create_pp_mesh(dp=shape.get("dp", 1), pp=shape["pp"], tp=shape.get("tp", 1))
        check_divisibility(model_cfg, mesh)
        return mesh, True
    if family.experts:
        # Experts ride a dedicated ep axis; tp shards within each
        # expert (BASELINE config 5 layout). A mesh_shape is honoured:
        # {"tp": 4} splits every expert over tp alone.
        from inference_gateway_tpu.parallel.mesh import create_moe_mesh

        if shape:
            ep, tp = shape.get("ep", 1), shape.get("tp", 1)
        else:
            ep = next((c for c in (8, 4, 2) if n_dev % c == 0 and model_cfg.num_experts % c == 0), 1)
            rem = n_dev // ep
            tp = next((c for c in (4, 2) if rem % c == 0 and model_cfg.num_kv_heads % c == 0), 1)
        return create_moe_mesh(dp=n_dev // (ep * tp), sp=1, ep=ep, tp=tp), False
    if shape:
        sp, tp = shape.get("sp", 1), shape.get("tp", 1)
    else:
        _, sp, tp = default_mesh_shape(n_dev)
    # tp must tile the model; degrade toward dp otherwise.
    while tp > 1 and (model_cfg.num_kv_heads % tp or model_cfg.intermediate_size % tp):
        tp //= 2
    mesh = create_mesh(dp=n_dev // (sp * tp), sp=sp, tp=tp)
    check_divisibility(model_cfg, mesh)
    return mesh, False


def _int4_group(model_cfg, quant_group: int, tp: int) -> int:
    """int4 group size: must (a) divide every contraction dim and (b)
    leave the per-weight group count divisible by tp, so a tp shard of an
    input-sharded weight owns whole groups (otherwise group boundaries
    cross shard boundaries and XLA reshards the weight stream every
    step)."""
    cins = [model_cfg.hidden_size, model_cfg.num_heads * model_cfg.hd, model_cfg.intermediate_size]

    def group_ok(g: int) -> bool:
        return all(c % g == 0 and (c // g) % tp == 0 for c in cins)

    if quant_group:
        if not group_ok(quant_group):
            raise ValueError(
                f"quant_group={quant_group} incompatible with model dims "
                f"{cins} under tp={tp}: need cin % group == 0 and "
                f"(cin/group) % tp == 0 for every matmul input dim")
        return quant_group
    group = min(128, min(cins) // tp if tp > 1 else min(cins))
    while group > 2 and not group_ok(group):
        group //= 2
    if not group_ok(group):
        raise ValueError(f"no int4 group size tiles model dims {cins} under tp={tp}")
    return group


def build_params(model_cfg, model, seed: int, dtype, quantize: str | None, mesh, *,
                 quant_group: int = 0, pp: bool = False, params=None, lap=None):
    """The weight tree an engine serves: ``model``'s (models/llama.py or
    models/mixtral.py) initialiser from ``seed`` unless ``params`` are
    handed in, weight-only quantised (``quantize`` "int8" | "int4"), laid
    out over ``mesh``. The one builder: ``Engine.__init__`` calls it, and
    so does whoever wants the tree an engine would hold (the benchmark's
    reference child builds an engine for it today).

    ``lap(key, *wait_for)`` (optional) closes a start-up span at each
    stage: "params_s", "quantize_s".

    Weight-only int8 halves the per-step weight HBM stream. Quantize
    BEFORE sharding so the mesh path lays out (q, scale) pairs with
    quantized_specs — int8 composes with meshes and MoE."""
    lap = lap or (lambda key, *wait_for: None)
    seeded = params is None  # ours to give up leaf by leaf when it is laid out
    if quantize in ("int8", "int4"):
        from inference_gateway_tpu.ops.quant import (
            init_quantized_llama_params,
            quantize_llama_params,
        )

        group = 128
        if quantize == "int4":
            group = _int4_group(model_cfg, quant_group,
                                mesh.shape.get("tp", 1) if mesh is not None else 1)

        def _fp_bytes() -> int:
            shapes = jax.eval_shape(
                partial(model.init_params, cfg=model_cfg, dtype=dtype), jax.random.PRNGKey(seed))
            return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))

        if params is None and _fp_bytes() > 2 << 30:
            # Random-weight quantized build at scale: init + quantize
            # one layer at a time so the full-precision tree is never
            # resident — Llama-3-8B-int4 then fits ONE 16 GiB chip
            # (full bf16 init alone would need ~16 GiB), and a mixture's
            # expert leaves are written block by block (ISSUE 28). The
            # per-layer key folding makes the values differ from
            # init_params, so small models take the quantize-after-init
            # path below and stay weight-identical to an unquantized
            # engine with the same seed (tests/test_quant.py relies on
            # this). (Built quantised: all of it is params_s, quantize_s 0.)
            params = init_quantized_llama_params(
                jax.random.PRNGKey(seed), model_cfg, mode=quantize, group=group, dtype=dtype)
            lap("params_s", params)
        else:
            if params is None:
                params = model.init_params(jax.random.PRNGKey(seed), model_cfg, dtype=dtype)
            lap("params_s", params)
            params = jax.jit(partial(quantize_llama_params, mode=quantize, group=group))(params)
        lap("quantize_s", params)
    elif params is None:
        params = model.init_params(jax.random.PRNGKey(seed), model_cfg, dtype=dtype)
    if mesh is not None:
        from inference_gateway_tpu.parallel.sharding import pp_param_specs, quantized_specs

        if pp:
            specs = pp_param_specs(model_cfg, quantized=quantize)
        else:
            specs = model.param_specs(model_cfg)
            if quantize in ("int8", "int4"):
                specs = quantized_specs(specs, mode=quantize)
        params = shard_params(params, mesh, specs, donate=seeded)
    lap("params_s", params)
    return params


class Engine:
    """Owns the weights, the cache, the family's record and the jitted step
    programs; the scheduler drives it through the submit / fetch pairs."""

    def __init__(self, config: EngineConfig, params=None, model_cfg: llama.LlamaConfig | None = None):
        self.config = config
        self.dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        # Start-up spans (ISSUE 24): seconds by construction stage, each
        # closed by a block_until_ready so that a stage's device work is
        # not billed to whichever later stage first waits. Served under
        # /debug/status "startup" with the sidecar's own stages.
        self.startup: dict[str, float] = dict.fromkeys(
            ("backend_s", "params_s", "quantize_s", "kv_pool_s", "structured_s"), 0.0)
        mark = time.perf_counter()

        def lap(key: str | None, *wait_for) -> None:
            nonlocal mark
            jax.block_until_ready(wait_for)
            now = time.perf_counter()
            if key is not None:
                self.startup[key] += now - mark
            mark = now

        preset = preset_config(config.model)
        if model_cfg is not None:
            self.model_cfg = model_cfg
        elif config.checkpoint_path:
            from inference_gateway_tpu.serving.checkpoint import load_checkpoint

            params, self.model_cfg = load_checkpoint(config.checkpoint_path, dtype=self.dtype)
        elif preset is not None:
            self.model_cfg = preset
        else:
            self.model_cfg, params = self._load_hf(config.model)

        # What serving this config takes, as its model module says it (models/family.py):
        # the module whose forwards the step programs trace (same call contract for
        # every family), the pool, the counted rows, and what the path does not carry.
        self.family = family = family_of(self.model_cfg)
        self._model = family.module
        self.is_moe = family.experts
        self.carries_state = family.state_store
        self.is_mla = family.pool == "latent"
        self.mtp = family.round_tokens == 2
        asked = {"spec_draft": config.spec_draft is not None, "vision_model": config.vision_model is not None,
                 "int4": config.quantize == "int4"}
        for feature, wanted in asked.items():
            if wanted and feature in family.refuses:   # refused by name, before anything is built
                raise ValueError(f"{config.model}: {family.refuses[feature]}")
        rows = family.cached_rows
        if config.attention == "paged" and rows is not None and rows.page_size != config.page_size:
            raise ValueError(f"{config.model}: its pool is laid out for pages of {rows.page_size} rows, "
                             f"not --page-size {config.page_size}")
        # Rows of the pool by context (identity for every family but one whose rows are
        # not one a token: ``Family.cached_rows``), for the host's lengths and indices
        # and, traced, for those a decode chunk derives on the device.
        self._rows = rows or (lambda n: n)
        # Presets have no tokenizer files: straight to the byte tokenizer,
        # never a hub look-up by preset name.
        self.tokenizer = load_tokenizer(config.tokenizer or (None if preset is not None else config.model))

        lap(None)
        n_dev = len(jax.devices())
        lap("backend_s")  # the first touch brings the runtime up
        self.mesh, self.pp = choose_mesh(config, self.model_cfg, n_dev)
        self.params = build_params(
            self.model_cfg, self._model, config.seed, self.dtype, config.quantize, self.mesh,
            quant_group=config.quant_group, pp=self.pp, params=params, lap=lap)

        # Paged serving for dense AND MoE families. The Pallas decode
        # kernel runs single-device or shard_mapped over tp; the GSPMD
        # gather path covers every other layout.
        self.paged = config.attention == "paged"
        self.allocator = None
        self.prefix_cache = None
        # Device observatory (ISSUE 19): attach() shadows the jitted
        # entry points with compile-ledger wrappers and this attribute
        # feeds the transfer audit. None = observability off — every
        # seam pays exactly one attribute check (same discipline as the
        # scheduler's timeline).
        self.observatory = None
        if self.paged:
            from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig, PageAllocator

            self.page_cfg = PagedCacheConfig(
                page_size=config.page_size, num_pages=config.num_pages,
                max_slots=config.max_slots, max_seq_len=config.max_seq_len, rows=family.cached_rows,
            )
            self.allocator = PageAllocator(self.page_cfg)
            # The family's own pool (``family.pool``), and beside it whatever it keeps a slot.
            cache = self._model.init_paged_cache(self.model_cfg, self.page_cfg, dtype=self.dtype)
            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P

                paged_specs = {"k": P(None, None, None, "tp"), "v": P(None, None, None, "tp")}
                cache = jax.device_put(cache, named(self.mesh, paged_specs))
            self.cache = cache
            self._flat_size = self.allocator.num_pages * config.page_size
            if config.prefix_cache:
                from inference_gateway_tpu.serving.kv_cache import PrefixCache

                # Pages can be handed over, a recurrent state cannot: with a
                # state store every hit is declined, and counted; so is one on a
                # pool whose pages of rows are not pages of tokens.
                self.prefix_cache = PrefixCache(
                    self.allocator, decline_hits=self.carries_state or family.cached_rows is not None,
                    successor=self.mtp)
        else:
            cache = self._model.init_cache(self.model_cfg, config.max_slots, config.max_seq_len, dtype=self.dtype)
            if self.mesh is not None:
                # Slot axis stays replicated (slots are scheduled
                # host-side); kv-heads shard on tp; under pp the LAYER
                # axis shards by stage alongside the weights.
                from jax.sharding import PartitionSpec as P

                lead = "pp" if self.pp else None
                cache_specs = {"k": P(lead, None, None, "tp", None), "v": P(lead, None, None, "tp", None)}
                cache = jax.device_put(cache, named(self.mesh, cache_specs))
            self.cache = cache

        lap("kv_pool_s", self.cache)

        # Ragged mixed-step serving (ISSUE 12): one compiled program per
        # engine step for any prefill/decode mix, dense or a mixture
        # (ISSUE 28) — spec rounds keep their own loop, a mixture whose
        # experts ride an ep axis keeps the bucketed paged path,
        # pp/multimodal carry state the ragged program doesn't.
        # A model with a state store admits through the ragged step alone
        # (the one program that hands a segment its slot's state), so a
        # paged engine of it is a mixed engine whatever the flag says; so
        # is one with an indexer (each query of a chunk selects for itself)
        # or a latent pool: ``family.mixed_alone``.
        ep = self.mesh.shape.get("ep", 1) if self.mesh is not None else 1
        self.mixed_ok = (
            self.paged and (config.mixed_step or family.mixed_alone)
            and config.spec_draft is None
            and ep == 1 and not self.pp and config.vision_model is None
        )
        biggest_bucket = max((b for b in config.prefill_buckets
                              if b <= config.max_seq_len), default=config.max_seq_len)
        self.mixed_budget = config.mixed_step_tokens or (biggest_bucket + config.max_slots)
        # Progress requires room for one prefill token past a full decode
        # batch (two queries a row where the model drafts for itself: ISSUE 56);
        # pad a little so chunks aren't degenerate.
        self.round_tokens = family.round_tokens   # the page horizon and the fetch block count by it too
        self.mixed_budget = max(self.mixed_budget, self.round_tokens * config.max_slots + 8)

        # The dispatch verdict this engine's layouts take (ISSUE 12
        # satellite): surfaced as the engine.attention_path gauge and a
        # /debug/status field. On a TPU the verdict is also enforced: a
        # paged engine whose layout lands on the gather fallback without
        # the IG_TPU_PAGED_KERNEL=0 switch asking for it would look
        # healthy and serve at a fraction of the chip, so it refuses to
        # build. (Interpret mode cannot occur there: the kernels take it
        # from the same utils.platform.on_tpu.)
        self.device = device_info()
        self.ragged_attention_plan: dict | None = None
        self.decode_attention_plan: dict | None = None
        self.pool_write_plan: dict | None = None
        if self.paged:
            from inference_gateway_tpu.ops.paged_attention import (
                FORCE_PAGED_KERNEL,
                decode_walk_plan,
                paged_dispatch,
                ragged_tile_plan,
            )

            platform = self.device["platform"]
            mesh_tp = self.mesh.shape.get("tp", 1) if self.mesh is not None else 1
            # (A latent pool: every head reads one key row, ops/mla_attention.py.)
            pool_heads, pool_row = ((1, self.model_cfg.latent_row) if self.is_mla else
                                    (self.model_cfg.num_kv_heads, self.model_cfg.num_kv_heads * self.model_cfg.hd))
            self.attention_path, self.attention_path_reason = paged_dispatch(
                pool_heads, self.model_cfg.num_heads, pool_row, tp=mesh_tp,
                platform=platform,
                n_devices=int(self.mesh.devices.size) if self.mesh is not None else 1,
                force=FORCE_PAGED_KERNEL)
            if (platform == "tpu" and self.attention_path == "gather"
                    and FORCE_PAGED_KERNEL != "0"):
                raise RuntimeError(
                    f"paged engine on a TPU ({self.device}) dispatched to the gather "
                    f"fallback: {self.attention_path_reason}")
            # The mixed step's attention for this layout (ISSUE 34, ISSUE 44)
            # and the decode kernel's page walk (ISSUE 37), a tp shard's where
            # the kernels run on a shard's heads: static per engine, so
            # /debug/status fields; the scheduler counts from the first which
            # rows take the wide tile and the pages their tiles walk. A model
            # whose attention reads what an indexer chooses holds no paged
            # kernel.
            kv_heads = getattr(self.model_cfg, "kernel_kv_heads", self.model_cfg.num_kv_heads)
            shards = mesh_tp if self.attention_path == "kernel_sharded" else 1
            layout = (self.model_cfg.num_heads // shards, kv_heads // shards,
                      self.model_cfg.num_kv_heads * self.model_cfg.hd // kv_heads, config.page_size,
                      self.page_cfg.max_pages_per_slot, self.dtype)
            if family.pool == "latent":
                from inference_gateway_tpu.ops.mla_attention import mla_walk_plan

                self.ragged_attention_plan = mla_walk_plan(
                    self.model_cfg.num_heads, self.model_cfg.latent_row, config.page_size,
                    self.page_cfg.max_pages_per_slot, self.dtype)
                self.decode_attention_plan = {
                    "pages_per_visit": self.ragged_attention_plan["one_query_pages_per_visit"],
                    "operand_dtype": jnp.dtype(self.dtype).name, "skips_empty_slots": True}
            elif family.pool == "kv":
                if self.mixed_ok:
                    self.ragged_attention_plan = ragged_tile_plan(*layout)
                self.decode_attention_plan = decode_walk_plan(*layout)
            # The pools' row writes (ISSUE 62): which path a launch's rows take
            # into the flat pools (ops/pool_write.py), by step program; static per
            # engine. The scheduler counts a mixed step's copies from its tiles.
            if family.row_writes:
                from inference_gateway_tpu.ops.pool_write import pool_write_plan, sharded_over

                layers, pages, page, row = self.cache["k"].shape
                programs = {"decode_chunk": config.max_slots * self.round_tokens}
                if self.mixed_ok:
                    programs["mixed_step"] = self.mixed_budget
                self.pool_write_plan = pool_write_plan(
                    programs, row, self.cache["k"].dtype, layers * pages * page, platform,
                    sharded=sharded_over(self.mesh))
        else:
            self.attention_path = "dense"
            self.attention_path_reason = "contiguous slot cache (paged attention not in use)"

        # The sampler's verdict (ISSUE 31, 57): which selection, at which group
        # width, this engine's (vocabulary, top_k) takes in select_top_k —
        # static per engine, so it is a /debug/status field and not a counter.
        self.sampler_plan = top_k_plan(
            self.model_cfg.vocab_size,
            effective_top_k(config.top_k, self.model_cfg.vocab_size))

        # Optional draft model for speculative decoding (config.spec_draft
        # names a llama-family preset/checkpoint sharing the target's
        # vocab). The draft keeps its own DENSE slot cache — it is small,
        # and dense rows make the ≤2-token catch-up writes trivial.
        self.spec = config.spec_draft is not None
        # Prompt-lookup ("ngram") drafting has NO draft model: proposals
        # are host-side n-gram continuations and the engine only runs
        # the one-pass target verify — so it composes with meshes (the
        # round-3 single-device restriction applied to draft WEIGHTS,
        # which don't exist here; round-4 verdict next #7).
        self.spec_ngram = config.spec_draft == "ngram"
        self.draft_cfg = None
        self.draft_params = None
        self.draft_cache = None
        if self.spec and not self.spec_ngram:
            assert not self.is_moe, ("speculative decoding with a separate draft model: mixture targets not supported "
                                     "(a latent-family preset with a next-token module drafts for itself)")
            if config.spec_draft in llama.PRESETS:
                self.draft_cfg = llama.PRESETS[config.spec_draft]
                self.draft_params = llama.init_params(
                    jax.random.PRNGKey(config.seed + 11), self.draft_cfg, dtype=self.dtype)
            else:
                self.draft_cfg, self.draft_params = self._load_hf(config.spec_draft)
            assert self.draft_cfg.vocab_size == self.model_cfg.vocab_size, (
                "draft and target must share a vocabulary")
            self.draft_cache = llama.init_cache(
                self.draft_cfg, config.max_slots, config.max_seq_len, dtype=self.dtype)
            if self.mesh is not None:
                # The draft is tiny relative to the target (that's the
                # point of drafting), so under a mesh it runs REPLICATED:
                # every device computes the same draft forward with zero
                # collectives, and the verify forward keeps the target's
                # tp sharding — one mixed GSPMD program per round.
                from jax.sharding import PartitionSpec as P

                rep = named(self.mesh, P())
                self.draft_params = jax.device_put(self.draft_params, rep)
                self.draft_cache = jax.device_put(self.draft_cache, rep)

        # Optional vision tower for the ENABLE_VISION multimodal path.
        self.vision_cfg = None
        self.vision_params = None
        if config.vision_model:
            from inference_gateway_tpu.models import vision

            self.vision_cfg = vision.PRESETS[config.vision_model]
            self.vision_params = vision.init_params(
                jax.random.PRNGKey(config.seed + 7), self.vision_cfg, dtype=self.dtype
            )

        self._rng = jax.random.PRNGKey(config.seed + 1)
        self._step_counter = 0
        self._lock = threading.Lock()
        # Device-resident chained decode state (decode_chunk_submit),
        # one shape each: _dev_carry is the last chunk's (pending token,
        # position, grammar mask state, done flag, remaining max_tokens
        # budget, chunk rng key — chained submits derive randomness on
        # device instead of uploading a fresh key), _dev_sampling the
        # uploaded (temps, top_ps, seeds, use_seed, stop table). Any
        # prefill invalidates the carry — newly admitted slots' tokens
        # exist only on the host.
        self._dev_carry = None
        self._dev_sampling = None
        # Host mirror of the chained steady state (ISSUE 14): which
        # slots the chain serves, their predicted write positions, and
        # how many cache tokens each has pages reserved for. Chained
        # submits consult ONLY these host arrays (vectorized ops, no
        # np.* construction — graftlint-enforced); when the reservation
        # horizon is exhausted, _reserve_chain_horizon tops it up in one
        # batched allocator pass and refreshes the device-resident page
        # table — the only time a chained steady state touches h2d.
        S = config.max_slots
        self._chain_active = np.zeros((S,), bool)
        self._pred_pos = np.zeros((S,), np.int64)
        self._reserved = np.zeros((S,), np.int64)
        self._dev_page_table = None
        self._dev_reserved = None
        eos = getattr(self.tokenizer, "eos_token_id", None)
        self._eos_id = eos if isinstance(eos, int) else None
        self._eos_stop_row = build_stop_row(self._eos_id)
        # Structured outputs (ISSUE 13): grammar mask tables + logit-bias
        # rows. The per-slot bias table is built here and is an operand
        # of every step program from its first trace (ISSUE 49: a
        # ``logit_bias`` request lowers nothing); the automaton's tables
        # materialize on the first GRAMMAR admission (StructuredRuntime.live
        # flips sticky-True and every step program is lowered ONCE more
        # with the mask gather fused in).
        self.structured = None
        lap(None)
        if config.structured and config.structured_states > 1:
            from inference_gateway_tpu.structured.runtime import StructuredRuntime

            self.structured = StructuredRuntime(
                self.tokenizer, self.model_cfg.vocab_size, config.max_slots,
                states_budget=config.structured_states,
                cache_size=config.structured_cache,
                max_schema_bytes=config.structured_max_schema_bytes)
            jax.block_until_ready(self.structured.bias_dev)
        lap("structured_s")
        # The automaton's placeholders for programs traced without a
        # grammar (ignored at trace time, but part of the jit signature).
        self._no_mask_tables = (
            jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.uint32))
        self._no_term_table = jnp.zeros((1,), bool)
        self._zero_mstates = np.zeros((config.max_slots,), np.int32)
        # Packed step descriptors (ISSUE 25): the two host-stepped
        # submits each stage ONE int32 buffer; its layout is fixed here
        # from max_slots, the mixed budget and the page-table shape, and
        # the step programs slice it apart with the same static offsets
        # (docs/observability.md lists the fields).
        T = self.mixed_budget
        P = self.page_cfg.max_pages_per_slot if self.paged else 0
        self._mixed_pack = PackedLayout([
            ("counter", (1,), "i32", 0),
            ("tokens", (1, T), "i32", 0),
            ("positions", (1, T), "i32", 0),
            ("write_idx", (1, T), "i32", self._flat_size),
            ("page_table", (S, P), "i32", 0),
            ("q_starts", (S,), "i32", 0),
            ("q_lens", (S,), "i32", 0),
            ("kv_lens", (S,), "i32", 0),
            ("temps", (S,), "f32", 0.0),
            ("top_ps", (S,), "f32", 1.0),
            ("seeds", (S,), "i32", 0),
            ("use_seed", (S,), "bool", 0),
            ("mstates", (S,), "i32", 0),
            # Rows whose one token is still on the device (ISSUE 40): it is
            # read from the step before's result block, not from ``tokens``.
            ("from_block", (S,), "bool", 0),
            # (ISSUE 56, a drafting model alone) the rows that DECODE: two queries, the
            # pending token and its draft, both read on the device with their positions,
            # write indices and length (``_mtp``); the rows whose prompt ENDS in this
            # step; and the token that follows each packed token, for the module.
            *([("mtp_decode", (S,), "bool", 0), ("mtp_ends", (S,), "bool", 0),
               ("next_tokens", (1, T), "i32", 0)] if self.mtp else []),
        ]) if self.paged else None
        self._fresh_pack = PackedLayout([
            ("counter", (1,), "i32", 0),
            ("tokens", (S,), "i32", 0),
            ("from_block", (S,), "bool", 0),
            ("positions", (S,), "i32", 0),
            ("done", (S,), "bool", 1),
            # Effectively unbounded: the host max_tokens check remains the
            # backstop for callers that don't ship budgets.
            ("budgets", (S,), "i32", 1 << 30),
            ("mstates", (S,), "i32", 0),
            ("temps", (S,), "f32", 0.0),
            ("top_ps", (S,), "f32", 1.0),
            ("seeds", (S,), "i32", 0),
            ("use_seed", (S,), "bool", 0),
            ("stop_table", (S, STOP_TABLE_WIDTH), "i32", self._eos_stop_row),
            ("page_table", (S, P), "i32", 0),
            ("reserved", (S if self.paged else 0,), "i32", 0),
        ])
        # Serving metrics surfaced via the sidecar's /metrics endpoint.
        self.metrics = {
            "prefill_tokens": 0,
            "decode_tokens": 0,
            "decode_steps": 0,
            "prefill_batches": 0,
        }
        # What a paged decode chunk (``_run_chunk_ee``) and a mixed step count
        # beside their tokens, a row of the fetch block each: the names are the
        # model module's, in the order its forwards return the counts.
        self._chunk_stats, self._mixed_stats = family.chunk_stats, family.mixed_stats
        if self.carries_state:
            # Segments that started at position 0, so from a zeroed state:
            # every admission, and every resume after a preemption.
            self.metrics["state_resets"] = 0
        # The result block of the mixed step dispatched last (ISSUE 40): both
        # host-stepped programs take it as an operand, every call, and read
        # from it the token of each row flagged ``from_block``, so they can be
        # dispatched before that step is fetched. Zeros until a step has run.
        self._last_block = jnp.zeros((2 + len(self._mixed_stats), S), jnp.float32)
        # A drafting model's rounds (ISSUE 56): what the round dispatched last left
        # on the device, a slot each, and what BOTH step programs start a decoding
        # row from, so that neither waits for a fetch: the pending token and its
        # position (they depend on what was accepted), the module's draft for the
        # position behind it with the strip it was drawn from, and the tokens that
        # round emitted. A prompt's last chunk writes a slot's entry first.
        self._mtp = None
        if self.mtp and self.paged:   # (a dense engine of it holds the tree alone: the benchmark's reference child)
            assert self.mixed_ok, f"{config.model}: its next-token module drafts in the paged step programs"
            k = effective_top_k(config.top_k, self.model_cfg.vocab_size)
            self._mtp = {"tok": jnp.zeros((S,), jnp.int32), "pos": jnp.zeros((S,), jnp.int32),
                         "draft": jnp.zeros((S,), jnp.int32), "qp": jnp.full((S, k), 1.0 / k, jnp.float32),
                         "qi": jnp.zeros((S, k), jnp.int32), "cnt": jnp.ones((S,), jnp.int32)}
            self._chain_epoch = 0   # fresh submits so far: a fetched chunk tightens the horizon of its own chain alone
            self._chunks_sent = 0   # decode chunks dispatched so far

    # ------------------------------------------------------------------
    @staticmethod
    def _load_hf(path: str):
        """Load a local HF Llama/Mixtral checkpoint (no network). A name that is
        neither a preset nor a directory is refused HERE: handed on,
        ``from_pretrained`` takes it for a hub id and asks the network for it
        (my chip runs, PR 50: the parent commit on a preset it did not know
        spent 40 s retrying a name resolution before it failed)."""
        if not os.path.isdir(path):
            raise ValueError(f"{path!r} is neither a model preset nor a local checkpoint directory")
        import torch  # CPU-only wheel is in the image
        from transformers import AutoConfig, AutoModelForCausalLM

        from inference_gateway_tpu.models import hf_loader

        hf_cfg = AutoConfig.from_pretrained(path, local_files_only=True)
        is_moe = getattr(hf_cfg, "model_type", "") == "mixtral"
        cfg = (hf_loader.mixtral_config_from_hf if is_moe else hf_loader.llama_config_from_hf)(hf_cfg)
        with torch.no_grad():
            model = AutoModelForCausalLM.from_pretrained(path, torch_dtype=torch.float32, local_files_only=True)
        convert = hf_loader.mixtral_params_from_hf if is_moe else hf_loader.llama_params_from_hf
        params = convert(model.state_dict(), cfg, dtype=jnp.bfloat16)
        del model
        return cfg, params

    # ------------------------------------------------------------------
    def bucket_for(self, length: int) -> int:
        """Smallest prefill bucket covering ``length``. In the ragged
        world this table is dispatch-only legacy (mixed steps pack exact
        lengths); over-length prompts raise the structured
        PromptTooLongError so the serving edge's ``prompt_too_long`` 400
        shape holds even when the edge check is bypassed (ISSUE 12
        satellite — this used to be a bare ValueError)."""
        for b in self.config.prefill_buckets:
            if length <= b and b <= self.config.max_seq_len:
                return b
        raise PromptTooLongError(length, self.max_prompt_len())

    def _bump_step(self) -> int:
        self._step_counter += 1
        return self._step_counter

    def _forward(self, *args, **kwargs):
        """The model's dense-cache forward (traced inside the step
        programs). A mixture's takes the mesh its experts are sharded
        over: its expert layer runs under ``shard_map`` there."""
        if self.is_moe and self.mesh is not None:
            kwargs["mesh"] = self.mesh
        return self._model.forward(*args, **kwargs)

    def _next_rng(self) -> jax.Array:
        return jax.random.fold_in(self._rng, self._bump_step())

    # -- structured outputs (ISSUE 13) ---------------------------------
    def _mask_args(self):
        """(automaton, next_table, bits_table, bias_table) for jitted step
        calls. The bias table is the runtime's own from the engine's
        construction on (None on an engine built without the runtime),
        so a ``logit_bias`` admission changes no signature. ``automaton``
        is trace-static: False, with placeholders for its tables, until
        the first grammar-constrained admission flips the runtime live
        (then sticky-True — one more lowering per step program, ever)."""
        rt = self.structured
        if rt is None:
            return (False,) + self._no_mask_tables + (None,)
        if rt.live:
            return True, rt.next_dev, rt.bits_dev, rt.bias_dev
        return (False,) + self._no_mask_tables + (rt.bias_dev,)

    def _mask_args_ee(self):
        """_mask_args plus the per-state TERMINAL table (ISSUE 14): the
        early-exit chunk fns read ``mterm[state]`` to fold "the grammar
        has nothing further to say" into the on-device done flag — the
        device mirror of GrammarSession.feed returning "end" on the next
        token."""
        automaton, mnext, mbits, mbias = self._mask_args()
        mterm = self.structured.term_dev if automaton else self._no_term_table
        return automaton, mnext, mbits, mterm, mbias

    def structured_register(self, slot: int, grammar, logit_bias) -> None:
        """Admission hook: make the request's grammar span device-resident
        (refcounted, shared by schema hash) and scatter its logit-bias
        row. No-op for unconstrained requests."""
        if self.structured is None or (grammar is None and not logit_bias):
            return
        with self._lock:
            self.structured.register_slot(slot, grammar, logit_bias)

    def _bias_logits(self, logits, mbias, slot_ids, mbits, mstates, automaton):
        """The sampling tail's additive bias, in every step program: each
        row's ``logit_bias`` row of the per-slot table (``slot_ids``, or
        the table's slots in order where None; zeros for an unbiased
        slot, which is exact) and, traced under ``automaton`` alone, the
        packed allowed set of the row's automaton state on top. An engine
        built without the structured runtime has no table and adds
        nothing."""
        if mbias is None:
            return logits
        rows = mbias[:-1] if slot_ids is None else mbias[slot_ids]
        if logits.ndim == 3:  # a verify forward's (S, K+1, V): the slot's row at every position
            rows = rows[:, None, :]
        if automaton:
            rows = packed_mask_bias(mbits[mstates], self.model_cfg.vocab_size) + rows
        return logits + rows

    def _verify_bias_logits(self, logits, mstates, draft_tokens, mnext, mbits, mbias, automaton):
        """``_bias_logits`` for a speculative verify forward's (S, K+1, V)
        logits (ISSUE 13): under a grammar position 0 is masked by the
        slot's current automaton state, position j by the state after
        consuming proposals d_1..d_j — a scan of K transition gathers, so
        ACCEPTED tokens can never break the grammar (a disallowed proposal
        has target probability exactly 0 under its masked strip and is
        rejected + resampled from the masked residual)."""
        if automaton:
            states = [mstates]
            for j in range(draft_tokens.shape[1]):
                states.append(mnext[states[-1], draft_tokens[:, j]])
            mstates = jnp.stack(states, axis=1)  # (S, K+1)
        return self._bias_logits(logits, mbias, None, mbits, mstates, automaton)

    # ------------------------------------------------------------------
    @partial(jax.jit, static_argnames=("self", "ring", "automaton"), donate_argnums=(2,))
    def _prefill_fn(self, params, cache, tokens, positions, lengths, slot_ids, temps, top_ps, seeds, use_seed, rng,
                    mstates=None, mnext=None, mbits=None, mbias=None, ring=False, automaton=False):
        if self.pp:
            logits, cache = llama.forward_pp(
                params, self.model_cfg, tokens, positions, lengths, cache,
                self.mesh, mode="prefill", last_only=True, slot_ids=slot_ids)
        else:
            ring_kw = {"ring_mesh": self.mesh} if ring else {}
            logits, cache = self._forward(
                params, self.model_cfg, tokens, positions, lengths, cache,
                mode="prefill", last_only=True, slot_ids=slot_ids, **ring_kw,
            )
        logits = self._bias_logits(logits, mbias, slot_ids, mbits, mstates, automaton)
        keys = per_row_keys(rng, seeds, use_seed, lengths)
        toks = sample_tokens(logits, rng, temps, top_ps, top_k=self.config.top_k, row_keys=keys)
        logprobs = compute_logprobs(logits, toks)
        nstates = mnext[mstates, toks] if automaton else jnp.zeros_like(toks)
        return toks, logprobs, nstates, cache

    @partial(jax.jit, static_argnames=("self", "automaton"), donate_argnums=(2,))
    def _prefill_chunk_fn_paged(self, params, cache, tokens, positions, lengths, write_idx,
                                page_table, temps, top_ps, seeds, use_seed, rng,
                                mstates=None, mnext=None, mbits=None, mbias=None,
                                slot_ids=None, automaton=False):
        """Paged chunked prefill: fresh tail tokens attend the slot's
        gathered pages (cached prefix + tail) causally — the
        prefix-cache fast path."""
        logits, cache = self._model.forward_paged(
            params, self.model_cfg, tokens, positions, lengths, cache, write_idx,
            page_table, mode="prefill_chunk", last_only=True, mesh=self.mesh,
        )
        logits = self._bias_logits(logits, mbias, slot_ids, mbits, mstates, automaton)
        keys = per_row_keys(rng, seeds, use_seed, lengths)
        toks = sample_tokens(logits, rng, temps, top_ps, top_k=self.config.top_k, row_keys=keys)
        logprobs = compute_logprobs(logits, toks)
        nstates = mnext[mstates, toks] if automaton else jnp.zeros_like(toks)
        return toks, logprobs, nstates, cache

    @partial(jax.jit, static_argnames=("self", "automaton"), donate_argnums=(2,))
    def _prefill_chunk_fn(self, params, cache, tokens, positions, lengths, slot_ids, temps, top_ps, seeds, use_seed, rng,
                          mstates=None, mnext=None, mbits=None, mbias=None, automaton=False):
        """One chunk of a long prompt: write at positions, attend the
        whole cache row causally (self._model.forward mode=prefill_chunk)."""
        if self.pp:
            logits, cache = llama.forward_pp(
                params, self.model_cfg, tokens, positions, lengths, cache,
                self.mesh, mode="prefill_chunk", last_only=True, slot_ids=slot_ids)
        else:
            logits, cache = self._forward(
                params, self.model_cfg, tokens, positions, lengths, cache,
                mode="prefill_chunk", last_only=True, slot_ids=slot_ids,
            )
        logits = self._bias_logits(logits, mbias, slot_ids, mbits, mstates, automaton)
        keys = per_row_keys(rng, seeds, use_seed, lengths)
        toks = sample_tokens(logits, rng, temps, top_ps, top_k=self.config.top_k, row_keys=keys)
        logprobs = compute_logprobs(logits, toks)
        nstates = mnext[mstates, toks] if automaton else jnp.zeros_like(toks)
        return toks, logprobs, nstates, cache

    @partial(jax.jit, static_argnames=("self", "automaton"), donate_argnums=(2,))
    def _prefill_fn_mm(self, params, cache, embeds, tokens, positions, lengths, slot_ids, temps, top_ps, seeds, use_seed, rng,
                       mstates=None, mnext=None, mbits=None, mbias=None, automaton=False):
        """Multimodal prefill: precomputed (image-spliced) embeddings
        replace the token-embedding lookup."""
        logits, cache = self._forward(
            params, self.model_cfg, tokens, positions, lengths, cache,
            mode="prefill", last_only=True, slot_ids=slot_ids, embeds=embeds,
        )
        logits = self._bias_logits(logits, mbias, slot_ids, mbits, mstates, automaton)
        keys = per_row_keys(rng, seeds, use_seed, lengths)
        toks = sample_tokens(logits, rng, temps, top_ps, top_k=self.config.top_k, row_keys=keys)
        logprobs = compute_logprobs(logits, toks)
        nstates = mnext[mstates, toks] if automaton else jnp.zeros_like(toks)
        return toks, logprobs, nstates, cache

    # -- early-exit fused chunks (ISSUE 14) -----------------------------
    def _chunk_done0(self, tokens, positions, done, budgets, stop_table,
                     mstates, mterm, automaton):
        """Initial per-slot done flags at chunk entry: the carried flag,
        plus every condition the PENDING token may already have tripped
        (async admission scatters first tokens without a host check —
        an EOS first token must freeze the row before step 0, exactly
        where Scheduler._emit will finish the stream)."""
        max_len = self.config.max_seq_len
        d = done | jnp.any(stop_table == tokens[:, None], axis=-1)
        d = d | (budgets <= 0) | (positions + 1 >= max_len)
        if automaton:
            d = d | mterm[mstates]
        return d

    def _chunk_step_ee(self, params, i, cache, tok, pos, ms, done, bud, gumbels,
                       k_eff, temps, top_ps, stop_table, mstates_args, write_args):
        """One early-exit decode step, shared by the dense, paged and
        pipeline-parallel while_loop bodies. Live rows advance one token
        (the forward, the chunk's pre-drawn gumbel, and for a
        grammar-constrained row a mask gather by automaton state before
        top-k/top-p and one more to advance the state — mask advancement
        never host-syncs mid-chunk, ISSUE 13); done rows FREEZE: carry
        unchanged, the emitted token is the frozen one (the host's stop
        detection re-fires on it and truncates), and paged KV writes
        are masked. Returns (cache, out_tok, out_lp, new carry...,
        experts read by this step or None)."""
        automaton, mnext, mbits, mterm, mbias = mstates_args
        max_len = self.config.max_seq_len
        # Clamped so the attended length never passes the cache row when
        # a request rides the chunk past max_seq_len: past it the paged
        # kernel's cdiv(len, page_size) would exceed max_pages_per_slot
        # and read page_table out of bounds (a garbage-page DMA).
        with jax.named_scope("carry"):
            pos_att = jnp.minimum(pos, max_len - 1)
        read = None  # a paged mixture's step counts the experts it read
        if write_args is None and self.pp:
            logits, cache = llama.forward_pp(
                params, self.model_cfg, tok[:, None], pos_att[:, None],
                pos_att + 1, cache, self.mesh, mode="decode", last_only=True)
        elif write_args is None:
            logits, cache = self._forward(
                params, self.model_cfg, tok[:, None], pos_att[:, None],
                pos_att + 1, cache, mode="decode")
            logits = logits[:, 0]
        else:
            page_table, reserved = write_args
            ps = self.config.page_size
            with jax.named_scope("carry"):
                row = self._rows(pos_att + 1) - 1   # where the pool holds this position (itself, but for ``Family.cached_rows``)
                page = jnp.take_along_axis(
                    page_table, (row // ps)[:, None], axis=1)[:, 0]
                # int32 throughout: a flat paged cache index always fits.
                w = page * ps + row % ps
                valid = (~done) & (pos < max_len) & (pos < reserved)
                w = jnp.where(valid, w, self._flat_size)
                counted = {"valid": ~done, "with_aux": True} if self._chunk_stats else {}
                # A done row attends nothing: the decode kernel passes over a
                # slot of length 0 and writes its row as zeros (ISSUE 37), where
                # a frozen row's stale context cost every step its pages (an
                # indexer's masked walk passes over it the same way: ISSUE 45).
                att_len = jnp.where(done, 0, row + 1)
            logits, cache, *rows = self._model.forward_paged(
                params, self.model_cfg, tok[:, None], pos_att[:, None],
                att_len, cache, w[:, None], page_table, mode="decode",
                last_only=True, mesh=self.mesh, **counted)
            if rows and self.is_moe:  # ``_chunk_stats``: a mixture's experts read, an indexer's or a window's counts
                with jax.named_scope("carry"):
                    per, more = self._model.split_aux(self.model_cfg, rows[0])
                    read = jnp.concatenate([_experts_read(per, tok.shape[0] * self.model_cfg.experts_per_token), more])
            elif rows:  # counted by the model, in ``_chunk_stats``' order (a windowed hybrid's pages)
                read = rows[0]
        with jax.named_scope("mask_bias"):
            logits = self._bias_logits(logits, mbias, None, mbits, ms, automaton)
        with jax.named_scope("sampling"):
            nxt = sample_tokens_pregumbel(logits, temps, top_ps, gumbels[i], k_eff)
            nxt = nxt.astype(jnp.int32)
            lp = compute_logprobs(logits, nxt)
        with jax.named_scope("carry"):  # the rows' flags, budgets and positions: what the next step starts from
            nms = mnext[ms, nxt] if automaton else ms
            nbud = bud - 1
            ndone = jnp.any(stop_table == nxt[:, None], axis=-1)
            ndone = ndone | (nbud <= 0) | (pos + 2 >= max_len)
            if automaton:
                ndone = ndone | mterm[nms]
            out_tok = jnp.where(done, tok, nxt)
            out_lp = jnp.where(done, 0.0, lp)
            tok = jnp.where(done, tok, nxt)
            pos = jnp.where(done, pos, pos + 1)
            ms = jnp.where(done, ms, nms)
            bud = jnp.where(done, bud, nbud)
            done = done | ndone
        return cache, out_tok, out_lp, tok, pos, ms, done, bud, read

    def _run_chunk_ee(self, params, cache, tokens, positions, done, budgets,
                      stop_table, temps, top_ps, seeds, use_seed, rng, mask_args,
                      write_args, n_steps):
        """The early-exit chunk driver: a lax.while_loop over up to
        ``n_steps`` decode steps that stops the moment every slot is
        done — the Kernel Looping move (arxiv 2410.23668): the
        synchronization boundary between decode iterations is gone, and
        the ITERATION COUNT itself is now a device-side decision. Output
        buffers are pre-filled with each row's frozen token, so steps
        the loop never ran still emit the token the host's stop
        detection expects. RNG (key derivation + gumbel draws) is
        precomputed for the whole chunk OUTSIDE the loop — one batched
        dispatch instead of n_steps small ones, which cost ~0.56 ms/step
        on v5e (round-3 device profile); the streams are bit-identical
        (see ops/sampling.chunk_gumbels)."""
        automaton = mask_args[0]
        mstates = mask_args[1]
        mask_tail = (automaton,) + mask_args[2:]
        with jax.named_scope("sampling"):  # the chunk's noise, drawn once
            keys = chunk_row_keys(rng, seeds, use_seed, positions, n_steps)
            k_eff = effective_top_k(self.config.top_k, self.model_cfg.vocab_size)
            gumbels = chunk_gumbels(keys, k_eff)
        # A paged mixture's carry also sums the experts its steps read, a
        # windowed hybrid's the pages they read and held.
        counted = bool(self._chunk_stats) and write_args is not None
        with jax.named_scope("carry"):
            done0 = self._chunk_done0(tokens, positions, done, budgets, stop_table,
                                      mstates, mask_args[4], automaton)
            S = tokens.shape[0]
            out_toks0 = jnp.broadcast_to(tokens[None, :], (n_steps, S)).astype(jnp.int32)
            out_lps0 = jnp.zeros((n_steps, S), jnp.float32)
            read0 = jnp.zeros((len(self._chunk_stats),), jnp.float32) if counted else None

        def cond(carry):
            i, _cache, _tok, _pos, _ms, done, _bud, _ot, _ol, *_read = carry
            with jax.named_scope("carry"):
                return (i < n_steps) & jnp.any(~done)

        def body(carry):
            i, cache, tok, pos, ms, done, bud, out_t, out_l, *read_sum = carry
            cache, o_tok, o_lp, tok, pos, ms, done, bud, read = self._chunk_step_ee(
                params, i, cache, tok, pos, ms, done, bud, gumbels, k_eff,
                temps, top_ps, stop_table, mask_tail, write_args)
            with jax.named_scope("carry"):
                out_t = jax.lax.dynamic_update_index_in_dim(out_t, o_tok, i, 0)
                out_l = jax.lax.dynamic_update_index_in_dim(out_l, o_lp, i, 0)
                return (i + 1, cache, tok, pos, ms, done, bud, out_t, out_l,
                        *([read_sum[0] + read] if counted else []))

        (i_ran, cache, tok_f, pos_f, ms_f, done_f, bud_f, out_toks, out_lps, *read_sum) = \
            jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), cache, tokens, positions, mstates, done0,
                 budgets, out_toks0, out_lps0, *([read0] if counted else [])))
        with jax.named_scope("carry"):  # the block the host fetches, and the key of the chunk behind this one
            if counted:
                # More rows of the block the host fetches anyway, one a name of
                # ``_chunk_stats``: ``experts_read`` and ``expert_visits`` are means
                # over the steps that ran, every other count their sum.
                stats = read_sum[0]
                if self.is_moe:
                    stats = stats.at[:2].divide(jnp.maximum(i_ran, 1).astype(jnp.float32))
                out_lps = jnp.concatenate([out_lps, jnp.broadcast_to(stats[:, None], (stats.shape[0], S))])
            # Steps the loop never ran emit each row's FINAL frozen token
            # (not the chunk-entry one), so the emitted block reads exactly
            # like a chunk whose frozen rows kept repeating their last
            # token — the host's stop detection re-fires on it either way.
            skipped = jnp.arange(n_steps)[:, None] >= i_ran
            out_toks = jnp.where(skipped, tok_f[None, :], out_toks)
            rng_next = jax.random.fold_in(rng, 1)
        return out_toks, out_lps, tok_f, pos_f, ms_f, done_f, bud_f, rng_next, cache

    @partial(jax.jit, static_argnames=("self", "n_steps", "automaton"), donate_argnums=(2,))
    def _decode_chunk_fn_ee(self, params, cache, tokens, positions, done, budgets,
                            stop_table, temps, top_ps, seeds, use_seed, rng,
                            mstates=None, mnext=None, mbits=None, mterm=None,
                            mbias=None, n_steps=8, automaton=False):
        """The decode chunk over the dense cache: up to n_steps fused
        decode steps with on-device stopping (stop table / budget /
        grammar terminal state in the carry); frozen rows rewrite their
        last real token's KV (bitwise identical values — a deterministic
        forward at an unchanged position), and the whole chunk exits
        early when every slot is done. Returns the (n, S) token and
        logprob blocks and the final carry, so the NEXT chunk chains off
        device-resident state with no host round-trip."""
        mask_args = (automaton, mstates, mnext, mbits, mterm, mbias)
        return self._run_chunk_ee(
            params, cache, tokens, positions, done, budgets, stop_table, temps,
            top_ps, seeds, use_seed, rng, mask_args, None, n_steps)

    @partial(jax.jit, static_argnames=("self", "n_steps", "automaton"), donate_argnums=(2,))
    def _decode_chunk_fn_paged_ee(self, params, cache, tokens, positions, done,
                                  budgets, stop_table, page_table, reserved,
                                  temps, top_ps, seeds, use_seed, rng,
                                  mstates=None, mnext=None, mbits=None, mterm=None,
                                  mbias=None, n_steps=8, automaton=False, mtp=None):
        """The decode chunk over the paged cache: the flat paged write
        index is computed ON DEVICE from the resident page table
        (page_table[slot, pos // page_size] · page_size + pos % page_size)
        and masked OOB for done rows and positions beyond the reserved
        horizon — the host assembles no write_idx per chunk, so a
        chained submit uploads nothing (ISSUE 14 tentpole b). A model that
        drafts for itself (ISSUE 56) hands ``mtp`` and runs ROUNDS
        (``_run_chunk_mtp``: one fetch block, the carry, the new ``mtp``)."""
        mask_args = (automaton, mstates, mnext, mbits, mterm, mbias)
        if self.mtp:
            return self._run_chunk_mtp(
                params, cache, tokens, positions, done, budgets, stop_table, temps,
                top_ps, seeds, use_seed, rng, mask_args, (page_table, reserved), n_steps, mtp)
        return self._run_chunk_ee(
            params, cache, tokens, positions, done, budgets, stop_table, temps,
            top_ps, seeds, use_seed, rng, mask_args, (page_table, reserved),
            n_steps)

    @partial(jax.jit, static_argnames=("self",))
    def _fresh_unpack_fn(self, packed, base_rng, block, mtp=None):
        """Fresh entry into the early-exit chunk, dense or paged (ISSUE
        25): the host's whole decode state rides ONE packed int32 buffer
        (``_fresh_pack``); this small program slices it apart and folds
        the chunk key from the engine's base key and the counter in the
        buffer. What it returns is exactly the device-resident state a
        chained submit dispatches from — the carry at chunk entry, the
        sampling parameters with the stop table, and (paged) the page
        table and reserved spans — so a fresh submit is one upload, this
        program, and the chained submit's own dispatch. (A fresh-entry
        variant of the chunk program would save this 0.3 ms dispatch and
        cost every start two more 7B executables to load, 2.5 s:
        PERF.md §6, PR 25.) ``block`` (ISSUE 40) is the result block of the
        mixed step dispatched last: a row flagged ``from_block`` takes its
        pending token from there, so this submit need not wait for that
        step's fetch; such a row whose token is one of its stop ids starts
        done, where the host will finish it once it has seen the token."""
        d = self._fresh_pack.unpack(packed)
        rng = jax.random.fold_in(base_rng, d["counter"][0])
        positions, budgets = d["positions"], d["budgets"]
        if self.mtp:
            # (ISSUE 56) every row's pending token and its position are what the
            # round dispatched last left on the device; a flagged row's round is
            # unfetched, and the host budgeted it one token: it emitted ``cnt``.
            tokens, positions = mtp["tok"], mtp["pos"]
            budgets = budgets - jnp.where(d["from_block"], mtp["cnt"] - 1, 0)
        else:
            tokens = jnp.where(d["from_block"], block[0].astype(jnp.int32), d["tokens"])
        stopped = jnp.any(d["stop_table"] == tokens[:, None], axis=-1)
        done = d["done"] | (d["from_block"] & stopped)
        carry = (tokens, positions, d["mstates"], done, budgets, rng)
        sampling = (d["temps"], d["top_ps"], d["seeds"], d["use_seed"], d["stop_table"])
        tables = (d["page_table"], d["reserved"]) if self.paged else None
        return carry, sampling, tables

    @partial(jax.jit, static_argnames=("self", "ring", "automaton"), donate_argnums=(2,))
    def _prefill_fn_paged(self, params, cache, tokens, positions, lengths, write_idx,
                          page_table, temps, top_ps, seeds, use_seed, rng,
                          mstates=None, mnext=None, mbits=None, mbias=None,
                          slot_ids=None, ring=False, automaton=False):
        ring_kw = {"ring_mesh": self.mesh} if ring else {}
        logits, cache = self._model.forward_paged(
            params, self.model_cfg, tokens, positions, lengths, cache, write_idx,
            page_table, mode="prefill", last_only=True, mesh=self.mesh, **ring_kw,
        )
        logits = self._bias_logits(logits, mbias, slot_ids, mbits, mstates, automaton)
        keys = per_row_keys(rng, seeds, use_seed, lengths)
        toks = sample_tokens(logits, rng, temps, top_ps, top_k=self.config.top_k, row_keys=keys)
        logprobs = compute_logprobs(logits, toks)
        nstates = mnext[mstates, toks] if automaton else jnp.zeros_like(toks)
        return toks, logprobs, nstates, cache

    def _mixed_step_body(self, params, cache, tokens, positions, write_idx, page_table,
                         q_starts, q_lens, kv_lens, temps, top_ps, seeds, use_seed, rng,
                         mstates=None, mnext=None, mbits=None, mbias=None, automaton=False):
        """One ragged MIXED step (ISSUE 12): prefill-chunk rows and
        decode rows in a single launch over the paged cache. This is the
        one compiled program that replaces the per-bucket
        _prefill_fn_paged / _prefill_chunk_fn_paged family on the mixed
        path — packed width is the fixed mixed_budget, so admission
        never recompiles and never pays bucket padding. Traced inside
        _mixed_step_fn."""
        counted = {"with_aux": True} if self._mixed_stats else {}
        logits, cache, *rows = self._model.forward_ragged(
            params, self.model_cfg, tokens, positions, cache, write_idx,
            page_table, q_starts, q_lens, kv_lens, mesh=self.mesh, **counted)
        # Mixed rows are slot-aligned: bias by each slot's logit_bias row
        # and, under a grammar, mask by its automaton state (constrained
        # prefill-tail rows sample their FIRST token here — same semantics).
        with jax.named_scope("mask_bias"):
            logits = self._bias_logits(logits, mbias, None, mbits, mstates, automaton)
        with jax.named_scope("sampling"):
            lens = kv_lens
            if self.family.cached_rows is not None:   # a seeded row's key is its context's, in tokens
                lens = positions[0, jnp.clip(q_starts + q_lens - 1, 0, positions.shape[1] - 1)] + 1
            keys = per_row_keys(rng, seeds, use_seed, lens)
            toks = sample_tokens(logits, rng, temps, top_ps, top_k=self.config.top_k, row_keys=keys)
            logprobs = compute_logprobs(logits, toks)
        if rows and not self.is_moe:
            return toks, logprobs, cache, rows[0]  # counted by the model, in ``_mixed_stats``' order
        if rows:
            # Rows of the fetch block, in ``_mixed_stats``' order: per layer the
            # most rows one expert multiplied and the mean over experts, the
            # experts read and the kernels' visits; each a mean over the layers.
            # Then what the attention seam or the model counted.
            with jax.named_scope("carry"):
                per, more = self._model.split_aux(self.model_cfg, rows[0])  # (L, E)
                stats = jnp.stack([jnp.mean(jnp.max(per, axis=1).astype(jnp.float32)), jnp.mean(per.astype(jnp.float32))])
                m = tokens.shape[-1] * self.model_cfg.experts_per_token
                return toks, logprobs, cache, jnp.concatenate([stats, _experts_read(per, m), more])
        return toks, logprobs, cache

    # -- a model that drafts with its own next-token module (ISSUE 56) ---
    def _mtp_round(self, params, cache, tokens, positions, write_idx, page_table, q_starts, q_lens, kv_lens,
                   decode, ends, next_tokens, tok_valid, draft, qp, qi, temps, top_ps, seeds, use_seed, rng,
                   ms, mask_tail, pair):
        """One ROUND of a model that drafts for itself, traced inside both step
        programs. The packed tokens (T,) hold, for a row that decodes, TWO
        queries: its pending token at position p and the module's draft at p + 1
        (``draft`` (S,), drawn from the strip ``qp`` / ``qi`` the round before
        left); for a prompt's chunk, its tokens. The model runs on all of them
        and gives logits at each row's first and last query; a decoding row's
        draft is accepted or refused by ``speculative.spec_accept`` against the
        module's strip (a greedy row: accepted where it IS the argmax) and the
        row emits [draft, a token from the second distribution] or [a token from
        the first's residual]: its second query's cache row is then dropped by
        position, the next round writes p + 1 again. A row whose prompt ends here
        (``ends``) samples its first token as every model's mixed step does. The
        module then runs over the same packed tokens, each with the token that
        FOLLOWS it (``next_tokens``, the host's for a prompt's own; the emitted
        ones where they were just decided; a refused draft's query left out), and
        leaves every live row's next draft and its strip.

        Returns (cache, emitted tokens (2, S), their logprobs (2, S), how many
        each row emitted (S,: 0, 1 or 2), which drafts were accepted (S,), the new
        draft, strip probabilities and strip ids, the automaton's new states, the
        mixture's auxiliary with the module's layer counted in)."""
        from inference_gateway_tpu.models import mixtral
        from inference_gateway_tpu.serving.speculative import spec_accept, strip_dist, strip_sample

        cfg = self.model_cfg
        automaton, mnext, mbits, mbias = mask_tail
        T, S = tokens.shape[0], q_starts.shape[0]
        k = effective_top_k(self.config.top_k, cfg.vocab_size)
        stream, cache, aux = self._model.forward_ragged(
            params, cfg, tokens[None], positions[None], cache, write_idx[None], page_table, q_starts, q_lens,
            kv_lens, mesh=None, with_aux=True, hidden=True, tok_valid=tok_valid, pair=pair)
        with jax.named_scope("carry"):
            live = q_lens > 0
            first = jnp.clip(q_starts, 0, T - 1)
            last = jnp.clip(q_starts + q_lens - 1, 0, T - 1)
        logits = mixtral.head_logits(params, cfg, stream[jnp.stack([first, last], axis=1)], params["final_norm"])
        with jax.named_scope("mask_bias"):
            # Under a grammar the first query is masked by the row's state, the
            # second by the state behind the draft.
            ms2 = jnp.stack([ms, mnext[ms, draft]], axis=1) if automaton else ms
            logits = self._bias_logits(logits, mbias, None, mbits, ms2, automaton)
        with jax.named_scope("sampling"):
            greedy = temps <= 1e-4
            # A round's draws come from (the row's key, the pending token's position):
            # a seeded stream is the same whichever program ran each of its rounds.
            base = jnp.where(use_seed[:, None], jax.vmap(jax.random.PRNGKey)(seeds),
                             jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(S)))
            keys = jax.vmap(jax.random.fold_in)(base, kv_lens - q_lens)
            draw = lambda n, f: jax.vmap(lambda key: f(jax.random.fold_in(key, n)))(keys)
            uniform = draw(0, lambda key: jax.random.uniform(key, (1,)))
            extra_gumbel = draw(1, lambda key: jax.random.gumbel(key, (k,)))
            draft_gumbel = draw(2, lambda key: jax.random.gumbel(key, (k,)))
            p_probs, p_idx = strip_dist(logits, jnp.broadcast_to(temps[:, None], (S, 2)),
                                        jnp.broadcast_to(top_ps[:, None], (S, 2)), k)
            out, count = spec_accept(p_probs, p_idx, qp[:, None], qi[:, None], draft[:, None], uniform,
                                     extra_gumbel, greedy)
            # A greedy row is the model's argmax stream token for token, ties as
            # jnp.argmax breaks them in every other program.
            top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            hit = draft == top[:, 0]
            out = jnp.where(greedy[:, None], jnp.where(hit[:, None], jnp.stack([draft, top[:, 1]], axis=1),
                                                       jnp.stack([top[:, 0], top[:, 0]], axis=1)), out)
            count = jnp.where(greedy, 1 + hit.astype(jnp.int32), count)
            # A second query past the cache row's end was never a position: refused.
            count = jnp.where(kv_lens > self.config.max_seq_len, 1, count)
            if pair != "only":
                # A prompt's row (a mixed step's; a decode chunk has none): the token
                # behind its last query, as every model's mixed step samples it.
                row_keys = per_row_keys(rng, seeds, use_seed, kv_lens)
                first_tok = sample_tokens(logits[:, 1], rng, temps, top_ps, top_k=self.config.top_k, row_keys=row_keys)
                out = jnp.where(decode[:, None], out, first_tok.astype(jnp.int32)[:, None])
            count = jnp.where(decode, count, 1) * live
            accepted = decode & live & (count == 2)
            lp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), out[:, :, None], axis=2)[:, :, 0]
            lp = jnp.where(decode[:, None], lp, lp[:, 1:])   # a prompt's row: its one token under the LAST query's logits
        with jax.named_scope("carry"):
            # What the module reads: each packed token beside the token that follows
            # it. A decoding row's are the two just emitted; a row whose prompt ends
            # here, its first token behind the last one's.
            refused = decode & live & (count == 1)
            at = lambda rows, idx: jnp.where(rows, idx, T)
            follows = next_tokens.at[at(decode & live, first)].set(out[:, 0], mode="drop")
            follows = follows.at[at(accepted, last)].set(out[:, 1], mode="drop")
            follows = follows.at[at(ends & live, last)].set(out[:, 0], mode="drop")
            m_lens = q_lens - refused
            m_kv = kv_lens - refused
            m_write = write_idx.at[at(refused, last)].set(self._flat_size, mode="drop")
            m_valid = tok_valid.at[at(refused, last)].set(False, mode="drop")
            nms = ms
            if automaton:
                after = mnext[ms, out[:, 0]]
                nms = jnp.where(live & (decode | ends), jnp.where(accepted, mnext[after, out[:, 1]], after), ms)
        block, cache, module_rows = mixtral.mtp_ragged(
            params, cfg, stream, follows, positions, cache, m_write, page_table, q_starts, m_lens, m_kv, m_valid,
            mesh=None, pair=pair)
        d_logits = mixtral.head_logits(params, cfg, block[jnp.clip(q_starts + m_lens - 1, 0, T - 1)],
                                       params["mtp"]["final_norm"])
        with jax.named_scope("mask_bias"):
            d_logits = self._bias_logits(d_logits, mbias, None, mbits, nms, automaton)
        with jax.named_scope("sampling"):
            n_qp, n_qi = strip_dist(d_logits, temps, top_ps, k)
            n_draft = strip_sample(n_qp, n_qi, draft_gumbel, greedy).astype(jnp.int32)
        with jax.named_scope("carry"):
            keep = lambda new, old: jnp.where(live.reshape((S,) + (1,) * (new.ndim - 1)), new, old)
            per, more = aux
            aux = (jnp.concatenate([per, module_rows[None]]), more)
        return (cache, out.T, lp.T, count, accepted, keep(n_draft, draft), keep(n_qp, qp), keep(n_qi, qi), nms, aux)

    def _mtp_stats(self, aux, m: int, count, accepted, decode, mixed: bool):
        """A drafting model's rows of the fetch block: the mixture's own
        (``_mixed_stats`` or ``_chunk_stats``: the experts', the latent pool's), then the rounds' three."""
        per, more = aux
        lead = ([jnp.mean(jnp.max(per, axis=1).astype(jnp.float32)), jnp.mean(per.astype(jnp.float32))] if mixed else [])
        rounds = decode & (count > 0)
        return jnp.concatenate([jnp.stack(lead) if lead else jnp.zeros((0,), jnp.float32), _experts_read(per, m), more,
                                jnp.stack([jnp.sum(rounds), jnp.sum(accepted),
                                           jnp.sum(jnp.where(rounds, count, 0))]).astype(jnp.float32)])

    def _pair_positions(self, page_table, pos):
        """A decoding row's two positions (S, 2), p and p + 1 (p held inside the cache
        row), and the flat write index of each from the row's pages; out of bounds
        past the row's end."""
        ps, max_len = self.config.page_size, self.config.max_seq_len
        two = jnp.minimum(pos, max_len - 1)[:, None] + jnp.arange(2, dtype=jnp.int32)[None, :]
        page = jnp.take_along_axis(page_table, jnp.minimum(two, max_len - 1) // ps, axis=1)
        return two, jnp.where(two < max_len, page * ps + two % ps, self._flat_size)

    def _mixed_step_mtp(self, params, cache, d, rng, mtp, mnext, mbits, mbias, automaton):
        """The mixed step of a model that drafts for itself (ISSUE 56), traced
        inside ``_mixed_step_fn``: a decoding row's two queries, their positions,
        write indices and length come from what the round before left on the device
        (``mtp``), never from the host, which has not fetched that round when it
        dispatches this one; the host says WHICH rows decode and where their two
        queries sit in the packed axis, and has reserved pages for the farthest
        they can have come."""
        S, T = d["q_starts"].shape[0], d["tokens"].shape[1]
        with jax.named_scope("carry"):
            decode, q_starts = d["mtp_decode"], d["q_starts"]
            pos = jnp.minimum(mtp["pos"], self.config.max_seq_len - 1)
            two, w = self._pair_positions(d["page_table"], pos)
            at = jnp.where(decode[:, None], q_starts[:, None] + jnp.arange(2)[None, :], T)      # undecoded: dropped
            put = lambda host, mine: host.at[0, at].set(mine, mode="drop")[0]
            tokens = put(d["tokens"], jnp.stack([mtp["tok"], mtp["draft"]], axis=1))
            positions = put(d["positions"], two)
            write_idx = put(d["write_idx"], w)
            kv_lens = jnp.where(decode, pos + 2, d["kv_lens"])
            tok_valid = jnp.arange(T) < jnp.sum(d["q_lens"])
        cache, toks, lps, count, accepted, draft, qp, qi, _nms, aux = self._mtp_round(
            params, cache, tokens, positions, write_idx, d["page_table"], q_starts, d["q_lens"], kv_lens,
            decode, d["mtp_ends"], d["next_tokens"][0], tok_valid, mtp["draft"], mtp["qp"], mtp["qi"],
            d["temps"], d["top_ps"], d["seeds"], d["use_seed"], rng, d["mstates"],
            (automaton, mnext, mbits, mbias), "mixed")
        with jax.named_scope("carry"):
            # The rows that now hold a pending token: the decoding ones and those whose
            # prompt ended. Their entry is where the next program starts them from.
            moved = (decode | d["mtp_ends"]) & (count > 0)
            last_tok = jnp.where(count == 2, toks[1], toks[0])
            new_pos = jnp.where(decode, pos + count, kv_lens)
            mtp = {"tok": jnp.where(moved, last_tok, mtp["tok"]), "pos": jnp.where(moved, new_pos, mtp["pos"]),
                   "draft": draft, "qp": qp, "qi": qi, "cnt": jnp.where(moved, count, mtp["cnt"])}
            stats = self._mtp_stats(aux, T * self.model_cfg.experts_per_token, count, accepted, decode, mixed=True)
            block = jnp.concatenate([jnp.stack([toks[0].astype(jnp.float32), lps[0], toks[1].astype(jnp.float32), lps[1],
                                                count.astype(jnp.float32)]),
                                     jnp.broadcast_to(stats[:, None], (stats.shape[0], S))])
        return block, cache, mtp

    def _run_chunk_mtp(self, params, cache, tokens, positions, done, budgets, stop_table, temps, top_ps, seeds,
                       use_seed, rng, mask_args, write_args, n_steps, mtp):
        """``_run_chunk_ee`` for a model that drafts for itself (ISSUE 56): up to
        ``n_steps`` ROUNDS, each of every live row's two queries through the model and
        the module (``_mtp_round``); a row emits one or two tokens a round and its
        position, budget and stop state move by what it emitted, on the device. The
        fetch block: the rounds' tokens (2 n, S) and logprobs (2 n, S), how many of
        each round's two a row emitted (n, S), the stats' rows, and the rows' final
        positions (1, S), by which the host tightens its page horizon."""
        automaton, mstates = mask_args[0], mask_args[1]
        mnext, mbits, mterm, mbias = mask_args[2:]
        page_table, reserved = write_args
        S = tokens.shape[0]
        max_len = self.config.max_seq_len
        with jax.named_scope("carry"):
            done0 = self._chunk_done0(tokens, positions, done, budgets, stop_table, mstates, mterm, automaton)
            zeros = jnp.zeros((n_steps, 2, S), jnp.float32)
            starts = 2 * jnp.arange(S, dtype=jnp.int32)
            nothing = jnp.zeros((2 * S,), jnp.int32)
            no_row = jnp.zeros((S,), bool)

        def cond(carry):
            with jax.named_scope("carry"):
                return (carry[0] < n_steps) & jnp.any(~carry[5])

        def body(carry):
            i, cache, tok, pos, ms, done, bud, draft, qp, qi, out_t, out_l, out_c, read_sum = carry
            with jax.named_scope("carry"):
                p = jnp.minimum(pos, max_len - 1)
                two, w = self._pair_positions(page_table, pos)
                w = jnp.where((~done)[:, None] & (two < reserved[:, None]), w, self._flat_size)
                q_lens = jnp.where(done, 0, 2)
            cache, toks, lps, count, accepted, draft, qp, qi, nms, aux = self._mtp_round(
                params, cache, jnp.stack([tok, draft], axis=1).reshape(-1), two.reshape(-1), w.reshape(-1), page_table,
                starts, q_lens, jnp.where(done, 0, p + 2), ~done, no_row, nothing, jnp.repeat(~done, 2),
                draft, qp, qi, temps, top_ps, seeds, use_seed, jax.random.fold_in(rng, i), ms,
                (automaton, mnext, mbits, mbias), "only")
            with jax.named_scope("carry"):
                # A stop id or the budget's end at the first emitted token discards the second.
                stop = jnp.any(stop_table[:, None, :] == toks.T[:, :, None], axis=-1)             # (S, 2)
                count = jnp.where(stop[:, 0], jnp.minimum(count, 1), jnp.minimum(count, bud))
                count = jnp.where(done, 0, count)
                accepted = accepted & (count == 2)
                last_tok = jnp.where(count == 2, toks[1], toks[0])
                npos, nbud = pos + count, bud - count
                ndone = stop[:, 0] | (stop[:, 1] & (count == 2)) | (nbud <= 0) | (npos + 1 >= max_len)
                if automaton:
                    ndone = ndone | mterm[nms]
                tok = jnp.where(done, tok, last_tok)
                pos = jnp.where(done, pos, npos)
                ms = jnp.where(done, ms, nms)
                bud = jnp.where(done, bud, nbud)
                stats = self._mtp_stats(aux, 2 * S * self.model_cfg.experts_per_token, count, accepted, ~done, mixed=False)
                out_t = jax.lax.dynamic_update_index_in_dim(out_t, toks.astype(jnp.float32), i, 0)
                out_l = jax.lax.dynamic_update_index_in_dim(out_l, lps, i, 0)
                out_c = jax.lax.dynamic_update_index_in_dim(out_c, count.astype(jnp.float32), i, 0)
                return (i + 1, cache, tok, pos, ms, done | ndone, bud, draft, qp, qi, out_t, out_l, out_c,
                        read_sum + stats)

        (i_ran, cache, tok_f, pos_f, ms_f, done_f, bud_f, draft, qp, qi, out_t, out_l, out_c, read_sum) = \
            jax.lax.while_loop(cond, body, (
                jnp.int32(0), cache, tokens, positions, mstates, done0, budgets, mtp["draft"], mtp["qp"], mtp["qi"],
                zeros, zeros, jnp.zeros((n_steps, S), jnp.float32),
                jnp.zeros((len(self._chunk_stats),), jnp.float32)))
        with jax.named_scope("carry"):
            stats = read_sum.at[:2].divide(jnp.maximum(i_ran, 1).astype(jnp.float32))   # (``_experts_read``'s pair: means)
            block = jnp.concatenate([out_t.reshape(2 * n_steps, S), out_l.reshape(2 * n_steps, S), out_c,
                                     jnp.broadcast_to(stats[:, None], (stats.shape[0], S)),
                                     pos_f.astype(jnp.float32)[None, :]])
            last = jnp.where(i_ran > 0, out_c[jnp.maximum(i_ran - 1, 0)].astype(jnp.int32), mtp["cnt"])
            mtp = {"tok": tok_f, "pos": pos_f, "draft": draft, "qp": qp, "qi": qi, "cnt": last}
            rng_next = jax.random.fold_in(rng, n_steps + 1)
        return block, tok_f, pos_f, ms_f, done_f, bud_f, rng_next, cache, mtp

    @partial(jax.jit, static_argnames=("self", "automaton"), donate_argnums=(2,))
    def _mixed_step_fn(self, params, cache, packed, base_rng, block,
                       mnext=None, mbits=None, mbias=None, automaton=False, mtp=None):
        """The mixed step as the submit dispatches it (ISSUE 25): every
        descriptor rides ONE packed int32 buffer (``_mixed_pack``), the
        step key is folded from the engine's base key and the counter in
        that buffer, and the (2, S) tokens-atop-logprobs block the fetch
        reads is built here — one upload and one dispatch a submit.
        ``block`` (ISSUE 40) is the block the step before returned: a row
        flagged ``from_block`` has one query, and its token is that block's
        for its slot (a token id is exact in float32 below 2**24), so the
        host builds and dispatches this step before it has fetched that
        one. Nothing else of a row depends on the token, except in a model
        that drafts for itself (ISSUE 56): a decoding row's position depends
        on what was accepted, so such an engine hands ``mtp``, what the round
        before left on the device, gets the new one back as a third result,
        and reads no ``block`` (``_mixed_step_mtp``)."""
        with jax.named_scope("carry"):  # the packed descriptors taken apart, the step's key, the flagged rows' tokens
            d = self._mixed_pack.unpack(packed)
            rng = jax.random.fold_in(base_rng, d["counter"][0])
        if self.mtp:
            return self._mixed_step_mtp(params, cache, d, rng, mtp, mnext, mbits, mbias, automaton)
        with jax.named_scope("carry"):
            at = jnp.where(d["from_block"], d["q_starts"], d["tokens"].shape[1])  # unflagged: dropped
            tokens = d["tokens"].at[0, at].set(block[0].astype(jnp.int32), mode="drop")
        toks, logprobs, cache, *stats = self._mixed_step_body(
            params, cache, tokens, d["positions"], d["write_idx"], d["page_table"],
            d["q_starts"], d["q_lens"], d["kv_lens"], d["temps"], d["top_ps"],
            d["seeds"], d["use_seed"], rng, mstates=d["mstates"], mnext=mnext,
            mbits=mbits, mbias=mbias, automaton=automaton)
        with jax.named_scope("carry"):  # the block the fetch reads
            block = jnp.stack([toks.astype(jnp.float32), logprobs])
            if stats:
                block = jnp.concatenate(
                    [block, jnp.broadcast_to(stats[0][:, None], (stats[0].shape[0], block.shape[1]))])
        return block, cache

    def _audit_transfer(self, direction: str, path: str, *arrays) -> int:
        """Transfer-audit seam (ISSUE 19): count one host↔device staging
        event with the number of host arrays staged and their summed
        nbytes (ISSUE 25: a packed submit stages ONE, and its nbytes is
        everything the submit uploads). The early-exit chained submit
        never calls this, so engine.transfers{direction="h2d",
        path="chain"} stays zero. Returns the number of arrays, for the
        submit's timeline record."""
        with cause_span("sched.record"):  # on the scheduler thread: the instruments' own cost
            staged = [a for a in arrays if a is not None]
            obs = self.observatory
            if obs is not None:
                obs.record_transfer(direction, path, sum(
                    int(getattr(a, "nbytes", 0)) for a in staged), arrays=len(staged))
        return len(staged)

    def mixed_step_submit(self, rows: "list[MixedRow]",
                          phases: dict | None = None) -> "MixedStepHandle":
        """Dispatch one ragged mixed step WITHOUT waiting (ISSUE 12).

        Rows are packed back to back into the fixed mixed_budget query
        axis (Σ len(token_ids) must fit it); each row's pages are
        grown/evicted for its new span, the flat write indices and
        (q_start, q_len, kv_len) descriptors are assembled host-side,
        and ONE jitted program computes every row and samples one token
        per row. The chained decode carry is invalidated — mixed steps
        advance cache positions outside the chain, so the next fused
        chunk must resubmit from host state (chain=False). A row flagged
        ``from_block`` (ISSUE 40) reads its one token from the block of
        the mixed step dispatched before this one, which need not have
        been fetched; this step's block takes its place for the next.

        ``phases`` (ISSUE 24): a dict the caller wants the submit's host
        phases stamped into (lock_wait, pages, assemble, upload_dispatch,
        in ms); it rides the returned handle."""
        total = sum(len(r.token_ids) for r in rows)
        assert rows and total <= self.mixed_budget, (total, self.mixed_budget)
        pack = self._mixed_pack
        t_lock = time.perf_counter_ns()
        with self._lock:
            stamp_since(phases, "lock_wait", t_lock)
            # Both phases write straight into views of the one staging
            # buffer (reset to its fill values: zeros, top_p 1, write
            # indices out of bounds).
            v = pack.begin()
            with phase_span(phases, "pages", "engine.mixed.pages"):
                write_idx = v["write_idx"]
                off = 0
                for r in rows:
                    n = len(r.token_ids)
                    self._ensure_with_evict(r.slot, r.start + n)
                    if not (self.mtp and r.kind == "decode"):   # (a drafting model's decoding rows: on the device)
                        write_idx[0, off:off + n] = self.allocator.flat_write_indices(
                            r.slot, r.start, n)
                    off += n
                v["page_table"][...] = self.allocator.page_table()
            with phase_span(phases, "assemble", "engine.mixed.assemble"):
                tokens, positions = v["tokens"], v["positions"]
                q_starts, q_lens, kv_lens = v["q_starts"], v["q_lens"], v["kv_lens"]
                temps, top_ps = v["temps"], v["top_ps"]
                seeds, use_seed, mstates = v["seeds"], v["use_seed"], v["mstates"]
                off = 0
                n_prefill = 0
                for r in rows:
                    n = len(r.token_ids)
                    tokens[0, off:off + n] = r.token_ids
                    positions[0, off:off + n] = r.start + np.arange(n, dtype=np.int32)
                    q_starts[r.slot] = off
                    q_lens[r.slot] = n
                    kv_lens[r.slot] = self._rows(r.start + n)
                    temps[r.slot] = r.temp
                    top_ps[r.slot] = r.top_p
                    if r.seed is not None:
                        seeds[r.slot] = int(r.seed)
                        use_seed[r.slot] = True
                    mstates[r.slot] = r.mask_state
                    if r.from_block:
                        assert n == self.round_tokens, (r.slot, n)
                        v["from_block"][r.slot] = True
                    if self.mtp:
                        # A decoding row's two queries are filled in on the device. A
                        # prompt's chunk hands the module each token's successor: its own
                        # next, and behind its last the prompt's next (``next_token``;
                        # None where the prompt ends here: the token sampled).
                        if r.kind == "decode":
                            assert n == 2, (r.slot, n)
                            v["mtp_decode"][r.slot] = True
                        else:
                            v["next_tokens"][0, off:off + n - 1] = r.token_ids[1:]
                            if r.next_token is None:
                                v["mtp_ends"][r.slot] = True
                            else:
                                v["next_tokens"][0, off + n - 1] = r.next_token
                    off += n
                    if r.kind == "prefill":
                        n_prefill += n
                    if self.carries_state and r.start == 0:
                        self.metrics["state_resets"] += 1
            with phase_span(phases, "upload_dispatch", "engine.mixed.upload_dispatch"):
                automaton, mnext, mbits, mbias = self._mask_args()
                v["counter"][0] = self._bump_step()
                staged = pack.snapshot()
                if self.mtp:
                    both, self.cache, self._mtp = self._mixed_step_fn(
                        self.params, self.cache, jnp.asarray(staged), self._rng, self._last_block,
                        mnext=mnext, mbits=mbits, mbias=mbias, automaton=automaton, mtp=self._mtp)
                else:
                    both, self.cache = self._mixed_step_fn(
                        self.params, self.cache, jnp.asarray(staged), self._rng, self._last_block,
                        mnext=mnext, mbits=mbits, mbias=mbias, automaton=automaton)
                    self._last_block = both
            # Positions moved outside the chained-carry bookkeeping.
            self._dev_carry = None
            n_decode_tokens = total - n_prefill
            if n_decode_tokens:
                # Pure-prefill chunk steps (the long-prompt ragged loop)
                # are NOT decode steps — counting them deflated decode
                # tokens-per-step on mixed engines (review finding).
                self.metrics["decode_steps"] += 1
                self.metrics["decode_tokens"] += n_decode_tokens
            self.metrics["prefill_tokens"] += n_prefill
        h2d_arrays = self._audit_transfer("h2d", "mixed", staged)
        return MixedStepHandle(both, list(rows), phases, h2d_arrays, self._state_stats(len(rows)))

    def mixed_step_fetch(self, handle: "MixedStepHandle"):
        """Block until a mixed step's sampled tokens are on host.
        Returns (tokens, logprobs) as numpy (max_slots,), row == slot; from a
        model that drafts for itself (ISSUE 56) (2, max_slots) each, a row's
        one or two tokens, with ``handle.counts`` (max_slots,) how many."""
        both, = _fetch_blocks(handle.phases, "mixed", handle.toks_lp)
        self._audit_transfer("d2h", "mixed", both)
        lead = 5 if self.mtp else 2
        if both.shape[0] > lead:
            handle.stats = {**(handle.stats or {}),
                            **{k: round(float(v), 3) for k, v in zip(self._mixed_stats, both[lead:, 0])}}
        if self.mtp:
            handle.counts = both[4].astype(np.int32)
            return both[0:4:2].astype(np.int32), both[1:4:2]
        return both[0].astype(np.int32), both[1]

    def attn_pages(self, rows: "list[MixedRow]") -> tuple[int, int]:
        """(pages walked, pages under the mask) by the ragged kernel's wide
        tiles over ``rows`` in one mixed step, all attention layers: host
        arithmetic on the rows' descriptors (``ragged_walk_pages``), the
        timeline's ``attn_pages_walked`` / ``attn_pages_masked``."""
        from inference_gateway_tpu.ops.paged_attention import ragged_walk_pages

        held = self.family.cached_rows or (lambda n: n)   # the rows a context holds: the walk is over them
        return ragged_walk_pages([(len(r.token_ids), int(held(r.start + len(r.token_ids)))) for r in rows],
                                 self.family.attention_layers, self.ragged_attention_plan,
                                 self.config.page_size)

    def write_copies(self, rows: "list[MixedRow]") -> int | None:
        """The copies one pool takes for ``rows`` in one mixed step on the run-copy
        path (``ops/pool_write.py``: a tile of ``tile_rows`` rows a copy, so a
        row's run of n rows from row r of its page is ceil((r % tile + n) / tile)
        of them): host arithmetic on the rows' descriptors, the timeline's
        ``write_copies``. None where a page is not whole tiles (its tiles then
        depend on where the pages lie)."""
        tile = self.pool_write_plan["tile_rows"]
        if self.config.page_size % tile:
            return None
        held = self.family.cached_rows or (lambda n: n)
        copies = 0
        for r in rows:
            first, last = int(held(r.start)), int(held(r.start + len(r.token_ids)))
            copies += -(-(first % tile + last - first) // tile)
        return copies

    def prompt_chunk(self, start: int, want: int) -> int:
        """How many of ``want`` prompt tokens from position ``start`` one step may take:
        all of them, but for a family whose rows fall at every ``cached_rows.window``
        tokens, where a chunk ends with the window (the next one starts it, and rolls)."""
        rows = self.family.cached_rows
        return want if rows is None else min(want, rows.window - start % rows.window)

    def _state_stats(self, rows: int) -> dict | None:
        """``state_bytes`` of a step's timeline record: what its rows hold in
        the state store, which the step reads and writes once a token."""
        if not self.carries_state:
            return None
        return {"state_bytes": int(rows) * self.model_cfg.state_bytes_per_slot}

    def family_status(self, scheduler=None) -> dict:
        """The family's /debug/status blocks, written by its model module
        (``Family.status``) over this engine's facts; with a ``scheduler``, what a
        drafting model's rounds have come to."""
        return self.family.status(StatusView(
            max_slots=self.config.max_slots, page_cfg=self.page_cfg if self.paged else None, dtype=self.dtype,
            state_resets=self.metrics.get("state_resets", 0),
            prefix_hits_declined=self.prefix_cache.declined if self.prefix_cache is not None else 0,
            scheduler=scheduler))

    def _prefill_one_ragged(self, prompt: list[int], slot: int, temp: float, top_p: float,
                            seed: int | None = None, grammar=None) -> PrefillResult:
        """Chunked ragged prefill for one long prompt on the PAGED cache
        (ISSUE 12): chunks of the mixed-step budget attend the slot's
        pages causally — paged engines previously had NO long-prompt
        path at all (max_prompt_len capped at the largest bucket)."""
        mask_state = grammar.global_state if grammar is not None else 0
        toks = logprobs = None
        start = 0
        while start < len(prompt):
            piece = prompt[start:start + self.prompt_chunk(start, self.mixed_budget)]
            h = self.mixed_step_submit([MixedRow(
                slot=slot, token_ids=list(piece), start=start, kind="prefill",
                temp=temp, top_p=top_p, seed=seed, mask_state=mask_state)])
            toks, logprobs = self.mixed_step_fetch(h)
            start += len(piece)
        with self._lock:
            self.metrics["prefill_batches"] += 1
            if self.prefix_cache is not None:
                self.prefix_cache.insert(prompt, self.allocator.pages_of(slot))
        return PrefillResult(slot, int(toks[slot]), float(logprobs[slot]))

    # ------------------------------------------------------------------
    IMAGE_PLACEHOLDER_ID = 0

    def prepare_multimodal(self, prompt_ids: list[int], images: list[np.ndarray]):
        """Encode images and build the spliced embedding row.

        images: (H, W, 3) float arrays in the vision tower's resolution.
        Returns (ids, embeds (T, hidden)) — ids carry placeholder runs at
        the front (LLaVA-style image-first layout).
        """
        assert self.vision_cfg is not None, "engine has no vision tower configured"
        from inference_gateway_tpu.models.vision import encode_images, splice_image_embeddings

        n_patches = self.vision_cfg.num_patches
        ids = [self.IMAGE_PLACEHOLDER_ID] * (n_patches * len(images)) + list(prompt_ids)
        tok_embeds = self.params["embed"][jnp.asarray(ids, jnp.int32)]
        feats = encode_images(
            self.vision_params, self.vision_cfg,
            jnp.asarray(np.stack(images), self.dtype),
        )  # (N_img, n_patches, H)
        starts = jnp.asarray([i * n_patches for i in range(len(images))], jnp.int32)
        embeds = splice_image_embeddings(tok_embeds, feats, starts)
        return ids, embeds

    def prefill(self, prompts: list[list[int]], slots: list[int], temps: list[float],
                top_ps: list[float], embeds: list | None = None,
                seeds: list | None = None, grammars: list | None = None,
                biases: list | None = None, stop_rows: np.ndarray | None = None,
                budgets: np.ndarray | None = None) -> list[PrefillResult]:
        """Synchronous prefill: submit + fetch."""
        return self.prefill_fetch(self.prefill_submit(
            prompts, slots, temps, top_ps, embeds=embeds, seeds=seeds,
            grammars=grammars, biases=biases, stop_rows=stop_rows,
            budgets=budgets))

    def prefill_fetch(self, handle: PrefillHandle,
                      phases: dict | None = None) -> list[PrefillResult]:
        """Block until a submitted prefill's first tokens are on host;
        ``phases`` takes the fetch's two stamps."""
        toks, logprobs = _fetch_blocks(phases, "prefill", handle.toks, handle.logprobs)
        self._audit_transfer("d2h", "prefill", toks, logprobs)
        return [PrefillResult(slot, int(toks[i]), float(logprobs[i]))
                for i, slot in enumerate(handle.slots)]

    @partial(jax.jit, static_argnames=("self",), donate_argnums=tuple(range(1, 11)))
    def _admit_scatter_fn_ee(self, tok, pos, ms, done, bud, temps, top_ps, seeds,
                             use_seed, stop_tab, slot_arr, new_toks, new_lens,
                             new_mstates, new_buds, new_stops, new_temps, new_tps,
                             new_seeds, new_use):
        """Fold a prefill batch's results into the chained decode state
        on-device (OOB padding rows drop) — admission stops being a
        pipeline barrier: the next chunk chains off state that already
        contains the admitted slots' first tokens, positions, and
        grammar mask states. Also re-arms their on-device stop state
        (ISSUE 14) — done flags clear, fresh max_tokens budgets and
        stop-token rows land — so the next chained chunk serves them
        with zero host involvement."""
        upd = lambda a, v: a.at[slot_arr].set(v.astype(a.dtype), mode="drop")
        return (upd(tok, new_toks), upd(pos, new_lens), upd(ms, new_mstates),
                done.at[slot_arr].set(False, mode="drop"), upd(bud, new_buds),
                stop_tab.at[slot_arr].set(new_stops, mode="drop"),
                upd(temps, new_temps), upd(top_ps, new_tps),
                upd(seeds, new_seeds), upd(use_seed, new_use))

    def prefill_submit(self, prompts: list[list[int]], slots: list[int], temps: list[float],
                       top_ps: list[float], embeds: list | None = None,
                       seeds: list | None = None, grammars: list | None = None,
                       biases: list | None = None,
                       stop_rows: np.ndarray | None = None,
                       budgets: np.ndarray | None = None) -> PrefillHandle:
        """Prefill a batch of prompts into their slots WITHOUT waiting.

        Pads to (max_prefill_batch, bucket). ``embeds`` optionally
        carries per-row (T_i, H) multimodal embedding overrides (from
        prepare_multimodal); ``grammars``/``biases`` per-row structured
        sessions and logit_bias maps (ISSUE 13) — registered here so the
        batch's first tokens are already grammar-masked. ``stop_rows``
        (B, STOP_TABLE_WIDTH) / ``budgets`` (B,) arm each admitted
        slot's ON-DEVICE stop criteria (ISSUE 14) when the chained carry
        exists; None keeps EOS-only tables and an unbounded budget (the
        host finish checks stay the backstop). Long-prompt paths (ring /
        chunked) resolve synchronously inside and return a materialized
        handle.
        """
        assert prompts and len(prompts) == len(slots)
        # Structured admission first: span acquire + bias scatter set the
        # runtime live (and each session's span base) BEFORE any mask
        # state is read or any step program traced.
        if self.structured is not None and (grammars or biases):
            for i, slot in enumerate(slots):
                self.structured_register(
                    slot, grammars[i] if grammars else None,
                    biases[i] if biases else None)
        sessions = grammars or [None] * len(prompts)
        # Prompts beyond the largest bucket take a long-context path:
        # ring attention over the sp axis when the mesh has one (ONE
        # sequence-sharded pass, O(T/sp) memory per device — dense AND
        # paged caches), else the serial chunked loop (dense cache).
        # The rest batch normally.
        biggest, ring_ok, long_path = self._long_prompt_path()
        # Multimodal rows can't ride the long path: neither the ring nor
        # the chunked prefill carries per-row embedding overrides, and
        # silently prefilling on token IDs alone would return plausible
        # wrong output. Let bucket_for raise instead — a loud admission
        # failure (finish_reason "error") beats a wrong answer.
        if embeds is not None and any(
            e is not None and len(p) > biggest for e, p in zip(embeds, prompts)
        ):
            long_path = False
        if self.spec and any(len(p) > biggest for p in prompts):
            raise ValueError(
                "speculative decoding requires prompts within the largest "
                "prefill bucket (the draft has no long-context prefill path "
                "yet); size prefill_buckets to cover max_seq_len")
        if long_path and any(len(p) > biggest for p in prompts):
            results = []
            short_idx = [i for i, p in enumerate(prompts) if len(p) <= biggest]
            for i, p in enumerate(prompts):
                if len(p) > biggest:
                    if ring_ok:
                        one = self._prefill_one_ring
                    elif self.paged:
                        one = self._prefill_one_ragged  # mixed_ok gated long_path
                    else:
                        one = self._prefill_one_chunked
                    results.append((i, one(p, slots[i], temps[i], top_ps[i],
                        seed=None if seeds is None else seeds[i],
                        grammar=sessions[i])))
            if short_idx:
                sub = self.prefill(
                    [prompts[i] for i in short_idx], [slots[i] for i in short_idx],
                    [temps[i] for i in short_idx], [top_ps[i] for i in short_idx],
                    embeds=[(embeds or [None] * len(prompts))[i] for i in short_idx] if embeds else None,
                    seeds=[(seeds or [None] * len(prompts))[i] for i in short_idx] if seeds else None,
                    grammars=[sessions[i] for i in short_idx] if grammars else None,
                    biases=[(biases or [None] * len(prompts))[i] for i in short_idx] if biases else None,
                    stop_rows=stop_rows[short_idx] if stop_rows is not None else None,
                    budgets=budgets[short_idx] if budgets is not None else None,
                )
                results.extend(zip(short_idx, sub))
            ordered = [r for _, r in sorted(results)]
            # Long paths run synchronously and bypass the standard
            # dispatch, so fold their results into any chained decode
            # state here (host values — they're already materialized).
            post_states = np.asarray(
                [0 if sessions[i] is None
                 else sessions[i].peek_global_after(r.first_token)
                 for i, r in sorted(results)], np.int32)
            with self._lock:
                self._scatter_admission(
                    np.asarray([r.slot for r in ordered], np.int32),
                    np.asarray([r.first_token for r in ordered], np.int32),
                    np.asarray([len(p) for p in prompts], np.int32),
                    np.asarray(temps, np.float32), np.asarray(top_ps, np.float32),
                    np.asarray([0 if (seeds is None or s is None) else int(s)
                                for s in (seeds or [None] * len(prompts))], np.int32),
                    np.asarray([seeds is not None and s is not None
                                for s in (seeds or [None] * len(prompts))]),
                    mstates=post_states, stop_rows=stop_rows, budgets=budgets,
                )
            return PrefillHandle(
                np.asarray([r.first_token for r in ordered], np.int32),
                np.asarray([r.logprob for r in ordered], np.float32),
                [r.slot for r in ordered], scattered=self._dev_carry is not None)
        Bp = self.config.max_prefill_batch
        assert len(prompts) <= Bp
        bucket = self.bucket_for(max(len(p) for p in prompts))

        tokens = np.zeros((Bp, bucket), np.int32)
        lengths = np.zeros((Bp,), np.int32)
        slot_arr = np.full((Bp,), self.config.max_slots, np.int32)  # OOB rows drop
        t_arr = np.zeros((Bp,), np.float32)
        p_arr = np.ones((Bp,), np.float32)
        seed_arr = np.zeros((Bp,), np.int32)
        use_seed = np.zeros((Bp,), bool)
        ms_arr = np.zeros((Bp,), np.int32)
        for i, (prompt, slot) in enumerate(zip(prompts, slots)):
            tokens[i, : len(prompt)] = prompt
            lengths[i] = len(prompt)
            slot_arr[i] = slot
            t_arr[i] = temps[i]
            p_arr[i] = top_ps[i]
            if seeds is not None and seeds[i] is not None:
                seed_arr[i] = int(seeds[i])
                use_seed[i] = True
            if sessions[i] is not None:
                ms_arr[i] = sessions[i].global_state
        positions = np.broadcast_to(np.arange(bucket, dtype=np.int32), (Bp, bucket))
        automaton, mnext, mbits, mbias = self._mask_args()
        mask_kw = dict(mstates=jnp.asarray(ms_arr), mnext=mnext, mbits=mbits,
                       mbias=mbias, automaton=automaton)

        has_mm = embeds is not None and any(e is not None for e in embeds)
        with self._lock:
            if has_mm and not self.paged:
                H = self.model_cfg.hidden_size
                full = self.params["embed"][jnp.asarray(tokens, jnp.int32)]
                for i, e in enumerate(embeds or []):
                    if e is not None:
                        e = jnp.asarray(e, full.dtype)
                        full = jax.lax.dynamic_update_slice(full, e[None], (i, 0, 0))
                toks, logprobs, nstates, self.cache = self._prefill_fn_mm(
                    self.params, self.cache, full, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(lengths), jnp.asarray(slot_arr), jnp.asarray(t_arr),
                    jnp.asarray(p_arr), jnp.asarray(seed_arr), jnp.asarray(use_seed), self._next_rng(),
                    **mask_kw,
                )
            elif self.paged:
                # Prefix-cache match: adopt shared pages, prefill tails only.
                offsets = [0] * len(prompts)
                if self.prefix_cache is not None:
                    for i, (prompt, slot) in enumerate(zip(prompts, slots)):
                        shared, matched = self.prefix_cache.match(prompt)
                        if shared:
                            self.allocator.adopt_pages(slot, shared)
                            offsets[i] = matched
                for i, (prompt, slot) in enumerate(zip(prompts, slots)):
                    self._ensure_with_evict(slot, len(prompt))
                use_chunk = any(o > 0 for o in offsets)
                if use_chunk:
                    tail_bucket = self.bucket_for(max(len(p) - o for p, o in zip(prompts, offsets)))
                    tokens = np.zeros((Bp, tail_bucket), np.int32)
                    positions = np.zeros((Bp, tail_bucket), np.int32)
                    write_idx = np.full((Bp, tail_bucket), self._flat_size, np.int64)
                    # Batch rows are NOT slot-aligned in prefill: gather
                    # each row's page-table row by its slot id.
                    full_table = self.allocator.page_table()
                    row_table = np.zeros((Bp, full_table.shape[1]), np.int32)
                    for i, (prompt, slot) in enumerate(zip(prompts, slots)):
                        tail = prompt[offsets[i]:]
                        tokens[i, : len(tail)] = tail
                        positions[i] = offsets[i] + np.arange(tail_bucket, dtype=np.int32)
                        write_idx[i, : len(tail)] = self.allocator.flat_write_indices(
                            slot, offsets[i], len(tail))
                        row_table[i] = full_table[slot]
                    toks, logprobs, nstates, self.cache = self._prefill_chunk_fn_paged(
                        self.params, self.cache, jnp.asarray(tokens), jnp.asarray(positions),
                        jnp.asarray(lengths), jnp.asarray(write_idx),
                        jnp.asarray(row_table), jnp.asarray(t_arr),
                        jnp.asarray(p_arr), jnp.asarray(seed_arr), jnp.asarray(use_seed),
                        self._next_rng(), slot_ids=jnp.asarray(slot_arr), **mask_kw,
                    )
                else:
                    write_idx = np.full((Bp, bucket), self._flat_size, np.int64)  # OOB = drop
                    for i, (prompt, slot) in enumerate(zip(prompts, slots)):
                        write_idx[i, : len(prompt)] = self.allocator.flat_write_indices(slot, 0, len(prompt))
                    toks, logprobs, nstates, self.cache = self._prefill_fn_paged(
                        self.params, self.cache, jnp.asarray(tokens), jnp.asarray(positions),
                        jnp.asarray(lengths), jnp.asarray(write_idx),
                        jnp.asarray(self.allocator.page_table()), jnp.asarray(t_arr),
                        jnp.asarray(p_arr), jnp.asarray(seed_arr), jnp.asarray(use_seed), self._next_rng(),
                        slot_ids=jnp.asarray(slot_arr), **mask_kw,
                    )
                if self.prefix_cache is not None:
                    for prompt, slot in zip(prompts, slots):
                        self.prefix_cache.insert(prompt, self.allocator.pages_of(slot))
            else:
                toks, logprobs, nstates, self.cache = self._prefill_fn(
                    self.params, self.cache, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(lengths), jnp.asarray(slot_arr), jnp.asarray(t_arr),
                    jnp.asarray(p_arr), jnp.asarray(seed_arr), jnp.asarray(use_seed), self._next_rng(),
                    **mask_kw,
                )
            self.metrics["prefill_tokens"] += int(lengths.sum())
            self.metrics["prefill_batches"] += 1
            if self.spec and not self.spec_ngram:
                # The draft model ingests the FULL prompt into its own
                # dense cache (no prefix sharing on the draft side), so
                # every spec round's catch-up stays ≤ 2 tokens.
                d_tokens = np.zeros((Bp, bucket), np.int32)
                for i, prompt in enumerate(prompts):
                    d_tokens[i, : len(prompt)] = prompt
                d_positions = np.broadcast_to(np.arange(bucket, dtype=np.int32), (Bp, bucket))
                self.draft_cache = self._draft_prefill_fn(
                    self.draft_params, self.draft_cache, jnp.asarray(d_tokens),
                    jnp.asarray(d_positions), jnp.asarray(lengths), jnp.asarray(slot_arr),
                )
            # Fold results into chained decode state on-device (futures
            # stay futures — no sync): admission is not a barrier. The
            # grammar states after the first sampled tokens ride along,
            # as do the per-slot stop rows / budgets arming the on-device
            # stop criteria (padding rows carry defaults and drop OOB).
            pad_stop = np.broadcast_to(
                self._eos_stop_row, (Bp, STOP_TABLE_WIDTH)).copy()
            pad_bud = np.full((Bp,), 1 << 30, np.int64)
            if stop_rows is not None:
                pad_stop[: len(prompts)] = stop_rows[: len(prompts)]
            if budgets is not None:
                pad_bud[: len(prompts)] = budgets[: len(prompts)]
            scattered = self._scatter_admission(
                slot_arr, toks, lengths, t_arr, p_arr, seed_arr, use_seed,
                mstates=nstates, stop_rows=pad_stop, budgets=pad_bud)
        self._audit_transfer("h2d", "prefill", tokens, positions, lengths,
                             slot_arr, t_arr, p_arr, seed_arr, use_seed, ms_arr)
        return PrefillHandle(toks[: len(slots)], logprobs[: len(slots)],
                             list(slots), scattered=scattered)

    def _scatter_admission(self, slot_arr, toks, lengths, t_arr, p_arr,
                           seed_arr, use_seed, mstates=None, stop_rows=None,
                           budgets=None) -> bool:
        """Scatter a prefill batch's (token, pos, sampling, mask-state,
        stop-row, budget, cleared done) rows into the device-resident
        chained state, if it exists. Caller holds _lock or is on the
        scheduler thread."""
        if self._dev_carry is None:
            return False
        if mstates is None:
            mstates = np.zeros((len(slot_arr),), np.int32)
        Bp = len(slot_arr)
        if stop_rows is None:
            stop_rows = np.broadcast_to(
                self._eos_stop_row, (Bp, STOP_TABLE_WIDTH))
        if budgets is None:
            budgets = np.full((Bp,), 1 << 30, np.int64)
        tok_d, pos_d, ms_d, done_d, bud_d, rng_d = self._dev_carry
        te_d, tp_d, se_d, us_d, stop_d = self._dev_sampling
        new = self._admit_scatter_fn_ee(
            tok_d, pos_d, ms_d, done_d, bud_d, te_d, tp_d, se_d, us_d, stop_d,
            jnp.asarray(slot_arr), jnp.asarray(toks), jnp.asarray(lengths),
            jnp.asarray(mstates), jnp.asarray(budgets, dtype=jnp.int32),
            jnp.asarray(stop_rows, dtype=jnp.int32),
            jnp.asarray(t_arr), jnp.asarray(p_arr), jnp.asarray(seed_arr),
            jnp.asarray(use_seed))
        self._dev_carry = (new[0], new[1], new[2], new[3], new[4], rng_d)
        self._dev_sampling = (new[6], new[7], new[8], new[9], new[5])
        # Chained steady-state host mirror: admitted slots join the chain
        # at their prompt length, with pages already reserved by the
        # prefill that produced these results (OOB padding rows drop).
        ok = slot_arr < self.config.max_slots
        s = slot_arr[ok]
        self._chain_active[s] = True
        self._pred_pos[s] = lengths[ok]
        if self.paged:
            ps = self.config.page_size
            self._reserved[s] = (lengths[ok] + ps - 1) // ps * ps
            self._dev_page_table = jnp.asarray(self.allocator.page_table())
            self._dev_reserved = jnp.asarray(self._reserved)
        return True

    def _prefill_one_chunked(self, prompt: list[int], slot: int, temp: float, top_p: float,
                             seed: int | None = None, grammar=None) -> PrefillResult:
        """Chunked prefill for one long prompt (chunk = largest bucket)."""
        chunk = max(b for b in self.config.prefill_buckets if b <= self.config.max_seq_len)
        total = len(prompt)
        mask_state = grammar.global_state if grammar is not None else 0
        automaton, mnext, mbits, mbias = self._mask_args()
        toks = logprobs = None
        with self._lock:
            for start in range(0, total, chunk):
                piece = prompt[start:start + chunk]
                tokens = np.zeros((1, chunk), np.int32)
                tokens[0, : len(piece)] = piece
                positions = (start + np.arange(chunk, dtype=np.int32))[None, :]
                lengths = np.asarray([start + len(piece)], np.int32)
                toks, logprobs, _nstates, self.cache = self._prefill_chunk_fn(
                    self.params, self.cache, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(lengths), jnp.asarray([slot], np.int32),
                    jnp.asarray([temp], np.float32), jnp.asarray([top_p], np.float32),
                    jnp.asarray([seed if seed is not None else 0], np.int32),
                    jnp.asarray([seed is not None]), self._next_rng(),
                    mstates=jnp.asarray([mask_state], np.int32), mnext=mnext,
                    mbits=mbits, mbias=mbias, automaton=automaton,
                )
                # Bumped per chunk, not once at the end: the hang
                # watchdog reads these as a progress signal, and a long
                # chunked prefill must look alive while it works.
                self.metrics["prefill_tokens"] += len(piece)
            self.metrics["prefill_batches"] += 1
        return PrefillResult(slot, int(np.asarray(toks)[0]), float(np.asarray(logprobs)[0]))

    def _prefill_one_ring(self, prompt: list[int], slot: int, temp: float, top_p: float,
                          seed: int | None = None, grammar=None) -> PrefillResult:
        """Ring-attention prefill for one long prompt: the sequence is
        padded to a multiple of the sp axis, sharded across it, and
        attended in ONE pass with KV blocks rotating the ring
        (ops/ring_attention.py). Cache write-back (dense row scatter or
        paged write_idx scatter) is the same code the bucketed path
        uses — GSPMD gathers the seq-sharded updates into the replicated
        (tp-sharded) cache. Composes with the paged pool: pages are
        reserved up front, padding rows drop via OOB write_idx."""
        sp = self.mesh.shape["sp"]
        T = len(prompt)
        # Local shards must tile evenly AND stay lane-friendly.
        unit = sp * 8
        Tp = (T + unit - 1) // unit * unit
        tokens = np.zeros((1, Tp), np.int32)
        tokens[0, :T] = prompt
        positions = np.arange(Tp, dtype=np.int32)[None, :]
        lengths = np.asarray([T], np.int32)
        t_arr = np.asarray([temp], np.float32)
        p_arr = np.asarray([top_p], np.float32)
        seed_arr = np.asarray([seed if seed is not None else 0], np.int32)
        use_seed = np.asarray([seed is not None])
        mask_state = grammar.global_state if grammar is not None else 0
        automaton, mnext, mbits, mbias = self._mask_args()
        mask_kw = dict(mstates=jnp.asarray([mask_state], np.int32), mnext=mnext,
                       mbits=mbits, mbias=mbias, automaton=automaton)
        with self._lock:
            if self.paged:
                self._ensure_with_evict(slot, T)
                write_idx = np.full((1, Tp), self._flat_size, np.int64)
                write_idx[0, :T] = self.allocator.flat_write_indices(slot, 0, T)
                toks, logprobs, _nstates, self.cache = self._prefill_fn_paged(
                    self.params, self.cache, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(lengths), jnp.asarray(write_idx),
                    jnp.asarray(self.allocator.page_table()), jnp.asarray(t_arr),
                    jnp.asarray(p_arr), jnp.asarray(seed_arr), jnp.asarray(use_seed),
                    self._next_rng(), slot_ids=jnp.asarray([slot], np.int32),
                    ring=True, **mask_kw,
                )
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(prompt, self.allocator.pages_of(slot))
            else:
                toks, logprobs, _nstates, self.cache = self._prefill_fn(
                    self.params, self.cache, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(lengths), jnp.asarray([slot], np.int32), jnp.asarray(t_arr),
                    jnp.asarray(p_arr), jnp.asarray(seed_arr), jnp.asarray(use_seed),
                    self._next_rng(), ring=True, **mask_kw,
                )
            self.metrics["prefill_tokens"] += T
            self.metrics["prefill_batches"] += 1
        return PrefillResult(slot, int(np.asarray(toks)[0]), float(np.asarray(logprobs)[0]))

    def _ensure_with_evict(self, slot: int, n_tokens: int) -> None:
        from inference_gateway_tpu.serving.kv_cache import OutOfPagesError

        try:
            try:
                self.allocator.ensure_capacity(slot, n_tokens)
            except OutOfPagesError:
                if self.prefix_cache is None:
                    raise
                need = (n_tokens + self.config.page_size - 1) // self.config.page_size
                self.prefix_cache.evict_for_pressure(min_free=need)
                self.allocator.ensure_capacity(slot, n_tokens)
        except OutOfPagesError as e:
            # Tag the failing slot so the scheduler can fail just that
            # request instead of the whole batch (advisor round-1).
            e.slot = slot
            raise

    def _reserve_horizon_pages(self, need: np.ndarray, n: int) -> None:
        """Batched KV-page pre-reservation for the chained-decode horizon
        (ISSUE 14), host side: every slot flagged in ``need`` gets pages
        covering pipeline_depth+1 future chunks (falling back to one
        chunk under page pressure, so deep horizons never manufacture
        exhaustion a chunk-by-chunk reservation wouldn't have hit) and the
        host mirror ``_reserved`` is raised to match. The caller ships
        the page table and the reserved spans to the device."""
        from inference_gateway_tpu.serving.kv_cache import OutOfPagesError

        ps = self.config.page_size
        max_len = self.config.max_seq_len
        depth = max(self.config.pipeline_depth, 1)
        per = self.round_tokens   # (two positions a round where the model drafts for itself: ISSUE 56)
        cap = np.minimum(self._pred_pos + per * n * (depth + 1), max_len)
        base = np.minimum(self._pred_pos + per * n, max_len)
        for slot in np.nonzero(need)[0]:
            s = int(slot)
            try:
                self._ensure_with_evict(s, int(cap[s]))
                got = int(cap[s])
            except OutOfPagesError:
                # Tagged with .slot by _ensure_with_evict if this
                # raises too — the scheduler's preemption path takes
                # over.
                self._ensure_with_evict(s, int(base[s]))
                got = int(base[s])
            self._reserved[s] = max(
                int(self._reserved[s]), (got + ps - 1) // ps * ps)

    def _reserve_chain_horizon(self, need: np.ndarray, n: int) -> None:
        """Top up the page horizon of a CHAINED steady state and refresh
        the device-resident page table and reserved spans ONCE. This is
        the only h2d traffic the chained steady state ever causes,
        amortized over the whole horizon; the common chained submit
        finds ``need`` empty and uploads nothing. (A fresh submit ships
        both inside its packed buffer instead.)"""
        try:
            self._reserve_horizon_pages(need, n)
        finally:
            # ALWAYS refresh the device mirrors, even when a later slot's
            # reservation raised: earlier slots in this loop already
            # extended their page lists and bumped the host mirror — if
            # the device tables stayed stale, the next chained chunk
            # would mask their writes OOB / read a page table missing
            # their new pages, silently corrupting those streams.
            self._dev_page_table = jnp.asarray(self.allocator.page_table())
            self._dev_reserved = jnp.asarray(self._reserved)

    def _chain_submit_locked(self, n: int):
        """The host-free chained submit (ISSUE 14 tentpole): everything —
        pending tokens, positions, grammar states, done flags, budgets,
        the rng key, sampling params, stop tables, page table, reserved
        spans — is already device-resident, so dispatching the next
        chunk uploads NOTHING and builds no host arrays (vectorized
        reads of the persistent host mirror only; graftlint's
        jax-hot-path chain-steady scope enforces this shape). Caller
        holds the engine lock."""
        if self._dev_carry is None:
            raise RuntimeError(
                "decode_chunk_submit(chain=True) with no device carry: "
                "a prefill or failure invalidated chained decode state; "
                "resubmit with chain=False")
        if self.paged:
            # Slots already at the cache cap are finishing ("length") —
            # excluding them keeps the reservation check from re-firing
            # every chunk once pred_pos runs past max_seq_len.
            need = (self._chain_active
                    & (self._pred_pos + self.round_tokens * n > self._reserved)
                    & (self._pred_pos < self.config.max_seq_len))
            if need.any():
                self._reserve_chain_horizon(need, n)
        return self._dispatch_chunk_ee_locked(n)

    def _dispatch_chunk_ee_locked(self, n: int):
        """Dispatch one early-exit chunk from the device-resident state
        (carry, sampling params, page table, reserved spans) and advance
        the host mirror: the whole of a chained submit once its horizon
        is known to hold, and the tail of a fresh one once the unpack
        program has put the host's state there. Chain-steady scope too:
        no upload, no host-array construction, no loop."""
        tok_in, pos_in, ms_in, done_in, bud_in, rng = self._dev_carry
        temps_d, tps_d, seeds_d, used_d, stop_d = self._dev_sampling
        automaton, mnext, mbits, mterm, mbias = self._mask_args_ee()
        chunk = dict(mstates=ms_in, mnext=mnext, mbits=mbits, mterm=mterm, mbias=mbias, n_steps=n, automaton=automaton)
        if self.mtp:
            # Rounds (ISSUE 56): the program builds the fetch block itself and hands the
            # carried draft state on; a row moves one or two positions a round.
            both, tok_f, pos_f, ms_f, done_f, bud_f, rng_f, self.cache, self._mtp = \
                self._decode_chunk_fn_paged_ee(
                    self.params, self.cache, tok_in, pos_in, done_in, bud_in,
                    stop_d, self._dev_page_table, self._dev_reserved,
                    temps_d, tps_d, seeds_d, used_d, rng, mtp=self._mtp, **chunk)
            self._chunks_sent += 1
        elif self.paged:
            toks, logprobs, tok_f, pos_f, ms_f, done_f, bud_f, rng_f, self.cache = \
                self._decode_chunk_fn_paged_ee(
                    self.params, self.cache, tok_in, pos_in, done_in, bud_in,
                    stop_d, self._dev_page_table, self._dev_reserved,
                    temps_d, tps_d, seeds_d, used_d, rng, **chunk)
        else:
            toks, logprobs, tok_f, pos_f, ms_f, done_f, bud_f, rng_f, self.cache = \
                self._decode_chunk_fn_ee(
                    self.params, self.cache, tok_in, pos_in, done_in, bud_in,
                    stop_d, temps_d, tps_d, seeds_d, used_d, rng, **chunk)
        if not self.mtp:
            both = jnp.concatenate([toks.astype(jnp.float32), logprobs], axis=0)
        self._pred_pos = self._pred_pos + self.round_tokens * n * self._chain_active
        self._dev_carry = (tok_f, pos_f, ms_f, done_f, bud_f, rng_f)
        n_active = int(self._chain_active.sum())
        self.metrics["decode_tokens"] += n_active * n
        self.metrics["decode_steps"] += n
        handle = _DecodeChunkHandle(both, n, stats=self._state_stats(n_active))
        if self.mtp:
            handle.epoch, handle.sent = self._chain_epoch, self._chunks_sent
        return handle

    def _fresh_submit_ee_locked(self, tokens, positions, active, temps, top_ps,
                                n, seeds, use_seed, mstates, stop_tables, budgets,
                                from_block=None):
        """chain=False: host state is authoritative — pack it all into
        ONE buffer (ISSUE 25), upload that (first chunk, failure
        recovery), arm the on-device stop criteria, and (re)build the
        chained steady-state host mirror the later host-free submits
        read. ``_fresh_unpack_fn`` turns the buffer into the
        device-resident state a chained submit starts from, and the
        chunk is dispatched as one. Caller holds the engine lock."""
        # Until the program below has run, nothing on the device matches
        # the host: a failure in here must not leave a chainable carry.
        self._dev_carry = None
        active = np.asarray(active, bool)
        pack = self._fresh_pack
        v = pack.begin()
        v["tokens"][:] = tokens
        if from_block is not None:  # the fill takes every token from the host
            v["from_block"][:] = from_block
        v["positions"][:] = positions
        v["done"][:] = ~active
        if budgets is not None:  # the fill is the effectively-unbounded budget
            v["budgets"][:] = budgets
        if mstates is not None:
            v["mstates"][:] = mstates
        v["temps"][:] = temps
        v["top_ps"][:] = top_ps
        if seeds is not None:  # the fills leave every row unseeded
            v["seeds"][:] = seeds
        if use_seed is not None:
            v["use_seed"][:] = use_seed
        if stop_tables is not None:  # the fill is the EOS-only row
            v["stop_table"][...] = stop_tables
        self._chain_active = active.copy()
        self._pred_pos = np.asarray(positions, np.int64).copy()
        if self.paged:
            # Fresh reservation state: recompute the horizon from the
            # allocator's truth (stale mirrors from a previous stream
            # must not understate OR overstate what is safe to write).
            self._reserved[:] = 0
            self._reserve_horizon_pages(active, n)
            v["page_table"][...] = self.allocator.page_table()
            v["reserved"][:] = self._reserved
        v["counter"][0] = self._bump_step()
        staged = pack.snapshot()
        if self.mtp:
            self._chain_epoch += 1
        self._dev_carry, self._dev_sampling, tables = self._fresh_unpack_fn(
            jnp.asarray(staged), self._rng, self._last_block, **({"mtp": self._mtp} if self.mtp else {}))
        if self.paged:
            self._dev_page_table, self._dev_reserved = tables
        handle = self._dispatch_chunk_ee_locked(n)
        handle.h2d_arrays = self._audit_transfer("h2d", "fresh", staged)
        return handle

    def decode_chunk_submit(self, tokens: np.ndarray, positions: np.ndarray,
                            active: np.ndarray, temps: np.ndarray, top_ps: np.ndarray,
                            n_steps: int | None = None, seeds: np.ndarray | None = None,
                            use_seed: np.ndarray | None = None, chain: bool = False,
                            mstates: np.ndarray | None = None,
                            stop_tables: np.ndarray | None = None,
                            budgets: np.ndarray | None = None,
                            phases: dict | None = None,
                            from_block: np.ndarray | None = None):
        """Dispatch ``n_steps`` fused decode steps WITHOUT waiting.

        JAX dispatch is asynchronous — the returned handle's arrays are
        futures, so the scheduler overlaps chunk N's readback with chunk
        N+1's execution by submitting before it fetches.

        chain=False: decode state (pending token, position, sampling
        params) is loaded from the host arrays — required for the first
        chunk and after any admission or failure recovery.
        ``stop_tables`` (S, STOP_TABLE_WIDTH) and ``budgets`` (S,) arm
        the on-device stop criteria (None = EOS-only tables and an
        effectively-unbounded budget — the host finish checks remain
        the backstop either way); ``seeds`` with ``use_seed`` seed rows
        (None = none seeded). ``from_block`` (S,) flags the rows whose
        pending token is what the mixed step dispatched last sampled for
        their slot and the host has not fetched (ISSUE 40): ``tokens`` is
        ignored there and the token read on the device.
        chain=True: the previous chunk's device-resident final carry is
        the input — no host upload, no sync. Every array argument is
        ignored (the carry, sampling params, stop state, and page-table
        horizon are all device/host-mirror resident) and the submit is
        genuinely host-free. Invalid after any prefill (which clears
        the carry): submitting chain=True then raises instead of
        silently decoding stale tokens.

        ``phases`` (ISSUE 24): a dict to stamp the submit's host phases
        into (lock_wait, upload_dispatch, in ms); it rides the handle.
        """
        n = n_steps or self.config.decode_chunk
        t_lock = time.perf_counter_ns()
        with self._lock:
            stamp_since(phases, "lock_wait", t_lock)
            with phase_span(phases, "upload_dispatch", "engine.chunk.upload_dispatch"):
                if chain:
                    # Host-free by construction (everything is device
                    # resident) — the audit records NOTHING for it,
                    # which is exactly how engine.transfers{h2d,chain}
                    # stays a scrapeable zero (ISSUE 19 invariant; the
                    # series is pre-seeded to 0 at attach).
                    handle = self._chain_submit_locked(n)
                else:
                    handle = self._fresh_submit_ee_locked(
                        tokens, positions, active, temps, top_ps, n, seeds,
                        use_seed, mstates, stop_tables, budgets, from_block)
        handle.phases = phases
        return handle

    # -- speculative decoding (serving/speculative.py) ------------------
    @partial(jax.jit, static_argnames=("self",), donate_argnums=(2,))
    def _draft_prefill_fn(self, dparams, dcache, tokens, positions, lengths, slot_ids):
        _, dcache = llama.forward(
            dparams, self.draft_cfg, tokens, positions, lengths, dcache,
            mode="prefill", last_only=True, slot_ids=slot_ids,
        )
        return dcache

    @partial(jax.jit, static_argnames=("self", "automaton"), donate_argnums=(3, 4))
    def _spec_round_fn(self, params, dparams, cache, dcache, catchup, catchup_len,
                       catchup_pos, temps, top_ps, write_idx, page_table,
                       uniforms, draft_gumbels, extra_gumbel,
                       mstates=None, mnext=None, mbits=None, mbias=None, automaton=False):
        """One speculative round for ALL slots (static shapes).

        catchup (S, 2): the emitted tokens the draft hasn't ingested
        (always 1 or 2 — see serving/speculative.py); catchup_pos (S,)
        is the position of catchup[:, 0] (== the draft's current cache
        length D); the pending token sits at P = D + catchup_len - 1.
        Returns (out_tokens (S, K+1), logprobs (S, K+1), counts (S,),
        cache, dcache).
        """
        from inference_gateway_tpu.serving.speculative import spec_accept, strip_dist, strip_sample

        dcfg = self.draft_cfg
        K = self.config.spec_k
        k = effective_top_k(self.config.top_k, self.model_cfg.vocab_size)
        S = catchup.shape[0]
        D = catchup_pos
        P = D + catchup_len - 1
        greedy = temps <= 1e-4
        slot_ids = jnp.arange(S, dtype=jnp.int32)
        max_len = self.config.max_seq_len

        # --- draft catch-up: ≤2-token block at positions D, D+1 --------
        cu_positions = D[:, None] + jnp.arange(2, dtype=jnp.int32)[None, :]
        dlogits, dcache = llama.forward(
            dparams, dcfg, catchup, cu_positions, D + catchup_len, dcache,
            mode="prefill_chunk", last_only=True, slot_ids=slot_ids,
        )
        # Draft proposals are biased and grammar-masked too (ISSUE 13):
        # the draft samples from the same allowed set the target will
        # verify against, so acceptance doesn't collapse on constrained
        # rows. The mask state advances along the proposal inside the
        # scan carry.
        dlogits = self._bias_logits(dlogits, mbias, None, mbits, mstates, automaton)

        # --- K draft proposals (scan over draft decode steps) ----------
        q0_probs, q0_idx = strip_dist(dlogits, temps, top_ps, k)
        d1 = strip_sample(q0_probs, q0_idx, draft_gumbels[:, 0], greedy)
        ds1 = mnext[mstates, d1] if automaton else jnp.zeros_like(d1)

        def dstep(carry, xs):
            dcache, tok, pos, dstate = carry
            i, gum = xs
            lg, dcache = llama.forward(
                dparams, dcfg, tok[:, None], pos[:, None], pos + 1, dcache,
                mode="decode", slot_ids=slot_ids,
            )
            lg = lg[:, 0]
            lg = self._bias_logits(lg, mbias, None, mbits, dstate, automaton)
            qp, qi = strip_dist(lg, temps, top_ps, k)
            nxt = strip_sample(qp, qi, gum, greedy)
            nstate = mnext[dstate, nxt] if automaton else dstate
            return (dcache, nxt, jnp.minimum(pos + 1, max_len - 1), nstate), (nxt, qp, qi)

        if K > 1:
            (dcache, _, _, _), (d_rest, q_rest_p, q_rest_i) = jax.lax.scan(
                dstep, (dcache, d1, jnp.minimum(P + 1, max_len - 1), ds1),
                (jnp.arange(1, K), draft_gumbels[:, 1:].swapaxes(0, 1)),
            )
            draft_tokens = jnp.concatenate([d1[:, None], d_rest.swapaxes(0, 1)], axis=1)
            q_probs = jnp.concatenate([q0_probs[:, None], q_rest_p.swapaxes(0, 1)], axis=1)
            q_idx = jnp.concatenate([q0_idx[:, None], q_rest_i.swapaxes(0, 1)], axis=1)
        else:
            draft_tokens = d1[:, None]
            q_probs, q_idx = q0_probs[:, None], q0_idx[:, None]

        # --- target verify: one forward over [pending, d_1..d_K] -------
        pending = jnp.take_along_axis(catchup, (catchup_len - 1)[:, None], axis=1)
        ver_tokens = jnp.concatenate([pending, draft_tokens], axis=1)  # (S, K+1)
        ver_positions = jnp.minimum(
            P[:, None] + jnp.arange(K + 1, dtype=jnp.int32)[None, :], max_len - 1)
        ver_lengths = jnp.minimum(P + K + 1, max_len)
        if self.paged:
            logits, cache = self._model.forward_paged(
                params, self.model_cfg, ver_tokens, ver_positions, ver_lengths,
                cache, write_idx, page_table, mode="prefill_chunk", last_only=False,
                mesh=self.mesh,
            )
        else:
            logits, cache = self._forward(
                params, self.model_cfg, ver_tokens, ver_positions, ver_lengths,
                cache, mode="prefill_chunk", last_only=False, slot_ids=slot_ids,
            )
        logits = self._verify_bias_logits(
            logits, mstates, draft_tokens, mnext, mbits, mbias, automaton)
        p_probs, p_idx = strip_dist(
            logits, jnp.broadcast_to(temps[:, None], (S, K + 1)),
            jnp.broadcast_to(top_ps[:, None], (S, K + 1)), k)

        out, counts = spec_accept(p_probs, p_idx, q_probs, q_idx, draft_tokens,
                                  uniforms, extra_gumbel, greedy)
        # Target logprob of each emitted token: dist at position j
        # predicts the token emitted as out[:, j].
        logp_full = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logprobs = jnp.take_along_axis(logp_full, out[:, :, None], axis=2)[:, :, 0]
        return out, logprobs, counts, cache, dcache

    def spec_round(self, catchup: np.ndarray, catchup_len: np.ndarray,
                   catchup_pos: np.ndarray, active: np.ndarray,
                   temps: np.ndarray, top_ps: np.ndarray,
                   seeds: np.ndarray | None = None,
                   use_seed: np.ndarray | None = None,
                   mstates: np.ndarray | None = None):
        """One speculative round for all slots: draft K, verify once,
        emit 1..K+1 tokens per live slot. Returns (out_tokens (S, K+1),
        logprobs (S, K+1), counts (S,)) as numpy."""
        assert self.spec, "engine built without spec_draft"
        S = self.config.max_slots
        K = self.config.spec_k
        k = effective_top_k(self.config.top_k, self.model_cfg.vocab_size)
        if seeds is None:
            seeds = np.zeros((S,), np.int32)
        if use_seed is None:
            use_seed = np.zeros((S,), bool)
        with self._lock:
            base_pos = catchup_pos + catchup_len - 1  # P per slot
            if self.paged:
                write_idx = np.full((S, K + 1), self._flat_size, np.int64)
                for slot in range(S):
                    if active[slot]:
                        pos = int(base_pos[slot])
                        cap = min(pos + K + 1, self.config.max_seq_len)
                        valid = max(0, cap - pos)
                        if valid:
                            self._ensure_with_evict(slot, cap)
                            write_idx[slot, :valid] = self.allocator.flat_write_indices(slot, pos, valid)
                page_table = jnp.asarray(self.allocator.page_table())
            else:
                write_idx = np.zeros((S, K + 1), np.int64)
                page_table = jnp.zeros((S, 1), jnp.int32)
            # Per-round randomness: seeded rows derive from (seed, P) so a
            # request's stream is reproducible regardless of batching.
            rng = self._next_rng()
            keys = jnp.where(
                jnp.asarray(use_seed)[:, None],
                jax.vmap(lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p))(
                    jnp.asarray(seeds), jnp.asarray(base_pos.astype(np.int32))),
                jax.vmap(lambda b: jax.random.fold_in(rng, b))(jnp.arange(S)),
            )
            uniforms = jax.vmap(lambda kk: jax.random.uniform(jax.random.fold_in(kk, 0), (K,)))(keys)
            draft_gumbels = jax.vmap(lambda kk: jax.random.gumbel(jax.random.fold_in(kk, 1), (K, k)))(keys)
            extra_gumbel = jax.vmap(lambda kk: jax.random.gumbel(jax.random.fold_in(kk, 2), (k,)))(keys)
            automaton, mnext, mbits, mbias = self._mask_args()
            out, logprobs, counts, self.cache, self.draft_cache = self._spec_round_fn(
                self.params, self.draft_params, self.cache, self.draft_cache,
                jnp.asarray(catchup.astype(np.int32)), jnp.asarray(catchup_len.astype(np.int32)),
                jnp.asarray(catchup_pos.astype(np.int32)), jnp.asarray(temps),
                jnp.asarray(top_ps), jnp.asarray(write_idx), page_table,
                uniforms, draft_gumbels, extra_gumbel,
                mstates=jnp.asarray(mstates if mstates is not None
                                    else self._zero_mstates),
                mnext=mnext, mbits=mbits, mbias=mbias, automaton=automaton,
            )
            self._dev_carry = None  # spec rounds don't chain with decode chunks
            n_active = int(active.sum())
            self.metrics["decode_steps"] += 1
            both = np.asarray(jnp.concatenate(
                [out.astype(jnp.float32), logprobs,
                 counts.astype(jnp.float32)[:, None]], axis=1))
        self._audit_transfer("h2d", "spec", catchup, catchup_len, catchup_pos,
                             temps, top_ps, write_idx, seeds, use_seed, mstates)
        self._audit_transfer("d2h", "spec", both)
        out_np = both[:, :K + 1].astype(np.int32)
        logp_np = both[:, K + 1:2 * (K + 1)]
        counts_np = both[:, -1].astype(np.int32)
        self.metrics["decode_tokens"] += int(counts_np[active].sum()) if n_active else 0
        return out_np, logp_np, counts_np

    @partial(jax.jit, static_argnames=("self", "automaton"), donate_argnums=(2,))
    def _spec_verify_ngram_fn(self, params, cache, pending, positions, draft_tokens,
                              temps, top_ps, write_idx, page_table, uniforms,
                              extra_gumbel,
                              mstates=None, mnext=None, mbits=None, mbias=None,
                              automaton=False):
        """One prompt-lookup round: verify K host-proposed tokens in ONE
        target forward. The draft "distribution" is a point mass on each
        proposal, expressed as a one-hot strip so spec_accept's ratio
        test reduces to: accept d_i with prob p(d_i) (greedy rows:
        accept iff d_i is the target argmax) — the standard
        prompt-lookup acceptance rule, via the same strip algebra the
        model-draft path uses (serving/speculative.py)."""
        from inference_gateway_tpu.serving.speculative import spec_accept, strip_dist

        K = self.config.spec_k
        k = effective_top_k(self.config.top_k, self.model_cfg.vocab_size)
        S = pending.shape[0]
        greedy = temps <= 1e-4
        max_len = self.config.max_seq_len
        slot_ids = jnp.arange(S, dtype=jnp.int32)

        ver_tokens = jnp.concatenate([pending[:, None], draft_tokens], axis=1)  # (S, K+1)
        ver_positions = jnp.minimum(
            positions[:, None] + jnp.arange(K + 1, dtype=jnp.int32)[None, :], max_len - 1)
        ver_lengths = jnp.minimum(positions + K + 1, max_len)
        if self.paged:
            logits, cache = self._model.forward_paged(
                params, self.model_cfg, ver_tokens, ver_positions, ver_lengths,
                cache, write_idx, page_table, mode="prefill_chunk", last_only=False,
                mesh=self.mesh,
            )
        else:
            logits, cache = self._forward(
                params, self.model_cfg, ver_tokens, ver_positions, ver_lengths,
                cache, mode="prefill_chunk", last_only=False, slot_ids=slot_ids,
            )
        # Grammar masks per verify position (ISSUE 13): the scheduler
        # repairs host-side proposals against the automaton, and this
        # mask guarantees the ACCEPTED prefix is grammar-valid even
        # when a repair was impossible.
        logits = self._verify_bias_logits(
            logits, mstates, draft_tokens, mnext, mbits, mbias, automaton)
        p_probs, p_idx = strip_dist(
            logits, jnp.broadcast_to(temps[:, None], (S, K + 1)),
            jnp.broadcast_to(top_ps[:, None], (S, K + 1)), k)

        # One-hot draft strips: index 0 carries the proposal with mass 1;
        # the rest are -1 (never a vocab id) with mass 0.
        q_idx = jnp.concatenate(
            [draft_tokens[:, :, None],
             jnp.full((S, K, k - 1), -1, draft_tokens.dtype)], axis=-1)
        q_probs = jnp.concatenate(
            [jnp.ones((S, K, 1), jnp.float32), jnp.zeros((S, K, k - 1), jnp.float32)], axis=-1)

        out, counts = spec_accept(p_probs, p_idx, q_probs, q_idx, draft_tokens,
                                  uniforms, extra_gumbel, greedy)
        logp_full = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logprobs = jnp.take_along_axis(logp_full, out[:, :, None], axis=2)[:, :, 0]
        return out, logprobs, counts, cache

    def spec_round_ngram(self, pending: np.ndarray, positions: np.ndarray,
                         draft_tokens: np.ndarray, active: np.ndarray,
                         temps: np.ndarray, top_ps: np.ndarray,
                         seeds: np.ndarray | None = None,
                         use_seed: np.ndarray | None = None,
                         mstates: np.ndarray | None = None):
        """One prompt-lookup speculative round for all slots.

        pending (S,): each slot's pending token at position positions[s];
        draft_tokens (S, K): host-proposed continuations (scheduler
        ngram_propose). Returns (out_tokens (S, K+1), logprobs, counts)
        as numpy. Emitted acceptance stats accumulate in metrics
        (spec_rounds / spec_accepted / spec_emitted)."""
        assert self.spec_ngram, "engine built without spec_draft='ngram'"
        S = self.config.max_slots
        K = self.config.spec_k
        k = effective_top_k(self.config.top_k, self.model_cfg.vocab_size)
        if seeds is None:
            seeds = np.zeros((S,), np.int32)
        if use_seed is None:
            use_seed = np.zeros((S,), bool)
        with self._lock:
            if self.paged:
                write_idx = np.full((S, K + 1), self._flat_size, np.int64)
                for slot in range(S):
                    if active[slot]:
                        pos = int(positions[slot])
                        cap = min(pos + K + 1, self.config.max_seq_len)
                        valid = max(0, cap - pos)
                        if valid:
                            self._ensure_with_evict(slot, cap)
                            write_idx[slot, :valid] = self.allocator.flat_write_indices(slot, pos, valid)
                page_table = jnp.asarray(self.allocator.page_table())
            else:
                write_idx = np.zeros((S, K + 1), np.int64)
                page_table = jnp.zeros((S, 1), jnp.int32)
            rng = self._next_rng()
            keys = jnp.where(
                jnp.asarray(use_seed)[:, None],
                jax.vmap(lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p))(
                    jnp.asarray(seeds), jnp.asarray(positions.astype(np.int32))),
                jax.vmap(lambda b: jax.random.fold_in(rng, b))(jnp.arange(S)),
            )
            uniforms = jax.vmap(lambda kk: jax.random.uniform(jax.random.fold_in(kk, 0), (K,)))(keys)
            extra_gumbel = jax.vmap(lambda kk: jax.random.gumbel(jax.random.fold_in(kk, 2), (k,)))(keys)
            automaton, mnext, mbits, mbias = self._mask_args()
            out, logprobs, counts, self.cache = self._spec_verify_ngram_fn(
                self.params, self.cache, jnp.asarray(pending.astype(np.int32)),
                jnp.asarray(positions.astype(np.int32)),
                jnp.asarray(draft_tokens.astype(np.int32)), jnp.asarray(temps),
                jnp.asarray(top_ps), jnp.asarray(write_idx), page_table,
                uniforms, extra_gumbel,
                mstates=jnp.asarray(mstates if mstates is not None
                                    else self._zero_mstates),
                mnext=mnext, mbits=mbits, mbias=mbias, automaton=automaton,
            )
            self._dev_carry = None  # spec rounds don't chain with decode chunks
            n_active = int(active.sum())
            both = np.asarray(jnp.concatenate(
                [out.astype(jnp.float32), logprobs,
                 counts.astype(jnp.float32)[:, None]], axis=1))
        self._audit_transfer("h2d", "spec", pending, positions, draft_tokens,
                             temps, top_ps, write_idx, seeds, use_seed, mstates)
        self._audit_transfer("d2h", "spec", both)
        out_np = both[:, :K + 1].astype(np.int32)
        logp_np = both[:, K + 1:2 * (K + 1)]
        counts_np = both[:, -1].astype(np.int32)
        if n_active:
            emitted = int(counts_np[active].sum())
            self.metrics["decode_tokens"] += emitted
            self.metrics["spec_rounds"] = self.metrics.get("spec_rounds", 0) + 1
            self.metrics["spec_emitted"] = self.metrics.get("spec_emitted", 0) + emitted
            self.metrics["spec_accepted"] = self.metrics.get("spec_accepted", 0) + int(
                (counts_np[active] - 1).sum())
        self.metrics["decode_steps"] += 1
        return out_np, logp_np, counts_np

    def decode_chunk_fetch(self, handle: "_DecodeChunkHandle"):
        """Block until a submitted chunk's results are on the host.
        Returns (tokens, logprobs) as numpy (n_steps, S)."""
        both, = _fetch_blocks(handle.phases, "chunk", handle.toks_lp)
        self._audit_transfer("d2h", "chunk", both)
        n = handle.n_steps
        if self.mtp:
            # (ISSUE 56) rounds: 2 n rows of tokens (a round's two, one under the
            # other), 2 n of logprobs, n of counts, the stats, the final positions.
            handle.counts = both[4 * n:5 * n].astype(np.int32)
            handle.stats = {**(handle.stats or {}),
                            **{k: round(float(v), 3) for k, v in zip(self._chunk_stats, both[5 * n:-1, 0])}}
            with self._lock:
                if handle.epoch == self._chain_epoch:
                    # The horizon's mirror counted two positions a round; the rows came
                    # this far, and the chunks dispatched since move them 2 n each at most.
                    ahead = 2 * n * (self._chunks_sent - handle.sent)
                    self._pred_pos = np.where(self._chain_active,
                                              np.minimum(self._pred_pos, both[-1].astype(np.int64) + ahead), self._pred_pos)
            return both[:2 * n].astype(np.int32), both[2 * n:4 * n]
        if both.shape[0] > 2 * n:
            handle.stats = {**(handle.stats or {}),
                            **{k: round(float(v), 3) for k, v in zip(self._chunk_stats, both[2 * n:, 0])}}
        return both[:n].astype(np.int32), both[n:2 * n]

    def decode_chunk(self, tokens: np.ndarray, positions: np.ndarray, active: np.ndarray,
                     temps: np.ndarray, top_ps: np.ndarray, n_steps: int | None = None,
                     seeds: np.ndarray | None = None, use_seed: np.ndarray | None = None,
                     chain: bool = False):
        """Synchronous submit+fetch — run ``n_steps`` fused decode steps
        for ALL slots and wait for the (n_steps, S) token block."""
        return self.decode_chunk_fetch(self.decode_chunk_submit(
            tokens, positions, active, temps, top_ps, n_steps=n_steps,
            seeds=seeds, use_seed=use_seed, chain=chain))

    # ------------------------------------------------------------------
    def save_checkpoint(self, path: str) -> None:
        from inference_gateway_tpu.serving.checkpoint import save_checkpoint

        save_checkpoint(path, self.params, self.model_cfg)

    @partial(jax.jit, static_argnames=("self",), donate_argnums=(1,))
    def _mark_done_fn(self, done, slot):
        """Freeze one slot in the chained early-exit carry (ISSUE 14):
        its pages are being released, so chunks submitted from here on
        must stop sampling AND stop writing KV for it (the device write
        mask keys off this flag). In-flight chunks submitted earlier are
        safe by program ordering — any stale write lands before the
        page's next occupant prefills over it."""
        return done.at[slot].set(True)

    def release_slot(self, slot: int, frozen: bool = False) -> None:
        """Return a finished slot's KV pages to the pool, drop its
        grammar-span reference, zero its logit-bias row, and freeze its
        row in any chained early-exit carry.

        ``frozen=True`` promises the device ALREADY froze the row (the
        finish was one the on-device stop state detected — the common
        case), so no carry patch is dispatched: the hot finish path
        stays pure-Python. Host-only finishes (stop strings,
        disconnects, preemption, failures) pass False and pay one tiny
        scatter so later chained chunks stop writing into freed pages."""
        with self._lock:
            if self.allocator is not None:
                self.allocator.release(slot)
            if self.structured is not None:
                with cause_span("structured.release_slot"):
                    self.structured.release_slot(slot)
            self._chain_active[slot] = False
            if not frozen and self._dev_carry is not None:
                tok, pos, ms, done, bud, rng = self._dev_carry
                self._dev_carry = (
                    tok, pos, ms,
                    self._mark_done_fn(done, jnp.int32(slot)), bud, rng)

    @property
    def mesh_axes(self) -> dict:
        """The mesh as axis -> size, for /debug/status: {"tp": 1} where
        the engine holds one device."""
        return {"tp": 1} if self.mesh is None else {k: int(v) for k, v in self.mesh.shape.items()}

    def context_window(self) -> int:
        return min(self.config.max_seq_len, self.model_cfg.max_position_embeddings)

    def _long_prompt_path(self) -> tuple[int, bool, bool]:
        """(largest prefill bucket, ring available, any long path
        available) — the ONE admission gate prefill_submit and the
        serving edge's fast-fail (max_prompt_len) both consult, so the
        400 check can never drift from actual admission behavior."""
        biggest = max(b for b in self.config.prefill_buckets
                      if b <= self.config.max_seq_len)
        if self.family.mixed_alone and self.mixed_ok:
            biggest = 0  # every prompt is a "long" one: the ragged step admits them all
        ring_ok = (
            self.mesh is not None
            and self.mesh.shape.get("sp", 1) > 1
            and not self.is_moe
            and self.model_cfg.sliding_window is None
        )
        # Mixed-step paged engines chunk long prompts through the ragged
        # program (ISSUE 12) — paged mode is no longer bucket-bounded.
        long_path = ring_ok or (not self.paged and not self.is_moe) or self.mixed_ok
        return biggest, ring_ok, long_path

    def max_prompt_len(self, multimodal: bool = False) -> int:
        """Largest admittable prompt in tokens (ISSUE 7 fast-fail).

        Engines with a long-prompt prefill path (ring attention over an
        sp axis, or the serial chunked loop on a dense non-MoE cache)
        admit up to the context window; paged/MoE/speculative/multimodal
        configurations without one are bounded by the largest prefill
        bucket — the serving edge rejects above it with a structured 400
        *before* a slot is allocated, instead of letting admission fail
        the request into a finish_reason "error" stream."""
        window = self.context_window() - 1
        biggest, _ring_ok, long_path = self._long_prompt_path()
        if multimodal:
            long_path = False  # long paths carry no embedding overrides
        if self.spec or not long_path:
            return min(biggest, window)
        return window

    def kv_utilization(self) -> float:
        """KV-cache pressure in [0, 1]: pages in use / total (paged
        attention), 0.0 when the cache is a flat full reservation —
        there is no page pool to exhaust. GIL-atomic int reads, safe to
        sample from the serving thread without the engine lock (ISSUE 3
        engine gauges)."""
        if self.allocator is None:
            return 0.0
        total = self.allocator.num_pages
        if total <= 0:
            return 0.0
        return 1.0 - self.allocator.free_page_count() / total

    def warmup(self) -> float:
        """Compile every program the scheduler's main path dispatches at
        this engine's configured shapes, so no request meets a compile
        on the scheduler thread, where the engine watchdog reads a long
        one as a wedged device and rebuilds a second engine on a chip
        that holds one (a 32-layer step program compiled in 3-5 s on a
        v5e; the watchdog's floor is 60 s).

        Covered: admission (the one mixed program on mixed engines; every
        prefill bucket plus the over-bucket chunk program elsewhere, with
        the scatter that folds first tokens into the chained carry), the
        fused decode chunk submitted fresh AND chained (under a mesh the
        carry's sharding gives the chained call its own executable), and
        the done-mark a host-side finish dispatches. A ``logit_bias``
        request runs these same executables: the per-slot bias table is
        their operand from this first trace (ISSUE 49). Not covered: the
        prefix-cache tail programs of bucketed paged engines, ring
        prefill (its width follows the prompt), speculative rounds, and
        the grammar variant every program gains on the first
        grammar-constrained request, whose automaton tables are built
        lazily (ROADMAP D4). Synthetic prompts never enter the prefix
        cache.

        Brackets the compile ledger (ISSUE 19) when an observatory is
        attached: compiles inside warmup are expected; any compile after
        the bracket closes is a steady-state recompile. Bracketing here
        (not in serve()) means a supervised engine restart's warmup is
        classified correctly too."""
        obs = self.observatory
        if obs is not None:
            obs.warmup_begin()
        t0 = time.perf_counter()
        S = self.config.max_slots
        idle = (np.zeros((S,), np.int32), np.zeros((S,), np.int32), np.zeros((S,), bool),
                np.zeros((S,), np.float32), np.ones((S,), np.float32))
        prefix_cache, self.prefix_cache = self.prefix_cache, None
        try:
            if self.mixed_ok:
                # Twice: the second call's block operand is the first's
                # result, as every later step's is (under a mesh its
                # sharding may give that call an executable of its own).
                for _ in range(2):
                    self.mixed_step_fetch(self.mixed_step_submit([MixedRow(
                        slot=0, token_ids=[1, 2, 3], start=0, kind="prefill")]))
                    self.release_slot(0)
            self.decode_chunk_fetch(self.decode_chunk_submit(*idle))
            if not self.mixed_ok:
                # One prompt per bucket, each a token longer than the
                # bucket below; past the last bucket, the chunked path.
                biggest, ring_ok, long_path = self._long_prompt_path()
                lengths = [1] + [b + 1 for b in self.config.prefill_buckets if b < biggest]
                if long_path and not ring_ok:
                    lengths.append(biggest + 1)
                for n in lengths:
                    if n <= self.max_prompt_len():
                        self.prefill([[1] * n], [0], [0.0], [1.0])
                        self.release_slot(0)
            for _ in range(2):
                self.decode_chunk_fetch(self.decode_chunk_submit(*idle, chain=True))
            self.release_slot(0)
        finally:
            self.prefix_cache = prefix_cache
            with self._lock:
                self._dev_carry = None
                self._chain_active[:] = False
        if obs is not None:
            obs.mark_warmup_complete()
        return time.perf_counter() - t0
