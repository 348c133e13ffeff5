"""What serving a model family takes, said once by the family's model module.

``serving/engine.py`` (``Engine.__init__``, ``choose_mesh``, ``build_params``)
and the sidecar's ``/debug/status`` read one record, ``family_of(model_cfg)``;
``models/llama.py``, ``models/mixtral.py``, ``models/phi4flash.py`` and
``models/jamba.py`` each define ``family(cfg)``, which fills it from the fields of its own config. A new
family changes its model module and nothing under ``serving/`` (ISSUE 58).
"""

from __future__ import annotations

import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Callable, Mapping


@dataclass(frozen=True)
class StatusView:
    """The engine's facts a family's ``/debug/status`` blocks read."""
    max_slots: int
    page_cfg: Any   # serving/kv_cache.PagedCacheConfig; None over the dense cache
    dtype: Any
    state_resets: int = 0           # segments that started from a zeroed state
    prefix_hits_declined: int = 0   # prefix hits a state store could not take
    scheduler: Any = None           # its spec_slot_rounds / spec_accepted / spec_emitted, where one runs


@dataclass(frozen=True)
class Family:
    # init_params / init_cache / init_paged_cache / param_specs / forward / forward_paged / forward_ragged
    module: ModuleType
    # A mixture: a mesh of its own has an ``ep`` axis, and the step programs'
    # auxiliary leads with the rows each expert multiplied (``module.split_aux``).
    experts: bool = False
    pool: str = "kv"   # what the page pool holds: "kv" | "kv+index" (an indexer's keys beside them) | "latent" (one array)
    # Its step programs write a step's keys and values through ``models/llama._write_kv``
    # (ISSUE 62: ``ops/pool_write.write_rows``, whose verdict the engine reports as
    # ``pool_write_plan``); False where the module writes its pool itself.
    row_writes: bool = True
    # A state a slot beside the pool: it cannot be handed over as pages can
    # (prefix hits are declined, and counted) and starts from zero at position 0
    # (``state_resets``); ``cfg.state_bytes_per_slot`` says how large.
    state_store: bool = False
    mixed_alone: bool = False   # admitted through the mixed step alone: a paged engine of it is a mixed engine whatever the flag says
    round_tokens: int = 1       # tokens a row may emit a round: 2 where the model drafts for itself
    # What a configuration may ask ("spec_draft" | "vision_model" | "int4" | "mesh", and
    # "image_part" of a request) that the family's path does not carry -> the reason, as
    # served behind "<model>: "; "mesh"'s names the ``{shape}`` asked for.
    refuses: Mapping[str, str] = field(default_factory=dict)
    # Names of the rows a decode chunk and a mixed step count beside their tokens,
    # in the order the step programs put them into the fetch block.
    chunk_stats: tuple[str, ...] = ()
    mixed_stats: tuple[str, ...] = ()
    # The layers that read the page pool through the paged kernels, as (window or
    # None, layers): what ``ragged_walk_pages`` counts a mixed step's pages over.
    attention_layers: tuple = ()
    # A pool whose rows are not one a token (ISSUE 61): ``cached_rows(n)`` is the rows
    # a context of n tokens holds (integer arithmetic: ints, numpy and traced arrays
    # alike; the row of token t is ``cached_rows(t + 1) - 1``). It says of itself where
    # it falls, at every multiple of ``cached_rows.window`` tokens, which no prompt chunk
    # may straddle, and the one ``cached_rows.page_size`` its pool is laid out for
    # (ops/eva_attention.FoldedRows). Pages of such rows are not pages of tokens: prefix
    # hits are declined, and counted. None: a row a token at index ``position``, as
    # every other family caches.
    cached_rows: Callable[[Any], Any] | None = None
    status: Callable[[StatusView], dict] = lambda view: {}   # the family's /debug/status blocks


INT8_ALONE = "int8 is the one weight-only quantisation built for it, not int4"   # a ``refuses["int4"]``


def attention_layers(layers: int, window: int | None, windowed: int) -> tuple:
    """``Family.attention_layers`` of ``layers`` layers, ``windowed`` of them under ``window``."""
    return tuple((w, n) for w, n in ((window, windowed), (None, layers - windowed)) if n)


def family_of(model_cfg) -> Family:
    """The record of a config, by the module that defines the config's class."""
    return sys.modules[type(model_cfg).__module__].family(model_cfg)


# Presets of models/phi4flash.py and models/jamba.py, by name: a module is
# imported when one of its presets is asked for, and not on the way to any other model.
PHI4FLASH_PRESETS = ("phi-4-mini-flash", "phi4flash-test-tiny")
JAMBA_PRESETS = ("jamba2-3b", "jamba-test-tiny")


def preset_config(name: str):
    """The config of a preset name, or None."""
    from inference_gateway_tpu.models import llama, mixtral

    if name in PHI4FLASH_PRESETS:
        from inference_gateway_tpu.models import phi4flash

        return phi4flash.PRESETS[name]
    if name in JAMBA_PRESETS:
        from inference_gateway_tpu.models import jamba

        return jamba.PRESETS[name]
    return llama.PRESETS.get(name) or mixtral.PRESETS.get(name)
