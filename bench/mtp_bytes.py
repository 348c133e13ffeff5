"""Bytes and operations ONE decode round of a ``pangu_ultra_moe`` configuration
must move and make, LOWER bounds, and what its drafting came to: what the
``mtp_*`` and ``pangu_*`` readers share. Sizes come from the configuration's
file alone, never from the program; what a round's rows chose, walked, drafted
and accepted comes from the program's own counters on the ``decode`` and
``mixed`` records of ``/debug/timeline``.

A ``pangu_ultra_moe`` configuration (``model_type`` in its file) has
``first_k_dense_replace`` leading layers with a dense SwiGLU of
``intermediate_size``, then expert layers with ``n_routed_experts`` HELD SwiGLU
experts of ``moe_intermediate_size`` under a router of ``routed_experts`` and
``n_shared_experts`` always-on ones; every layer a latent attention of five
matrices (W_DQ hidden x q_lora_rank, W_UQ q_lora_rank x heads x (nope + rope),
W_DKV hidden x (kv_lora_rank + rope), W_UKV kv_lora_rank x heads x (nope + v),
W_O heads x v x hidden); and ``num_nextn_predict_layers`` next-token modules,
each one more expert layer behind W_eh (2 hidden x hidden). A decode ROUND runs
every live row's TWO queries (its pending token and the module's draft) through
the layers and the head, then the accepted ones through the module and the head.

A round reads once, at the served weight type:

- every layer's and the module's five attention matrices; the dense layers' MLP;
- in every expert layer and the module the three matrices of each DISTINCT
  expert its tokens chose among the HELD (the record's ``experts_read``: a mean
  over the expert layers, the module's among them, and over the chunk's rounds)
  and of the shared expert, and the router at the activation type;
- W_eh and the head (hidden x the HELD vocabulary); embedding rows are left out;
- each row's latent context ONCE for its two queries, in every layer and the
  module's: ``latent_pages_read`` (the record's sum, over the rounds and all
  caching layers, of the pages its rows' tiles walked: a row of two queries is
  one tile) x page size x (kv_lora_rank + rope) values at the cache type.

Scales, norms, activations and the rows a round writes are left out: a share
over 100% is a fault of this count, never of the program. Nothing where a record
lacks a counter.

A round's operations (2 a multiply-add): every query token's projections and
dense or shared MLP, the held experts' rows at their EXPECTED number (a token's
``num_experts_per_tok`` choices fall on the held ``n_routed_experts`` of
``routed_experts`` alike: the decode record carries how many experts were read,
not how many rows each multiplied), the latent read as two queries a row over
the row's context (a (query, cached token, head): 2 x (kv_lora_rank + rope) for
the score and 2 x kv_lora_rank for the weighted latent), W_eh a module token,
and the head for each query. The model runs 2 tokens a live row a round, the
module 1 + the round's accepted share.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
COUNTERS = ("experts_read", "latent_pages_read", "mtp_drafted", "mtp_accepted")


def is_pangu(config: dict) -> bool:
    return config.get("model_type") == "pangu_ultra_moe"


def sizes(config: dict) -> dict:
    dep = config["deployment_sizes"]
    lead = int(config["first_k_dense_replace"])
    return {"H": int(config["hidden_size"]), "L": int(config["num_hidden_layers"]), "lead": lead,
            "modules": int(config["num_nextn_predict_layers"]),
            "Hq": int(config["num_attention_heads"]), "V": int(config["vocab_size"]),
            "I": int(config["intermediate_size"]), "Im": int(config["moe_intermediate_size"]),
            "held": int(config["n_routed_experts"]), "routed": int(config["routed_experts"]),
            "top_k": int(config["num_experts_per_tok"]), "shared": int(config["n_shared_experts"]),
            "rank": int(config["kv_lora_rank"]), "q_rank": int(config["q_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]), "rope": int(config["qk_rope_head_dim"]), "v": int(config["v_head_dim"]),
            "page": int(dep["page_size"]), "chunk": int(dep["decode_chunk"]),
            "w": DTYPE_BYTES[dep["weight_dtype"]], "a": DTYPE_BYTES[dep["activation_dtype"]], "kv": DTYPE_BYTES[dep["kv_dtype"]]}


def attention_params(config: dict) -> int:
    """One layer's five attention matrices."""
    s = sizes(config)
    return (s["H"] * s["q_rank"] + s["q_rank"] * s["Hq"] * (s["nope"] + s["rope"]) + s["H"] * (s["rank"] + s["rope"])
            + s["rank"] * s["Hq"] * (s["nope"] + s["v"]) + s["Hq"] * s["v"] * s["H"])


def expert_params(config: dict) -> int:
    """One expert's (or one shared expert's) three matrices."""
    s = sizes(config)
    return 3 * s["H"] * s["Im"]


def round_bytes(config: dict, record: dict) -> float | None:
    """Bytes a decode round must read, from a ``decode`` record; None where it
    lacks a counter or ran no round."""
    if any(record.get(k) is None for k in COUNTERS) or not record.get("steps"):
        return None
    s = sizes(config)
    caching = s["L"] + s["modules"]                       # layers that read a latent context: the module's is one
    expert_layers = s["L"] - s["lead"] + s["modules"]
    weights = (caching * attention_params(config) + s["lead"] * 3 * s["H"] * s["I"]
               + expert_layers * (record["experts_read"] + s["shared"]) * expert_params(config)
               + s["modules"] * 2 * s["H"] * s["H"] + s["H"] * s["V"]) * s["w"]
    router = expert_layers * s["H"] * s["routed"] * s["a"]
    latent = record["latent_pages_read"] / record["steps"] * s["page"] * (s["rank"] + s["rope"]) * s["kv"]
    return weights + router + latent


def round_flops(config: dict, record: dict) -> float | None:
    """Operations of a decode round's query tokens, from a ``decode`` record;
    None where it lacks a counter or ran no round."""
    if any(record.get(k) is None for k in COUNTERS) or not record.get("steps") or not record["mtp_drafted"]:
        return None
    s = sizes(config)
    rows = record["mtp_drafted"] / record["steps"]                       # live rows a round
    module_tokens = rows * (1.0 + record["mtp_accepted"] / record["mtp_drafted"])
    held_rows = s["top_k"] * s["held"] / s["routed"]                     # a token's expected rows on the held experts
    ffn = (held_rows + s["shared"]) * expert_params(config)
    main = 2 * rows * (s["L"] * attention_params(config) + s["lead"] * 3 * s["H"] * s["I"] + (s["L"] - s["lead"]) * ffn)
    module = module_tokens * s["modules"] * (attention_params(config) + ffn + 2 * s["H"] * s["H"])
    head = (2 * rows + module_tokens) * s["H"] * s["V"]
    # two queries a row over the row's context, every caching layer: the record's pages are already the sum over layers
    latent = 2 * s["Hq"] * (2 * s["rank"] + s["rope"]) * record["latent_pages_read"] / record["steps"] * s["page"]
    return 2.0 * (main + module + head + latent)


def round_share(sources: dict, count, peak: str) -> float | None:
    """``count(config, record)`` of a decode round over the chip's ``peak`` a
    second, as a share (%) of the decode chunk program's device time a round
    (``trace_classes.json`` ``modules.decode_chunk``: its median duration, over
    ``decode_chunk`` rounds): the median over the ``decode`` records dispatched
    inside the capture, as the time is a median. None for another configuration,
    without a trace, or where a record lacks a counter."""
    from stats import dispatched_in_capture, median

    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("decode_chunk_s") or not is_pangu(config):
        return None
    records = dispatched_in_capture(sources)
    if records is None:
        return None
    counts = [count(config, t) for t in records if t.get("kind") == "decode"]
    if not counts or any(c is None for c in counts):
        return None
    return 100.0 * (median(counts) / sources["chip"][peak]) / (trace["decode_chunk_s"] / sizes(config)["chunk"])


def drafting(records) -> dict | None:
    """What the drafts of ``records`` (``decode`` and ``mixed``) came to: the
    rounds that verified one, the drafts accepted, the tokens emitted; None
    where none was drafted."""
    recs = [t for t in records or [] if t.get("kind") in ("decode", "mixed") and t.get("mtp_drafted")]
    drafted = sum(t["mtp_drafted"] for t in recs)
    if not drafted:
        return None
    return {"drafted": drafted, "accepted": sum(t.get("mtp_accepted", 0) for t in recs),
            "tokens": sum(t.get("mtp_tokens", 0) for t in recs)}
