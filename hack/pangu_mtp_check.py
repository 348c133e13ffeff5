#!/usr/bin/env python3
"""By hand, on the chip: the next-token module of ``openpangu-ultra-moe-718b-int8``
against the reference's module, and a round's SECOND token against the reference.

The cell's ``correct`` compares served logprobs of greedy probes with the plain
reference's full forward; at seeded weights few drafts are accepted, so it rarely
sees a token that came from a row's second query, and never the module's own
output. This builds the engine ONCE at the published widths (the cell's sidecar
arguments, four slots in place of 64 so that the reference's float32 views have
room) and checks, under ``bench/reference.py``'s tolerances (0.45 nats the
largest, 0.12 the mean: the same int8 weights on both sides, bf16 activations
against float32, as for the model's own logits; the module adds one block and
two norms to the same arithmetic):

(i)  the module's draft distribution (the strip the round before left on the
     device, at temperature 1: its 64 largest, renormalised) at >= 40 positions,
     a prompt's last and decoded ones, against ``mtp_mla_moe.module_forward``
     over the same tokens: the logprobs of the strip's entries of probability
     >= 0.01, served against reference;
(ii) greedy rounds whose drafts are ACCEPTED: a first pass records each row's
     greedy continuation, a second pass over the same prompts feeds it back as
     the drafts (the device's draft entry is overwritten before every round),
     through mixed steps and through decode chunks; a round then emits two
     tokens, and the second's served logprob (the row's second query: the pair
     launch, the write at p + 1, the position moved by two on the device) is
     compared with the reference's full forward. (In bf16 a near-tie may fall
     the other way in a round of two queries than it did in the recorded round
     of one; such a row's later drafts are not known, so it is fed a token that
     is always refused from there on and its later rounds are not compared.)

    python3 hack/pangu_mtp_check.py [--rehearse-cpu] [--rounds 10]

Prints one line of JSON a check and exits 0 when both are inside the tolerances.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "bench")]
CONFIG = ROOT / "bench" / "configs" / "openpangu-ultra-moe-718b-int8.json"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--rounds", type=int, default=16)
    args = ap.parse_args()
    import reference

    from inference_gateway_tpu.utils.platform import configure_compile_cache, force_platform

    force_platform("cpu" if args.rehearse_cpu else "tpu")
    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops.quant import QTensor
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow

    config = json.loads(CONFIG.read_text())
    run_cfg = config["rehearsal"] if args.rehearse_cpu else config
    model = run_cfg["model"] if args.rehearse_cpu else config
    ref = reference.load_reference(config["reference"])
    sizes = ref.sizes(model)
    cli = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    S = 4
    eng = Engine(EngineConfig(model=run_cfg["preset"], quantize=cli["--quantize"], dtype=cli["--dtype"], attention="paged",
                              page_size=int(cli["--page-size"]), max_slots=S, max_seq_len=int(cli["--max-seq-len"]),
                              use_mesh=False, mixed_step=True, decode_chunk=int(run_cfg["deployment_sizes"]["decode_chunk"])))
    bias_ids, bias_value = config["logit_bias"]["ids"], float(config["logit_bias"]["value"])
    for slot in range(S):
        eng.structured_register(slot, None, {i: bias_value for i in bias_ids})
    bias = np.zeros((sizes["vocab_size"],), np.float32)
    bias[bias_ids] = bias_value
    params, layers = eng.params, eng.params["layers"]
    f32 = lambda w: (w.q.astype(jnp.float32) * w.scale.astype(jnp.float32)) if isinstance(w, QTensor) else w.astype(jnp.float32)
    stacked = {n: layers[n] for n in ref.LEAVES}   # an ARGUMENT: closed over, the tree is a constant of the executable
    layer_f32 = jax.jit(lambda stacked, i: {n: f32(jax.tree.map(lambda a: a[i], stacked[n])) for n in stacked})
    layer = lambda i: jax.block_until_ready(layer_f32(stacked, i))
    module_f32 = jax.jit(lambda mp: {**{n: f32(jax.tree.map(lambda a: a[0], mp["layers"][n])) for n in ref.EXPERT_LEAVES},
                                     **{n: f32(mp[n]) for n in ref.MODULE_LEAVES}})
    head, final_norm = f32(params["lm_head"]), f32(params["final_norm"])

    def references(ids):
        """(log-softmax of the model's biased logits [T, V], the module's biased logits [T - 1, V])."""
        x = ref.stream(ids, params["embed"], layer, sizes)
        logits = ref.forward(ids, params["embed"], layer, final_norm, head, sizes)[:]
        module = jax.block_until_ready(module_f32(params["mtp"]))
        drafts = ref.module_forward(ids, x, params["embed"], module, head, sizes)[:]
        del module
        return np.asarray(jax.nn.log_softmax(logits + bias[None, :], axis=-1)), np.asarray(drafts) + bias[None, :]

    rng = np.random.default_rng(56)
    lengths = [24, 40, 64, 90] if args.rehearse_cpu else [40, 136, 264, 400]
    prompts = [[int(config["tokenizer"]["bos_id"])] + [int(t) for t in rng.integers(32, 127, size=n - 1)] for n in lengths]

    def admit(temp):
        """Every prompt into its slot, a mixed step each; returns the first tokens."""
        first = []
        for slot, ids in enumerate(prompts):
            h = eng.mixed_step_submit([MixedRow(slot=slot, token_ids=ids, start=0, kind="prefill", temp=temp)])
            toks, _ = eng.mixed_step_fetch(h)
            first.append(int(toks[0, slot]))
        return first

    def strips():
        mtp = eng._mtp
        return np.asarray(mtp["qi"]), np.asarray(mtp["qp"])

    def round_(seqs, temp, mixed):
        """One round of every row; returns (tokens, logprobs, counts) by slot."""
        if mixed:
            h = eng.mixed_step_submit([MixedRow(slot=s, token_ids=[0, 0], start=len(seqs[s]) - 1, kind="decode", temp=temp)
                                       for s in range(S)])
            toks, lps = eng.mixed_step_fetch(h)
            return toks, lps, h.counts
        positions = np.asarray([len(seqs[s]) - 1 for s in range(S)], np.int32)
        h = eng.decode_chunk_submit(np.zeros((S,), np.int32), positions, np.ones((S,), bool), np.full((S,), temp, np.float32),
                                    np.ones((S,), np.float32), n_steps=1)
        toks, lps = eng.decode_chunk_fetch(h)
        return toks, lps, h.counts[0]

    def release():
        for slot in range(S):
            eng.release_slot(slot)      # (its bias row goes with it)
            eng.structured_register(slot, None, {i: bias_value for i in bias_ids})

    # -- (i) the module's strips at temperature 1 --------------------------------
    first = admit(1.0)
    seqs = [list(p) + [t] for p, t in zip(prompts, first)]
    seen = {s: [(len(seqs[s]) - 2, *[a[s] for a in strips()])] for s in range(S)}   # (module row, ids, probabilities)
    accepted = 0
    for r in range(args.rounds):
        toks, _, counts = round_(seqs, 1.0, mixed=r % 2 == 0)
        for s in range(S):
            seqs[s] += [int(t) for t in toks[:counts[s], s]]
            accepted += int(counts[s] == 2)
        qi, qp = strips()
        for s in range(S):
            seen[s].append((len(seqs[s]) - 2, qi[s], qp[s]))
    release()
    diffs = []
    for s in range(S):
        _, drafts = references(seqs[s])
        for at, qi, qp in seen[s]:
            want = np.asarray(jax.nn.log_softmax(jnp.asarray(drafts[at][qi])))
            keep = qp >= 0.01
            diffs.append(np.abs(np.log(qp[keep]) - want[keep]))
    positions = len(diffs)
    diffs = np.concatenate(diffs)
    check_i = {"check": "i_module_draft_logprobs", "positions": positions, "comparisons": int(diffs.size),
               "max_abs_diff": float(diffs.max()), "mean_abs_diff": float(diffs.mean()),
               "accepted_at_temperature_1": accepted, "rounds": args.rounds * S}
    print(json.dumps(check_i), flush=True)

    # -- (ii) every draft accepted: the second token of a round ------------------
    first = admit(0.0)
    greedy = [list(p) + [t] for p, t in zip(prompts, first)]
    accepted_own = 0
    while min(len(g) - len(p) for g, p in zip(greedy, prompts)) < 2 * args.rounds + 2:
        toks, _, counts = round_(greedy, 0.0, mixed=True)
        for s in range(S):
            greedy[s] += [int(t) for t in toks[:counts[s], s]]
            accepted_own += int(counts[s] == 2)
    release()
    admit(0.0)
    seqs = [list(p) + [g[len(p)]] for p, g in zip(prompts, greedy)]
    # A row follows its recorded stream until bf16's rounding of a near-tie makes a round of two queries
    # choose another token than the round of one did (the two are different programs' sums); from there
    # its drafts are no longer known to be the argmax, and it is fed a token that is always refused.
    second, emitted_two, follows = {s: [] for s in range(S)}, 0, [True] * S
    k = eng._mtp["qp"].shape[1]
    for r in range(args.rounds):
        draft = np.asarray([greedy[s][len(seqs[s])] if follows[s] else 0 for s in range(S)], np.int32)
        with eng._lock:
            eng._mtp = {**eng._mtp, "draft": jnp.asarray(draft), "qp": jnp.zeros((S, k), jnp.float32).at[:, 0].set(1.0),
                        "qi": jnp.zeros((S, k), jnp.int32).at[:, 0].set(jnp.asarray(draft))}
        toks, lps, counts = round_(seqs, 0.0, mixed=r % 2 == 0)
        for s in range(S):
            if counts[s] == 2:
                emitted_two += 1
                second[s].append((len(seqs[s]) + 1, int(toks[1, s]), float(lps[1, s])))   # (its position, token, logprob)
            seqs[s] += [int(t) for t in toks[:counts[s], s]]
            follows[s] = follows[s] and seqs[s] == greedy[s][:len(seqs[s])]
    release()
    diffs = []
    for s in range(S):
        logp, _ = references(seqs[s])
        diffs += [abs(logp[at - 1, tok] - lp) for at, tok, lp in second[s]]
    diffs = np.asarray(diffs)
    check_ii = {"check": "ii_second_tokens_of_accepted_rounds", "rounds": args.rounds * S, "emitted_two": emitted_two,
                "rows_that_followed_their_recorded_stream_to_the_end": sum(follows),
                "max_abs_diff": float(diffs.max()), "mean_abs_diff": float(diffs.mean()),
                "accepted_of_its_own_drafts_at_temperature_0": accepted_own}
    print(json.dumps(check_ii), flush=True)
    ok = all(c["max_abs_diff"] <= reference.TOLERANCE_MAX_NATS and c["mean_abs_diff"] <= reference.TOLERANCE_MEAN_NATS
             for c in (check_i, check_ii)) and positions >= 40 and emitted_two >= 0.5 * args.rounds * S
    print(json.dumps({"ok": ok, "tolerance_max_nats": reference.TOLERANCE_MAX_NATS,
                      "tolerance_mean_nats": reference.TOLERANCE_MEAN_NATS, "platform": jax.devices()[0].platform,
                      "peak_bytes": [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
