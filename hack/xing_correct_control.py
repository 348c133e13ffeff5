#!/usr/bin/env python3
"""By hand, on the chip: controls of ``xing4.0-29b-a4b.long-context``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 47):
(a) the hyper-connections replaced by one plain residual stream, x + F(norm x):
what a program whose residual seam fell back to the shared skeleton's sum would
compute; (b) the rotary part q_rope . k_rope dropped from the attention's
scores: what a program that cached or read the latent's 512 values and lost the
row's last 64 would compute; (c) every matrix re-rounded to int4 in groups of
128: the step one precision below the int8 the configuration states. A control
has to come out NOT ok (exit code 1 from the child): if it reads ok, the
comparison cannot see the mechanism. ``sound`` runs the file unchanged, which
has to read ok.

    python3 hack/xing_correct_control.py <run's out_dir> [--controls sound a b c] [--rehearse-cpu]
    python3 hack/xing_correct_control.py --seeding [--rehearse-cpu]

``--seeding`` builds the tree as the reference child does and runs the
reference over 96 seeded tokens, a layer at a time, and prints what the seeding
(``ops/quant._xing_std``) makes of the new mechanisms: how far H_res lies from
the identity and from the uniform 1/n (mean absolute difference an entry, both
blocks of every layer), the spread of H_pre and H_post, and how many DISTINCT
experts the tokens chose in each expert layer (more than ``num_experts_per_tok``,
or the router sends every token the same way).
"""
import json
import sys
from pathlib import Path

from trinity_correct_control import run

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "xing4.0-29b-a4b-int8.json"
CONTROLS = {
    "sound": {},
    "a": {"assumed_mechanisms": {"residual_mixing": False}},
    "b": {"assumed_mechanisms": {"rope_scores": False}},
    "c": {"assumed_mechanisms": {"matrix_rounding": "int4"}},
}


def seeding(rehearse: bool) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "bench")]
    import reference

    from inference_gateway_tpu.utils.platform import force_platform

    force_platform("cpu" if rehearse else "tpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops.quant import QTensor
    from inference_gateway_tpu.serving.engine import Engine

    config = json.loads(CONFIG.read_text())
    run_cfg = config["rehearsal"] if rehearse else config
    model = run_cfg["model"] if rehearse else config
    ref = reference.load_reference(config["reference"])
    sizes = ref.sizes(model)
    engine = Engine(reference.engine_config(run_cfg["preset"], run_cfg["sidecar_args"]))
    layers = engine.params["layers"]
    f32 = lambda w: (w.q.astype(jnp.float32) * w.scale.astype(jnp.float32)) if isinstance(w, QTensor) else w.astype(jnp.float32)
    stacked = {n: layers[n] for n in ref.LEAVES}   # an ARGUMENT: closed over, the tree is a constant of the executable
    layer_f32 = jax.jit(lambda stacked, i: {n: f32(jax.tree.map(lambda a: a[jnp.minimum(i, a.shape[0] - 1)], stacked[n]))
                                             for n in stacked})
    layer = lambda i: layer_f32(stacked, i)
    ids = jnp.asarray(np.random.default_rng(47).integers(32, 127, size=96))
    n, eye = sizes["hc_mult"], jnp.eye(sizes["hc_mult"])
    lead, eps = sizes["first_k_dense_replace"], sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = engine.params["embed"][ids].astype(jnp.float32)
        X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
        positions = jnp.arange(len(ids))
        for i in range(sizes["num_hidden_layers"]):
            names = ref.DENSE_LEAVES if i < lead else ref.EXPERT_LEAVES
            w = {name.removeprefix("dense_") if i < lead else name: a
                 for name, a in layer(i if i < lead else i - lead).items() if name in names}
            row = {"layer": i}
            for block in ("attn", "ffn"):
                pre, post, res = ref.hyper_maps(X, w[f"hc_{block}_phi"], w[f"hc_{block}_alpha"], w[f"hc_{block}_bias"], sizes)
                row[block] = {"res_from_identity": round(float(jnp.mean(jnp.abs(res - eye))), 3),
                              "res_from_uniform": round(float(jnp.mean(jnp.abs(res - 1.0 / n))), 3),
                              "rows_cols_off_one": float(jnp.maximum(jnp.abs(res.sum(1) - 1).max(), jnp.abs(res.sum(2) - 1).max())),
                              "pre": [round(float(v), 2) for v in (pre.min(), pre.max())],
                              "post": [round(float(v), 2) for v in (post.min(), post.max())]}
                if block == "ffn" and i >= lead:
                    h = ref.dense.rms_norm(jnp.einsum("tn,tnc->tc", pre, X), w["moe_norm"], eps)
                    _, idx = ref.route(h, w, sizes)
                    row["distinct_experts"] = int(jnp.unique(idx).size)
                if block == "attn":
                    X = ref.attention_block(X, w, positions, sizes)
            X = jax.block_until_ready(ref.ffn_block(X, w, sizes, i < lead))
            row["stream_rms"] = round(float(jnp.sqrt(jnp.mean(jnp.square(X)))), 2)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    if "--seeding" in sys.argv[1:]:
        sys.exit(seeding("--rehearse-cpu" in sys.argv[1:]))
    sys.exit(run(CONFIG, CONTROLS, ["a", "b"], __doc__))
