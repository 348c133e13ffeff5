"""Multi-host DCN execution — 2 REAL processes (round-4 verdict next #8).

parallel/distributed.py wires jax.distributed.initialize, but through
round 3 nothing ever ran it beyond the dry run.
This test spawns two actual OS processes, each contributing 2 virtual
CPU devices, initializes the coordination service, builds ONE global
(tp=4) mesh spanning both processes, and runs a sharded llama prefill +
decode step — the collectives cross the process boundary exactly the
way DCN traffic does on a pod (SURVEY.md:418-419).

Both processes must agree with each other AND with a single-process
unsharded reference.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np

_WORKER = r"""
import json, os, sys
import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax

# JAX_PLATFORMS=cpu above is what binds the worker to the CPU; the parent
# exports it too, so a worker can never reach for a chip the parent's
# machine may hold.

import jax.numpy as jnp

sys.path.insert(0, os.environ["REPO_ROOT"])
from inference_gateway_tpu.parallel.distributed import (
    global_mesh, initialize_distributed, process_info)

ok = initialize_distributed()
assert ok, "initialize_distributed returned False under worker env"
info = process_info()
assert info["process_count"] == 2, info
assert info["global_devices"] == 4, info

from jax.sharding import NamedSharding, PartitionSpec as P

from inference_gateway_tpu.models import llama
from inference_gateway_tpu.parallel.sharding import llama_param_specs, named

# tp=4 shards the KV-head axis 4 ways; test-tiny is GQA with 2 kv heads,
# so widen to MHA (4 kv heads) for this geometry.
import dataclasses
cfg = dataclasses.replace(llama.PRESETS["test-tiny"], num_kv_heads=4)
mesh = global_mesh(dp=1, sp=1, tp=4)

host_params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
cache = llama.init_cache(cfg, 1, 32, dtype=jnp.float32)

def put(tree, spec_tree, m):
    def one(x, s):
        sh = NamedSharding(m, s)
        return jax.make_array_from_callback(x.shape, sh, lambda idx: np.asarray(x)[idx])
    return jax.tree.map(one, tree, spec_tree, is_leaf=lambda n: isinstance(n, P))

params = put(host_params, llama_param_specs(cfg), mesh)
cache = put(cache, {"k": P(None, None, None, "tp", None), "v": P(None, None, None, "tp", None)}, mesh)

prompt = [1, 2, 3, 4, 5]
T = len(prompt)
tokens = jnp.asarray([prompt], jnp.int32)
positions = jnp.arange(T, dtype=jnp.int32)[None, :]
lengths = jnp.asarray([T], jnp.int32)

with jax.sharding.set_mesh(mesh):
    logits, cache = llama.forward(params, cfg, tokens, positions, lengths, cache,
                                  mode="prefill", last_only=True)
    # argmax/abs-sum as jitted GLOBAL reductions: the outputs are fully
    # replicated scalars addressable on every process (reading a raw
    # addressable shard would give each process a different tp slice).
    tok1 = int(jax.jit(lambda l: jnp.argmax(l.reshape(-1)))(logits))
    step_logits, cache = llama.forward(
        params, cfg, jnp.asarray([[tok1]], jnp.int32), jnp.asarray([[T]], jnp.int32),
        jnp.asarray([T + 1]), cache, mode="decode")
    tok2 = int(jax.jit(lambda l: jnp.argmax(l.reshape(-1)))(step_logits))
    checksum = float(jax.jit(lambda l: jnp.abs(l).sum())(step_logits))

# Phase 2 — ring attention with the sp axis SPANNING the process
# boundary: mesh (dp=1, sp=2, tp=2) lays sp outermost over the 4 global
# devices, so each sp block lives on a different process and the ring's
# lax.ppermute rotation of KV blocks is genuine cross-host (DCN)
# traffic — the long-context analog of phase 1's tp collectives.
mesh2 = global_mesh(dp=1, sp=2, tp=2)
params2 = put(host_params, llama_param_specs(cfg), mesh2)
T2 = int(os.environ["RING_T2"])
tokens2 = jnp.asarray([list(range(1, T2 + 1))], jnp.int32)
positions2 = jnp.arange(T2, dtype=jnp.int32)[None, :]
lengths2 = jnp.asarray([T2], jnp.int32)
with jax.sharding.set_mesh(mesh2):
    ring_logits, _ = llama.forward(params2, cfg, tokens2, positions2, lengths2,
                                   mode="prefill", ring_mesh=mesh2)
    ring_tok = int(jax.jit(lambda l: jnp.argmax(l[:, -1]))(ring_logits))
    ring_checksum = float(jax.jit(lambda l: jnp.abs(l).sum())(ring_logits))

out = {"pid": info["process_index"], "tok1": tok1, "tok2": tok2,
       "checksum": checksum, "ring_tok": ring_tok,
       "ring_checksum": ring_checksum}
with open(os.environ["OUT_PATH"] + f".{info['process_index']}", "w") as f:
    json.dump(out, f)
print("WORKER_OK", out, flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


RING_T2 = 32


def test_two_process_sharded_prefill_decode(tmp_path):
    port = _free_port()
    out_path = str(tmp_path / "result.json")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for pid in range(2):
        env = dict(os.environ,
                   COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                   NUM_PROCESSES="2", PROCESS_ID=str(pid),
                   REPO_ROOT=repo, OUT_PATH=out_path,
                   JAX_PLATFORMS="cpu", RING_T2=str(RING_T2),
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        env.pop("PYTEST_CURRENT_TEST", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for p in procs:
        try:
            stdout, stderr = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError("multi-host worker timed out")
        assert p.returncode == 0, f"worker failed:\n{stderr[-2000:]}"
        assert "WORKER_OK" in stdout
        outs.append(stdout)

    results = []
    for pid in range(2):
        with open(f"{out_path}.{pid}") as f:
            results.append(json.load(f))
    # Both processes computed the SAME replicated result (the collectives
    # crossed the process boundary and agreed).
    assert results[0]["tok1"] == results[1]["tok1"]
    assert results[0]["tok2"] == results[1]["tok2"]
    np.testing.assert_allclose(results[0]["checksum"], results[1]["checksum"], rtol=1e-5)
    assert results[0]["ring_tok"] == results[1]["ring_tok"]
    np.testing.assert_allclose(results[0]["ring_checksum"], results[1]["ring_checksum"],
                               rtol=1e-5)

    # And it matches the single-process unsharded reference.
    import dataclasses

    import jax
    import jax.numpy as jnp

    from inference_gateway_tpu.models import llama

    cfg = dataclasses.replace(llama.PRESETS["test-tiny"], num_kv_heads=4)
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    cache = llama.init_cache(cfg, 1, 32, dtype=jnp.float32)
    prompt = [1, 2, 3, 4, 5]
    T = len(prompt)
    logits, cache = llama.forward(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        jnp.arange(T, dtype=jnp.int32)[None, :], jnp.asarray([T]), cache,
        mode="prefill", last_only=True)
    ref1 = int(np.asarray(logits).argmax())
    step_logits, _ = llama.forward(
        params, cfg, jnp.asarray([[ref1]], jnp.int32), jnp.asarray([[T]], jnp.int32),
        jnp.asarray([T + 1]), cache, mode="decode")
    ref2 = int(np.asarray(step_logits)[0, 0].argmax())
    assert results[0]["tok1"] == ref1
    assert results[0]["tok2"] == ref2

    # Ring phase: the cross-process sp ring must reproduce the dense
    # single-process prefill's next token.
    T2 = RING_T2
    ring_ref, _ = llama.forward(
        params, cfg, jnp.asarray([list(range(1, T2 + 1))], jnp.int32),
        jnp.arange(T2, dtype=jnp.int32)[None, :], jnp.asarray([T2]), None,
        mode="prefill")
    assert results[0]["ring_tok"] == int(np.asarray(ring_ref)[0, -1].argmax())
