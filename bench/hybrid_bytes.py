"""Bytes ONE decode step of a hybrid configuration must read, and where its
state-space operations are found in a trace: what the ``hybrid_*`` and ``ssm_*``
readers share. Sizes come from the configuration's file alone, never from the
program.

A hybrid configuration (``mamba_d_state`` in its file; the layer kinds are
those of ``references/sambay_diff.py``: with N layers, P = N / 2 pairs and the
middle pair M = P / 2) holds, and a decode step reads once:

- the MLP of every layer (3 x H x I), the Mamba mixer of M + 1 layers (in
  H x 2 Di, out Di x H at the weight type; W_x Di x (R + 2 N) and W_dt R x Di at
  the activation type), self attention of M + 1 layers (H x (H + 2 kv) and
  H x H), the gated memory unit (2 x H x Di) and cross attention (2 x H x H) of
  P - M - 1 layers each, and the tied head, the embedding at the activation type;
- for every row of context c: M windowed layers x min(c, window) and (1 full +
  P - M - 1 cross readers) x c cached tokens, each 2 x kv heads x head size x
  the cache type (the cross layers read the full layer's pages again);
- the row's state, read and written: (M + 1) x (Di x N + (K - 1) x Di) float32 x 2.

Scales, norms, biases, lambdas, the convolution and A / D are under 0.1% and
left out, which makes a share a lower bound.

The state-space operations' device time is read off the trace file by a child
(``python bench/hybrid_bytes.py <file.xplane.pb> <pattern>``,
``JAX_PLATFORMS=cpu``). An event of the operation line is the whole HLO
instruction and carries no more than its device offset and duration (my chip
run, PR 32: the ``jax.named_scope`` path of an operation, ``ssm.conv`` /
``ssm.scan`` in the program, is not in the profile), so the operations are
known by what only they touch: the state store's own shapes, from the
configuration's file, as a result or an operand: a layer's states
``f32[slots,Di,N]``, one state ``f32[Di,N]`` (the mixed step's token loop), the
convolution's tails ``f32[slots,K-1,Di]`` and window ``f32[slots,K,Di]``; or a
kernel named ``ssm_*``. The stacked store of all layers (a leading axis more)
does not match, so the layer loops that only carry it are not counted. The
union of the matching intervals, so a loop and its body are not both counted.
The elementwise part of the mixed step's convolution (over [tokens, Di] arrays
like many others) is not seen: a lower bound, by under a tenth of the scan's time.
"""

from __future__ import annotations

import functools
import json
import os
import re
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def ssm_pattern(config: dict) -> str:
    """What an operation of the convolution or the scan holds in its HLO text."""
    s = sizes(config)
    slots = int(config["deployment_sizes"]["max_slots"])
    shapes = [(slots, s["Di"], s["N"]), (s["Di"], s["N"]), (slots, s["K"] - 1, s["Di"]), (slots, s["K"], s["Di"])]
    return r"^%?ssm_\w+|f32\[(" + "|".join(",".join(map(str, sh)) for sh in shapes) + r")\]"


def is_hybrid(config: dict) -> bool:
    return "mamba_d_state" in config


def sizes(config: dict) -> dict:
    H, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    pairs = int(config["num_hidden_layers"]) // 2
    dep = config["deployment_sizes"]
    return {"H": H, "I": int(config["intermediate_size"]), "L": 2 * pairs, "V": int(config["vocab_size"]),
            "kv": int(config["num_key_value_heads"]) * (H // heads), "window": int(config["sliding_window"]),
            "Di": int(config["mamba_expand"]) * H, "N": int(config["mamba_d_state"]),
            "K": int(config["mamba_d_conv"]), "R": int(config["mamba_dt_rank"]),
            "mamba": pairs // 2 + 1, "windowed": pairs // 2, "cross": pairs - pairs // 2 - 1,
            "w": DTYPE_BYTES[dep["weight_dtype"]], "a": DTYPE_BYTES[dep["activation_dtype"]],
            "c": DTYPE_BYTES[dep["kv_dtype"]], "s": DTYPE_BYTES[dep.get("state_dtype", "float32")]}


def weight_bytes_per_step(config: dict) -> int:
    """The matrices and the tied head as the program holds them."""
    s = sizes(config)
    H, I, Di = s["H"], s["I"], s["Di"]
    quantised = (s["L"] * 3 * H * I + s["mamba"] * (H * 2 * Di + Di * H + H * (H + 2 * s["kv"]) + H * H)
                 + s["cross"] * (2 * H * Di + 2 * H * H))
    plain = s["mamba"] * (Di * (s["R"] + 2 * s["N"]) + s["R"] * Di) + s["V"] * H
    return quantised * s["w"] + plain * s["a"]


def cache_token_bytes(config: dict) -> int:
    """One cached token of ONE layer: keys and values."""
    s = sizes(config)
    return 2 * s["kv"] * s["c"]


def state_bytes_per_row(config: dict) -> int:
    """A row's recurrent state over all Mamba layers, held once."""
    s = sizes(config)
    return s["mamba"] * s["Di"] * (s["N"] + s["K"] - 1) * s["s"]



def live_rows(requests, t0: float, t1: float, window: int) -> tuple[float, float, float]:
    """(mean rows decoding, mean of the sum of their contexts, mean of the sum
    of min(context, window)) over the monotonic interval [t0, t1], from the
    client's own records as ``stats.mean_live_context`` reads them: a request
    decodes from its first content frame to its last and grows a token a token."""
    if t1 <= t0:
        return 0.0, 0.0, 0.0
    rows = whole = seen = 0.0
    for r in requests:
        if r.first_content_t is None or len(r.usages) != 1:
            continue
        first, last = r.first_content_t, max(r.last_content_t, r.first_content_t + 1e-9)
        u, v = max(first, t0), min(last, t1)
        if v <= u:
            continue
        rate = r.content_chars / (last - first)
        c0 = r.usages[0]["prompt_tokens"] + rate * (u - first)
        c1 = c0 + rate * (v - u)
        rows += v - u
        whole += (v - u) * (c0 + c1) / 2
        if c1 <= window or rate <= 0:
            seen += (v - u) * min((c0 + c1) / 2, window)
        elif c0 >= window:
            seen += (v - u) * window
        else:  # crosses the window inside the interval
            t_cross = (window - c0) / rate
            seen += t_cross * (c0 + window) / 2 + (v - u - t_cross) * window
    span = t1 - t0
    return rows / span, whole / span, seen / span


def step_bytes(config: dict, rows: float, contexts: float, windowed_contexts: float) -> float:
    """One decode step with ``rows`` rows whose contexts sum to ``contexts``
    and whose min(context, window) sum to ``windowed_contexts``."""
    s = sizes(config)
    tokens = s["windowed"] * windowed_contexts + (1 + s["cross"]) * contexts
    return weight_bytes_per_step(config) + tokens * cache_token_bytes(config) + rows * 2 * state_bytes_per_row(config)


def row_bytes(config: dict, context: float) -> float:
    """What one decoding row of ``context`` tokens adds to a step's bytes."""
    return step_bytes(config, 1, context, min(context, sizes(config)["window"])) - weight_bytes_per_step(config)


def _union_s(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def read_file(path: str, pattern: str) -> dict:
    """Seconds the operations whose HLO text ``pattern`` finds ran (union of
    their intervals on a device's operation line), averaged over the devices,
    and how many events."""
    from jax.profiler import ProfileData

    ssm_op = re.compile(pattern)
    spec = json.loads((BENCH / "trace_classes.json").read_text())
    dev, op_line = re.compile(spec["device_plane"]), re.compile(spec["op_line"])
    per_device, events = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not dev.search(plane.name):
            continue
        hits, seen = [], False
        for line in plane.lines:
            if not op_line.search(line.name):
                continue
            for ev in line.events:
                seen = True
                if ssm_op.search(ev.name):
                    hits.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
        if seen:
            per_device.append(_union_s(hits))
            events += len(hits)
    n = max(len(per_device), 1)
    return {"ssm_s": sum(per_device) / n, "ssm_events": events}


@functools.lru_cache(maxsize=4)
def _read_in_child(path: str, pattern: str) -> dict:
    done = subprocess.run([sys.executable, str(Path(__file__).resolve()), path, pattern], capture_output=True, text=True,
                          timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if done.returncode != 0:
        raise RuntimeError(f"bench/hybrid_bytes.py could not read {path}: {done.stderr[-2000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def ssm_times(trace: dict, config: dict) -> dict | None:
    """``read_file`` of the trace the run reduced (its ``file``), once a run;
    None for a configuration without a state, or where the trace holds no
    such operation: a program without the layer. A hand-built trace brings
    its own ``ssm_ops``."""
    if not is_hybrid(config):
        return None
    times = trace.get("ssm_ops") or (_read_in_child(trace["file"], ssm_pattern(config)) if trace.get("file") else None)
    return times if times and times["ssm_events"] > 0 else None


if __name__ == "__main__":
    print(json.dumps(read_file(sys.argv[1], sys.argv[2])))
