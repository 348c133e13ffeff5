"""CLI: ``python -m inference_gateway_tpu.serving`` — run the TPU sidecar."""

from __future__ import annotations

import time

STARTED_NS = time.perf_counter_ns()  # before the imports below: start-up spans count from here

import argparse  # noqa: E402
import asyncio  # noqa: E402

from inference_gateway_tpu.serving.engine import EngineConfig  # noqa: E402
from inference_gateway_tpu.serving.server import serve  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU serving sidecar (OpenAI-compatible)")
    p.add_argument("--model", default="tinyllama-1.1b", help="preset name or local HF checkpoint path")
    p.add_argument("--checkpoint", default=None, help="orbax checkpoint directory to restore")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--max-slots", type=int, default=64)
    p.add_argument("--max-seq-len", type=int, default=2048)
    p.add_argument("--max-prefill-batch", type=int, default=8)
    p.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    p.add_argument("--quantize", default=None, choices=["int8", "int4"],
                   help="weight-only quantization: int8 halves the weight HBM "
                        "stream, int4 (group-128 packed nibbles) quarters it")
    p.add_argument("--attention", default="dense", choices=["dense", "paged"])
    p.add_argument("--page-size", type=int, default=32)
    p.add_argument("--decode-chunk", type=int, default=8)
    p.add_argument("--vision-model", default=None, help="vision tower preset for multimodal")
    p.add_argument("--spec-draft", default=None,
                   help="speculative decoding: llama-family draft model preset/"
                        "path sharing the target's vocab (serving/speculative.py)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens proposed per speculative round")
    p.add_argument("--spec-adaptive", action="store_true",
                   help="n-gram spec only: fall back to the pipelined decode "
                        "loop when acceptance is low, re-probing periodically")
    p.add_argument("--no-mesh", action="store_true", help="disable multi-device sharding")
    p.add_argument("--metrics-push-url", default=None,
                   help="gateway OTLP push endpoint (e.g. http://gateway:8080/v1/metrics)")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="bind jax to this platform: tpu fails at start-up when "
                        "there is no chip, cpu is dev serving with interpreted "
                        "kernels; unset, jax takes a chip if it finds one and "
                        "the CPU otherwise")
    return p


def engine_config(args: argparse.Namespace) -> EngineConfig:
    """The command line's engine: every flag that shapes the weights, the
    cache or the step programs, mapped in this one place (the server's
    own flags, host, port, names and the push URL, are ``main``'s)."""
    return EngineConfig(
        model=args.model,
        checkpoint_path=args.checkpoint,
        max_slots=args.max_slots,
        max_seq_len=args.max_seq_len,
        max_prefill_batch=args.max_prefill_batch,
        dtype=args.dtype,
        use_mesh=not args.no_mesh,
        quantize=args.quantize,
        attention=args.attention,
        page_size=args.page_size,
        decode_chunk=args.decode_chunk,
        vision_model=args.vision_model,
        spec_draft=args.spec_draft,
        spec_k=args.spec_k,
        spec_adaptive=args.spec_adaptive,
    )


def main() -> None:
    args = build_parser().parse_args()

    if args.platform:
        from inference_gateway_tpu.utils.platform import force_platform

        force_platform(args.platform)

    # Multi-host pods: join the jax.distributed world before touching
    # devices (no-op single-host).
    from inference_gateway_tpu.parallel.distributed import initialize_distributed

    initialize_distributed()

    cfg = engine_config(args)
    asyncio.run(serve(cfg, host=args.host, port=args.port, served_model_name=args.served_model_name,
                      metrics_push_url=args.metrics_push_url, started_ns=STARTED_NS))


if __name__ == "__main__":
    main()
