"""Encode worker service: the E stage of multimodal E/P/D.

Reference parity: components/src/dynamo/vllm/multimodal_handlers/
encode_worker_handler.py run as its own component. Frontends reach it via
MultimodalPreprocessor (handlers.py).

Usage:
  python -m dynamo_tpu.multimodal --namespace prod --llm-d-model 896
"""

from __future__ import annotations

import argparse
import asyncio

from dynamo_tpu import config
from dynamo_tpu.multimodal.encoder import VisionEncoderConfig
from dynamo_tpu.multimodal.handlers import EncodeWorkerHandler
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.utils.jax_env import (
    configure_compile_cache,
    require_serving_platform,
)
from dynamo_tpu.utils.logging import configure_logging


async def main() -> None:
    parser = argparse.ArgumentParser("dynamo-tpu encode worker (multimodal E stage)")
    parser.add_argument("--namespace", default=config.NAMESPACE.get())
    parser.add_argument("--component", default="encoder")
    parser.add_argument("--endpoint", default="encode")
    parser.add_argument("--clip-model", default=None,
                        help="HF CLIPVisionModel checkpoint directory "
                        "(real weights; overrides the --vit-* shape flags)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--patch-size", type=int, default=32)
    parser.add_argument("--vit-d-model", type=int, default=256)
    parser.add_argument("--vit-layers", type=int, default=2)
    parser.add_argument("--llm-d-model", type=int, required=True,
                        help="target LLM hidden size (embedding projection)")
    args = parser.parse_args()
    if args.image_size % args.patch_size != 0:
        parser.error(
            f"--image-size {args.image_size} must be divisible by "
            f"--patch-size {args.patch_size}"
        )
    n_heads = VisionEncoderConfig.n_heads
    if args.vit_d_model % n_heads != 0:
        parser.error(
            f"--vit-d-model {args.vit_d_model} must be divisible by "
            f"n_heads={n_heads}"
        )

    configure_logging()
    configure_compile_cache()
    require_serving_platform()
    runtime = DistributedRuntime.from_settings()
    if args.clip_model:
        from dynamo_tpu.multimodal.encoder import load_clip_vision

        params, vcfg = load_clip_vision(args.clip_model, args.llm_d_model)
        handler = EncodeWorkerHandler(vcfg, params=params)
        print(f"loaded CLIP vision tower from {args.clip_model}", flush=True)
    else:
        handler = EncodeWorkerHandler(
            VisionEncoderConfig(
                image_size=args.image_size,
                patch_size=args.patch_size,
                d_model=args.vit_d_model,
                n_layers=args.vit_layers,
                out_dim=args.llm_d_model,
            )
        )
    endpoint = (
        runtime.namespace(args.namespace)
        .component(args.component)
        .endpoint(args.endpoint)
    )
    served = await endpoint.serve_endpoint(handler.generate)
    print(
        f"encode worker serving {args.namespace}/{args.component}/{args.endpoint} "
        f"({handler.config.n_patches} tokens/image)",
        flush=True,
    )
    try:
        await asyncio.Event().wait()
    finally:
        await served.shutdown(grace_period=config.GRACE_PERIOD.get())
        await runtime.shutdown(grace_period=config.GRACE_PERIOD.get())


if __name__ == "__main__":
    asyncio.run(main())
