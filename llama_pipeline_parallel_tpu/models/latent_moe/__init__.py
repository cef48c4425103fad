from llama_pipeline_parallel_tpu.models.latent_moe.config import (  # noqa: F401
    LatentMoEConfig,
)
