from llama_pipeline_parallel_tpu.models.window_moe.config import (  # noqa: F401
    WindowMoEConfig,
)
