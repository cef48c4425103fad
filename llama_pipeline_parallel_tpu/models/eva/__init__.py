from llama_pipeline_parallel_tpu.models.eva.config import (  # noqa: F401
    EvaConfig,
)
