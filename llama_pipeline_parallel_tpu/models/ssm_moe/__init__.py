from llama_pipeline_parallel_tpu.models.ssm_moe.config import (  # noqa: F401
    SsmMoEConfig,
)
