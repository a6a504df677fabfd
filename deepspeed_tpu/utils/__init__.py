from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu.utils.memory import see_memory_usage
from deepspeed_tpu.utils.tree import (
    tree_size_bytes,
    tree_num_params,
    tree_cast,
    tree_zeros_like,
)
