"""Per-layer metric ``moe_blocks_per_layer_step.glm``: see moe_readers.moe_blocks_per_layer_step."""
from moe_readers import moe_blocks_per_layer_step as read  # noqa: F401
