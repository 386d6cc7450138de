"""Per-layer metric ``flash_attn_roofline.glm``: see moe_readers.flash_attn_roofline."""
from moe_readers import flash_attn_roofline as read  # noqa: F401
