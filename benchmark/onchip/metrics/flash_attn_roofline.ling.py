"""Per-layer metric ``flash_attn_roofline.ling``: see ling_readers.flash_attn_roofline."""
from ling_readers import flash_attn_roofline as read  # noqa: F401
