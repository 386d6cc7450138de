"""Per-layer metric ``flash_attn_roofline.ouro``: see tri_readers.flash_attn_roofline."""
from tri_readers import flash_attn_roofline as read  # noqa: F401
