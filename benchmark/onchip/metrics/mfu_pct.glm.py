"""Per-layer metric ``mfu_pct.glm``: see moe_readers.mfu_pct."""
from moe_readers import mfu_pct as read  # noqa: F401
