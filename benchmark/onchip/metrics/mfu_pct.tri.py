"""Per-layer metric ``mfu_pct.tri``: see moe_readers.mfu_pct."""
from moe_readers import mfu_pct as read  # noqa: F401
