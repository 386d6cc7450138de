"""Per-layer metric ``moe_load_max_over_mean.tri``: see moe_readers.moe_load_max_over_mean."""
from moe_readers import moe_load_max_over_mean as read  # noqa: F401
