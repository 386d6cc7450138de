"""Per-layer metric ``moe_pairs_per_token.tri``: see moe_readers.moe_pairs_per_token."""
from moe_readers import moe_pairs_per_token as read  # noqa: F401
