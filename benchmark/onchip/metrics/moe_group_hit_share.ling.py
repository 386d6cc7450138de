"""Per-layer metric ``moe_group_hit_share.ling``: see ling_readers.moe_group_hit_share."""
from ling_readers import moe_group_hit_share as read  # noqa: F401
