"""Per-layer metric ``flash_tiles_visited_share.tri``: see tri_readers.flash_tiles_visited_share."""
from tri_readers import flash_tiles_visited_share as read  # noqa: F401
