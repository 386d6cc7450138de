"""Per-layer metric ``mfu_pct.lm``: see readers.mfu_pct."""
from readers import mfu_pct as read  # noqa: F401
