"""Per-layer metric ``window_compiles.sol``: see readers.window_compiles."""
from readers import window_compiles as read  # noqa: F401
