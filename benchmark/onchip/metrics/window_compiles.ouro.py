"""Per-layer metric ``window_compiles.ouro``: see readers.window_compiles."""
from readers import window_compiles as read  # noqa: F401
