"""Per-layer metric ``window_compiles.tri``: see readers.window_compiles."""
from readers import window_compiles as read  # noqa: F401
