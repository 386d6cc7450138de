"""Per-layer metric ``setup_compile_s.tri``: see readers.setup_compile_s."""
from readers import setup_compile_s as read  # noqa: F401
