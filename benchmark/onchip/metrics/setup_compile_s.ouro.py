"""Per-layer metric ``setup_compile_s.ouro``: see readers.setup_compile_s."""
from readers import setup_compile_s as read  # noqa: F401
