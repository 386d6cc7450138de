"""Per-layer metric ``device_busy_ms_per_step.ouro``: see readers.device_busy_ms_per_step."""
from readers import device_busy_ms_per_step as read  # noqa: F401
