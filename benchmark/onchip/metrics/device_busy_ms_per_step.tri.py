"""Per-layer metric ``device_busy_ms_per_step.tri``: see readers.device_busy_ms_per_step."""
from readers import device_busy_ms_per_step as read  # noqa: F401
