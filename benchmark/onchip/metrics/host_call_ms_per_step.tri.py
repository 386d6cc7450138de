"""Per-layer metric ``host_call_ms_per_step.tri``: see readers.host_call_ms_per_step."""
from readers import host_call_ms_per_step as read  # noqa: F401
