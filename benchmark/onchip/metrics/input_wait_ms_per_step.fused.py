"""Per-layer metric ``input_wait_ms_per_step.fused``: see readers.input_wait_ms_per_step."""
from readers import input_wait_ms_per_step as read  # noqa: F401
