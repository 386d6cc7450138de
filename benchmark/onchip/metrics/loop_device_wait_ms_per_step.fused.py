"""Per-layer metric ``loop_device_wait_ms_per_step.fused``: see program_spans.loop_device_wait_ms_per_step."""
from program_spans import loop_device_wait_ms_per_step as read  # noqa: F401
