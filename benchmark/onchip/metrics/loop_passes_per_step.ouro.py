"""Per-layer metric ``loop_passes_per_step.ouro``: see ouro_readers.loop_passes_per_step."""
from ouro_readers import loop_passes_per_step as read  # noqa: F401
