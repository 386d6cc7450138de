"""Per-layer metric ``loop_exit_pass_mean.ouro``: see ouro_readers.loop_exit_pass_mean."""
from ouro_readers import loop_exit_pass_mean as read  # noqa: F401
