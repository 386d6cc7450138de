"""Per-layer metric ``device_idle_share.ouro``: see readers.device_idle_share."""
from readers import device_idle_share as read  # noqa: F401
